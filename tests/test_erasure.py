"""Erasure streaming-layer tests, mirroring the reference's grid:
cmd/erasure-encode_test.go (offline disks), cmd/erasure-decode_test.go
(drives down, corrupted shards), cmd/erasure-heal_test.go (heal roundtrip),
plus ShardSize/ShardFileSize math checks against cmd/erasure-coding.go."""
import io

import numpy as np
import pytest

from minio_tpu.erasure import (Erasure, BitrotAlgorithm, new_bitrot_writer,
                               new_bitrot_reader, bitrot_shard_file_size)
from minio_tpu.erasure.bitrot import bitrot_logical_size
from minio_tpu.erasure.streaming import (BufferSink, BufferSource,
                                         erasure_encode, erasure_decode,
                                         erasure_heal)
from minio_tpu.utils import errors

ALGO = BitrotAlgorithm.BLAKE2B256S


def rng_bytes(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


def encode_to_buffers(k, m, block_size, data, offline=()):
    """Encode data through bitrot writers into in-memory shard files."""
    er = Erasure(k, m, block_size)
    sinks = [BufferSink() for _ in range(k + m)]
    shard_size = er.shard_size()
    writers = [None if i in offline else
               new_bitrot_writer(sinks[i], ALGO, shard_size)
               for i in range(k + m)]
    quorum = k + 1 if k == m else k
    n = erasure_encode(er, io.BytesIO(data), writers, quorum)
    assert n == len(data)
    for w in writers:
        if w is not None:
            w.close()
    return er, sinks


def readers_from(sinks, er, total_length, drop=()):
    shard_size = er.shard_size()
    till = er.shard_file_size(total_length)
    out = []
    for i, s in enumerate(sinks):
        if i in drop or not s.closed:
            out.append(None)
        else:
            out.append(new_bitrot_reader(
                BufferSource(s.getvalue()), ALGO, till, shard_size))
    return out


GRID = [
    (2, 2, 64 << 10, 1 << 20),
    (4, 2, 1 << 20, 3 << 20),
    (8, 4, 1 << 20, (4 << 20) + 123457),
    (16, 4, 1 << 20, 2 << 20),
    (5, 3, 1 << 20, 1 << 20),  # k not a power of two, odd shard sizes
]


@pytest.mark.parametrize("k,m,bs,size", GRID)
def test_encode_decode_roundtrip(k, m, bs, size):
    data = rng_bytes(size, seed=k * 31 + m)
    er, sinks = encode_to_buffers(k, m, bs, data)
    # verify on-disk shard file sizes match reference math
    for s in sinks:
        assert len(s.getvalue()) == bitrot_shard_file_size(
            er.shard_file_size(size), er.shard_size(), ALGO)
    out = BufferSink()
    stats = erasure_decode(er, out, readers_from(sinks, er, size), 0, size, size)
    assert out.getvalue() == data
    assert stats.bytes_written == size


@pytest.mark.parametrize("k,m,bs,size", GRID)
def test_decode_with_drives_down(k, m, bs, size):
    data = rng_bytes(size, seed=1)
    er, sinks = encode_to_buffers(k, m, bs, data)
    # drop up to m shards (mix of data+parity)
    drop = tuple(range(0, m, 2)) + tuple(range(k, k + (m + 1) // 2))
    drop = drop[:m]
    out = BufferSink()
    erasure_decode(er, out, readers_from(sinks, er, size, drop=drop),
                   0, size, size)
    assert out.getvalue() == data


def test_decode_insufficient_shards():
    k, m, bs, size = 4, 2, 1 << 20, 2 << 20
    data = rng_bytes(size)
    er, sinks = encode_to_buffers(k, m, bs, data)
    drop = (0, 1, 4)  # m+1 drives down
    out = BufferSink()
    with pytest.raises(errors.StorageError):
        erasure_decode(er, out, readers_from(sinks, er, size, drop=drop),
                       0, size, size)


def test_decode_range_reads():
    k, m, bs = 4, 2, 1 << 20
    size = (3 << 20) + 789
    data = rng_bytes(size, seed=7)
    er, sinks = encode_to_buffers(k, m, bs, data)
    for off, ln in [(0, 100), (size - 100, 100), (bs - 3, 7),
                    (bs, bs), ((1 << 20) + 17, (1 << 20) + 100), (size, 0),
                    (123, 0)]:
        out = BufferSink()
        erasure_decode(er, out, readers_from(sinks, er, size), off, ln, size)
        assert out.getvalue() == data[off: off + ln], (off, ln)


def test_decode_detects_bitrot_and_reconstructs():
    k, m, bs, size = 4, 2, 1 << 20, 2 << 20
    data = rng_bytes(size, seed=3)
    er, sinks = encode_to_buffers(k, m, bs, data)
    # corrupt one byte mid-chunk in shard 1
    blob = bytearray(sinks[1].getvalue())
    blob[len(blob) // 2] ^= 0xFF
    sinks[1].buf = io.BytesIO(blob)

    out = BufferSink()
    stats = erasure_decode(er, out, readers_from(sinks, er, size), 0, size, size)
    assert out.getvalue() == data
    # the corrupted reader must be flagged for heal-on-read
    assert isinstance(stats.errs[1], errors.FileCorrupt)


def test_encode_with_offline_disks_quorum():
    k, m, bs, size = 4, 2, 1 << 20, 1 << 20
    data = rng_bytes(size, seed=9)
    # m offline: still meets write quorum k
    er, sinks = encode_to_buffers(k, m, bs, data, offline=(1, 5))
    out = BufferSink()
    erasure_decode(er, out, readers_from(sinks, er, size), 0, size, size)
    assert out.getvalue() == data
    # too many offline: write quorum failure
    with pytest.raises(errors.StorageError):
        encode_to_buffers(k, m, bs, data, offline=(0, 1, 4))


def test_heal_roundtrip():
    """cmd/erasure-heal_test.go analogue: wipe shards, heal, verify."""
    k, m, bs = 8, 4, 1 << 20
    size = (2 << 20) + 4321
    data = rng_bytes(size, seed=11)
    er, sinks = encode_to_buffers(k, m, bs, data)
    wiped = (2, 9, 11)
    readers = readers_from(sinks, er, size, drop=wiped)
    heal_sinks = {i: BufferSink() for i in wiped}
    writers = [None] * (k + m)
    for i in wiped:
        writers[i] = new_bitrot_writer(heal_sinks[i], ALGO, er.shard_size())
    erasure_heal(er, writers, readers, size)
    for i in wiped:
        assert heal_sinks[i].getvalue() == sinks[i].getvalue()
    # decode reading ONLY from healed shards + minimum others
    drop = tuple(j for j in range(k + m) if j not in wiped)[:m]
    merged = list(sinks)
    for i in wiped:
        merged[i] = heal_sinks[i]
    out = BufferSink()
    erasure_decode(er, out, readers_from(merged, er, size, drop=drop),
                   0, size, size)
    assert out.getvalue() == data


def test_empty_object():
    er, sinks = encode_to_buffers(4, 2, 1 << 20, b"")
    for s in sinks:
        assert s.getvalue() == b""
    out = BufferSink()
    erasure_decode(er, out, readers_from(sinks, er, 0), 0, 0, 0)
    assert out.getvalue() == b""


def test_shard_math_reference_values():
    """Check against hand-computed cmd/erasure-coding.go:115-141 values."""
    er = Erasure(4, 2, 10 << 20)
    assert er.shard_size() == (10 << 20) // 4
    # 15 MiB object: one full block (shard 2.5MiB) + 5MiB tail -> ceil(5M/4)
    size = 15 << 20
    assert er.shard_file_size(size) == (10 << 20) // 4 + -(-(5 << 20) // 4)
    assert er.shard_file_size(0) == 0
    assert er.shard_file_size(-1) == -1
    # offsets clamp to shard file size
    assert er.shard_file_offset(0, size, size) == er.shard_file_size(size)
    er2 = Erasure(16, 4, 1 << 20)
    assert er2.shard_size() == (1 << 20) // 16
    assert bitrot_logical_size(
        bitrot_shard_file_size(123457, er2.shard_size(), ALGO),
        er2.shard_size(), ALGO) == 123457


def test_streaming_bitrot_layout():
    """[digest][chunk] interleave layout (cmd/bitrot-streaming.go:74-89)."""
    sink = BufferSink()
    w = new_bitrot_writer(sink, ALGO, shard_size=1024)
    payload = rng_bytes(2500, seed=5)
    w.write(payload)
    w.close()
    blob = sink.getvalue()
    h = ALGO.digest_size
    assert len(blob) == 3 * h + 2500
    r = new_bitrot_reader(BufferSource(blob), ALGO, 2500, 1024)
    assert r.read_at(0, 1024) == payload[:1024]
    assert r.read_at(1024, 1476) == payload[1024:]
    with pytest.raises(ValueError):
        r.read_at(100, 10)  # unaligned


# --- HighwayHash + fused verify/reconstruct (BASELINE config 4) --------------

HH = BitrotAlgorithm.HIGHWAYHASH256S


def test_highwayhash_batched_dims_match_flat():
    """The multi-dim device path (natural-dims packet transpose — the
    fused pipeline's shape) is bit-identical to the flat 2-D path,
    including a non-32-multiple chunk size (tail packet)."""
    import jax.numpy as jnp

    from minio_tpu.native import highwayhash as hhn
    from minio_tpu.ops import hh_jax
    rng = np.random.default_rng(9)
    for nbytes in (128, 84):  # 4 packets / 2 packets + 20-byte tail
        data = rng.integers(0, 256, (2, 3, 2, nbytes), dtype=np.uint8)
        d32 = jnp.asarray(np.ascontiguousarray(data).view(np.uint32))
        kw = hh_jax._key_words(hhn.TEST_KEY)
        got = np.asarray(hh_jax.hash256_device_words(kw, nbytes, d32))
        flat = np.asarray(hh_jax.hash256_device_words(
            kw, nbytes, d32.reshape(12, nbytes // 4)))
        assert np.array_equal(got.reshape(12, 8), flat), nbytes
        want = hhn.hash256_batch(hhn.TEST_KEY, data.reshape(12, nbytes))
        digs = np.ascontiguousarray(got.reshape(12, 8)).view(np.uint8)
        assert np.array_equal(digs, want), nbytes


def test_highwayhash_test_vectors():
    """Native HighwayHash pinned to the published 64-bit vectors, and the
    device (JAX) kernel bit-identical to it across packet/remainder paths."""
    from minio_tpu.native import highwayhash as hhn
    from minio_tpu.ops import hh_jax
    data = bytes(range(64))
    for size, want in enumerate(hhn.TEST_VECTORS_64):
        assert hhn.hash64(hhn.TEST_KEY, data[:size]) == want, size
    rng = np.random.default_rng(3)
    for L in (4, 28, 32, 36, 1024, 4096):
        chunks = rng.integers(0, 256, size=(2, L), dtype=np.uint8)
        assert np.array_equal(hh_jax.hash256_chunks(hhn.TEST_KEY, chunks),
                              hhn.hash256_batch(hhn.TEST_KEY, chunks))


def test_default_algo_is_highwayhash(monkeypatch):
    """HighwayHash256S is the default (reference parity, fastest on both
    the AVX2 ingest path and — after the round-5 layout fix — the device
    fused path); MUR3X256S stays selectable. See BASELINE.md."""
    from minio_tpu import native
    from minio_tpu.erasure.bitrot import (DEFAULT_BITROT_ALGO,
                                          BitrotAlgorithm,
                                          default_bitrot_algo)
    if native.available():
        monkeypatch.delenv("MINIO_TPU_BITROT_ALGO", raising=False)
        assert default_bitrot_algo() is BitrotAlgorithm.HIGHWAYHASH256S
        monkeypatch.setenv("MINIO_TPU_BITROT_ALGO", "mur3x256S")
        assert default_bitrot_algo() is BitrotAlgorithm.MUR3X256S
    assert DEFAULT_BITROT_ALGO.streaming
    assert DEFAULT_BITROT_ALGO.available
    assert DEFAULT_BITROT_ALGO.digest_size == 32
    # both streaming algorithms stay available for recorded parts
    assert HH.streaming and HH.available and HH.digest_size == 32


def encode_hh(k, m, block_size, data):
    er = Erasure(k, m, block_size)
    sinks = [BufferSink() for _ in range(k + m)]
    writers = [new_bitrot_writer(sinks[i], HH, er.shard_size())
               for i in range(k + m)]
    n = erasure_encode(er, io.BytesIO(data), writers, k + 1 if k == m else k)
    assert n == len(data)
    for w in writers:
        w.close()
    return er, sinks


def hh_readers(er, sinks, size, dead=(), corrupt=()):
    sfs = er.shard_file_size(size)
    out = []
    for i, s in enumerate(sinks):
        if i in dead:
            out.append(None)
            continue
        blob = bytearray(s.getvalue())
        if i in corrupt:
            blob[len(blob) // 2] ^= 0xFF
        out.append(new_bitrot_reader(BufferSource(bytes(blob)), HH, sfs,
                                     er.shard_size()))
    return out


def test_fused_degraded_decode():
    """Degraded GET rides the fused device verify+reconstruct launch."""
    data = rng_bytes((2 << 20) + 777, seed=11)
    er, sinks = encode_hh(4, 2, 1 << 20, data)
    out = io.BytesIO()
    erasure_decode(er, out, hh_readers(er, sinks, len(data), dead=(0, 2)),
                   0, len(data), len(data))
    assert out.getvalue() == data


def _get_blocks(route):
    from minio_tpu.obs import metrics as mx
    return mx.counters_snapshot().get(
        'minio_tpu_pipeline_get_blocks_total{route="%s"}' % route, 0)


class _FdThenGone:
    """A local shard file whose fd is lost after ``good`` fileno() calls:
    the native pread then fails, the Python read_at keeps working."""

    def __init__(self, src, good):
        self.src, self.left = src, good

    def read_at(self, offset, length):
        return self.src.read_at(offset, length)

    def fileno(self):
        self.left -= 1
        return self.src.fileno() if self.left >= 0 else -1


def file_readers(tmp_path, er, sinks, size, dead=(), corrupt=(),
                 fd_gone=None):
    """hh_readers over real local files (_FileReadAt, as XLStorage
    hands them out): every live reader answers fileno()."""
    from minio_tpu.storage.xlstorage import _FileReadAt
    sfs = er.shard_file_size(size)
    out = []
    for i, s in enumerate(sinks):
        if i in dead:
            out.append(None)
            continue
        blob = bytearray(s.getvalue())
        if i in corrupt:
            blob[len(blob) // 2] ^= 0xFF
        path = tmp_path / f"shard{i}"
        path.write_bytes(bytes(blob))
        src = _FileReadAt(str(path))
        if fd_gone is not None and i == fd_gone[0]:
            src = _FdThenGone(src, fd_gone[1])
        out.append(new_bitrot_reader(src, HH, sfs, er.shard_size()))
    return out


@pytest.mark.parametrize("read", ["whole", "ranged", "prealloc",
                                  "prealloc_ranged"])
def test_native_degraded_decode(tmp_path, read):
    """A degraded GET over local shard files is one native call a block
    (route native_degraded), never the dispatch queue, and exact for
    whole, ranged (mid-block start and end) and zero-copy sink reads."""
    from minio_tpu.erasure.streaming import PreallocSink
    data = rng_bytes((3 << 20) + 777, seed=21)
    er, sinks = encode_hh(4, 2, 1 << 20, data)
    off, n = (0, len(data)) if read in ("whole", "prealloc") \
        else ((1 << 20) + 4321, (1 << 20) + 99)
    out = PreallocSink(n) if read.startswith("prealloc") else io.BytesIO()
    nd, fu = _get_blocks("native_degraded"), _get_blocks("fused")
    stats = erasure_decode(
        er, out, file_readers(tmp_path, er, sinks, len(data), dead=(0, 5)),
        off, n, len(data))
    assert bytes(out.getvalue()) == data[off: off + n]
    assert stats.bytes_written == n
    want = 4 if off == 0 else 2
    assert _get_blocks("native_degraded") - nd == want
    assert _get_blocks("fused") == fu
    # the missing readers keep their votes for heal-on-read
    assert isinstance(stats.errs[0], errors.DiskNotFound)
    assert isinstance(stats.errs[5], errors.DiskNotFound)


def test_native_degraded_decode_corrupt_source(tmp_path):
    data = rng_bytes(3 << 20, seed=22)
    er, sinks = encode_hh(4, 2, 1 << 20, data)
    readers = file_readers(tmp_path, er, sinks, len(data), dead=(0,),
                           corrupt=(4,))
    nd = _get_blocks("native_degraded")
    out = io.BytesIO()
    stats = erasure_decode(er, out, readers, 0, len(data), len(data))
    assert out.getvalue() == data
    assert _get_blocks("native_degraded") > nd
    # the corrupt PARITY source (position 3 of the chosen 1,2,3,4) must
    # carry the FileCorrupt vote for heal-on-read, under its own index
    assert isinstance(stats.errs[4], errors.FileCorrupt)
    assert isinstance(stats.errs[0], errors.DiskNotFound)
    assert [e for i, e in enumerate(stats.errs) if i not in (0, 4)] == \
        [None] * 4


def test_native_degraded_decode_source_lost_mid_object(tmp_path):
    """A chosen source whose fd goes away after the first block: its
    pread fails, it gets a FaultyDisk vote, the block is redone and the
    next live reader replaces it for the rest of the object."""
    data = rng_bytes((6 << 20) + 5, seed=23)
    er, sinks = encode_hh(4, 2, 1 << 20, data)
    readers = file_readers(tmp_path, er, sinks, len(data), dead=(1,),
                           fd_gone=(2, 1))
    nd = _get_blocks("native_degraded")
    out = io.BytesIO()
    stats = erasure_decode(er, out, readers, 0, len(data), len(data))
    assert out.getvalue() == data
    assert isinstance(stats.errs[2], errors.FaultyDisk)
    # block 0 with source 2, block 1 lost it, later blocks use shard 5
    assert _get_blocks("native_degraded") - nd >= 6


@pytest.mark.parametrize("why", ["dispatch_env", "fault_armed",
                                 "buffer_source"])
def test_degraded_decode_keeps_fused_route(tmp_path, monkeypatch, why):
    """What the native degraded call must not take: a forced dispatch
    GET, a chaos run, sources without an fd. These stay on the fused
    device verify+reconstruct."""
    from minio_tpu import fault
    data = rng_bytes(2 << 20, seed=24)
    er, sinks = encode_hh(4, 2, 1 << 20, data)
    if why == "buffer_source":
        readers = hh_readers(er, sinks, len(data), dead=(0,))
    else:
        readers = file_readers(tmp_path, er, sinks, len(data), dead=(0,))
    if why == "dispatch_env":
        monkeypatch.setenv("MINIO_TPU_GET_PATH", "dispatch")
    if why == "fault_armed":
        fault.arm("disk:no-such-endpoint:read_at:delay(1)")
    nd, fu = _get_blocks("native_degraded"), _get_blocks("fused")
    out = io.BytesIO()
    try:
        erasure_decode(er, out, readers, 0, len(data), len(data))
    finally:
        fault.clear()
    assert out.getvalue() == data
    assert _get_blocks("native_degraded") == nd
    assert _get_blocks("fused") - fu == 2


def test_fused_decode_detects_corruption_and_retries():
    data = rng_bytes(2 << 20, seed=12)
    er, sinks = encode_hh(4, 2, 1 << 20, data)
    readers = hh_readers(er, sinks, len(data), dead=(0,), corrupt=(1,))
    out = io.BytesIO()
    stats = erasure_decode(er, out, readers, 0, len(data), len(data))
    assert out.getvalue() == data
    # the corrupt source must carry a FileCorrupt vote for heal-on-read
    assert any(isinstance(e, errors.FileCorrupt) for e in stats.errs)


def test_fused_heal_roundtrip_and_corruption():
    data = rng_bytes((3 << 20) + 12345, seed=13)
    er, sinks = encode_hh(16, 4, 1 << 20, data)
    # heal shards 0 and 19 while source 3 is corrupted
    targets = (0, 19)
    healed = {t: BufferSink() for t in targets}
    writers = [new_bitrot_writer(healed[i], HH, er.shard_size())
               if i in targets else None for i in range(20)]
    erasure_heal(er, writers,
                 hh_readers(er, sinks, len(data), dead=targets, corrupt=(3,)),
                 len(data))
    for t in targets:
        assert healed[t].getvalue() == sinks[t].getvalue(), t


def test_raw_read_contract():
    # chunk = half the shard so multi-chunk raw reads exist
    er = Erasure(4, 2, 1 << 20)
    chunk_size = er.shard_size() // 2
    data = rng_bytes(1 << 20, seed=14)
    sinks = [BufferSink() for _ in range(6)]
    writers = [new_bitrot_writer(s, HH, chunk_size) for s in sinks]
    erasure_encode(er, io.BytesIO(data), writers, 4)
    for w in writers:
        w.close()
    r = new_bitrot_reader(BufferSource(sinks[0].getvalue()), HH,
                          er.shard_file_size(len(data)), chunk_size)
    assert r.fusable
    dig, chunk = r.read_at_raw(0, r.shard_size)
    h = HH.new()
    h.update(chunk)
    assert h.digest() == dig
    # multi-chunk raw read returns the concatenated per-chunk digests
    digs2, payload2 = r.read_at_raw(0, 2 * r.shard_size)
    assert len(digs2) == 2 * HH.digest_size
    assert digs2[:HH.digest_size] == dig
    assert payload2[: r.shard_size] == chunk
    h = HH.new()
    h.update(payload2[r.shard_size:])
    assert digs2[HH.digest_size:] == h.digest()
    with pytest.raises(ValueError):
        r.read_at_raw(1, 8)  # unaligned


def test_bitrot_chunk_is_16k_and_recorded(tmp_path):
    """New objects record the 16 KiB device-friendly bitrot chunk in
    xl.meta and remain readable/healable (TPU-first chunking choice)."""
    import io as _io
    from minio_tpu.erasure.bitrot import (BITROT_CHUNK_KEY,
                                          DEFAULT_BITROT_CHUNK)
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.storage import XLStorage
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(6)]
    ol = ErasureObjects(disks, default_parity=2)
    ol.make_bucket("b")
    data = rng_bytes((2 << 20) + 999, seed=15)
    ol.put_object("b", "o", _io.BytesIO(data), len(data))
    fi = disks[0].read_version("b", "o")
    assert fi.metadata[BITROT_CHUNK_KEY] == str(DEFAULT_BITROT_CHUNK)
    assert ol.get_object_bytes("b", "o") == data
    # degraded read still exact
    import shutil as _sh
    _sh.rmtree(str(tmp_path / "d0" / "b" / "o"))
    assert ol.get_object_bytes("b", "o") == data


def test_failed_put_returns_block_buffer_to_pool(tmp_path):
    """A stream that dies mid-read during PUT (client disconnect) must
    return the pooled block buffer on the exception edge instead of
    leaking it to the GC (graftlint GL022 regression)."""
    import io as _io
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.runtime.bufpool import global_pool
    from minio_tpu.storage import XLStorage
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, default_parity=1)
    ol.make_bucket("b")

    class _Hangup(_io.RawIOBase):
        def readinto(self, b):           # zero-copy read path
            raise OSError("client hung up")

        def read(self, n=-1):
            raise OSError("client hung up")

    pool = global_pool()
    pool.clear()
    before = pool.stats()["retained"]
    with pytest.raises(Exception):
        ol.put_object("b", "o", _Hangup(), 4 << 20)
    assert pool.stats()["retained"] > before  # buffer came back pooled
