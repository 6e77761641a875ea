"""Bitrot protection — per-shard checksums in the reference's two modes
(cmd/bitrot.go, cmd/bitrot-streaming.go, cmd/bitrot-whole.go):

- **streaming** (default): the shard file interleaves a fixed-size digest
  before every up-to-shard_size chunk: ``[H][chunk][H][chunk]...``; total
  file size = ceil(len/shard_size)*H + len (bitrotShardFileSize,
  cmd/bitrot.go:140). Reads must be chunk-aligned; each chunk is verified on
  read (cmd/bitrot-streaming.go:115-151).
- **whole-file**: one digest over the whole shard, stored in xl.meta; file
  holds raw bytes (cmd/bitrot-whole.go).

Algorithms: HighwayHash256S (streaming) is the default, served by the native
C++ library (minio_tpu/native/highwayhash.cpp) on the CPU paths and by the
device kernel (minio_tpu/ops/hh_jax.py) in the fused verify+reconstruct
launch; BLAKE2b-256 is the fallback when the native build is unavailable.
SHA256 and BLAKE2b-512 complete the algorithm table (cmd/bitrot.go:33-44).
"""
from __future__ import annotations

import enum
import hashlib
import os
from dataclasses import dataclass

import numpy as np

from ..utils import errors

#: xl.meta key recording the streaming-bitrot chunk size an object was
#: written with (readers must use the same chunking to find the digests).
BITROT_CHUNK_KEY = "x-minio-internal-bitrot-chunk"

#: Default streaming chunk. The reference uses the erasure shard size
#: (cmd/erasure-coding.go:115); we default to 16 KiB because the device
#: hash is lane-parallel ACROSS chunks and sequential within one, so finer
#: chunks widen the VPU batch for fused verify+reconstruct. Override with
#: MINIO_TPU_BITROT_CHUNK (parsed once; malformed values fall back).
DEFAULT_BITROT_CHUNK = 16384


def _env_chunk() -> int:
    try:
        return int(os.environ.get("MINIO_TPU_BITROT_CHUNK",
                                  str(DEFAULT_BITROT_CHUNK)).strip())
    except ValueError:
        return DEFAULT_BITROT_CHUNK


_CONFIGURED_CHUNK = _env_chunk()


def pick_bitrot_chunk(shard_size: int) -> int:
    """Streaming chunk size for a new object with the given erasure shard
    size: the configured default when it divides the shard (so block reads
    stay chunk-aligned), else the shard size itself. Resolved through the
    config KVS (bitrot.chunk: env > stored > default), so admin set-config
    applies to new objects without restart."""
    try:
        from ..config import get_config_sys
        c = get_config_sys().get_int("bitrot", "chunk", _CONFIGURED_CHUNK)
    except Exception:  # noqa: BLE001 — registry unavailable: env/default
        c = _CONFIGURED_CHUNK
    if c > 0 and shard_size % c == 0:
        return c
    return shard_size

#: The reference's fixed HighwayHash key (cmd/bitrot.go:31) is a magic
#: constant; we use our own framework-wide key (any fixed key works — the
#: hash is for corruption detection, not authentication).
HIGHWAY_KEY = bytes.fromhex(
    "4be734fa8e238acd263e83e6bb968552040f935da39f441497e09d1322de36a0")


class BitrotAlgorithm(enum.Enum):
    SHA256 = "sha256"
    BLAKE2B512 = "blake2b"
    HIGHWAYHASH256 = "highwayhash256"
    HIGHWAYHASH256S = "highwayhash256S"
    BLAKE2B256S = "blake2b256S"  # no-native streaming fallback (blake2b-256)
    #: TPU-native streaming default: two-seed MurmurHash3_x86_128 — pure
    #: u32 ops, so the fused device verify runs at VPU rate (~4x the
    #: u64-emulated HighwayHash kernel). The reference picked HighwayHash
    #: for AVX2 for the same hardware-fit reason (cmd/bitrot.go:51).
    MUR3X256S = "mur3x256S"

    @property
    def streaming(self) -> bool:
        return self in (BitrotAlgorithm.HIGHWAYHASH256S,
                        BitrotAlgorithm.BLAKE2B256S,
                        BitrotAlgorithm.MUR3X256S)

    @property
    def digest_size(self) -> int:
        return _ALGOS[self]().digest_size

    def new(self):
        return _ALGOS[self]()

    @property
    def available(self) -> bool:
        try:
            self.new()
            return True
        except Exception:
            return False


def _batch_digests(algo: BitrotAlgorithm, blob: bytes, n: int,
                   chunk_size: int) -> "np.ndarray":
    """Digests of n equal chunks as uint8 [n, digest_size]; HighwayHash
    and MUR3X256 go through the native batch entries (one ctypes call)."""
    if algo in (BitrotAlgorithm.HIGHWAYHASH256,
                BitrotAlgorithm.HIGHWAYHASH256S):
        from ..native import highwayhash as hhn
        return hhn.hash256_batch(
            HIGHWAY_KEY,
            np.frombuffer(blob, dtype=np.uint8).reshape(n, chunk_size))
    if algo is BitrotAlgorithm.MUR3X256S:
        from ..native import mur3py
        return mur3py.hash256_batch(
            HIGHWAY_KEY,
            np.frombuffer(blob, dtype=np.uint8).reshape(n, chunk_size))
    out = np.empty((n, algo.digest_size), dtype=np.uint8)
    for i in range(n):
        h = algo.new()
        h.update(blob[i * chunk_size: (i + 1) * chunk_size])
        out[i] = np.frombuffer(h.digest(), dtype=np.uint8)
    return out


def _blake2b256():
    return hashlib.blake2b(digest_size=32)


def _blake2b512():
    return hashlib.blake2b(digest_size=64)


def _highwayhash256():
    from ..native import highwayhash
    return highwayhash.HighwayHash256(HIGHWAY_KEY)


def _mur3x256():
    from ..native import mur3py
    return mur3py.Mur3x256(HIGHWAY_KEY)


_ALGOS = {
    BitrotAlgorithm.SHA256: hashlib.sha256,
    BitrotAlgorithm.BLAKE2B512: _blake2b512,
    BitrotAlgorithm.HIGHWAYHASH256: _highwayhash256,
    BitrotAlgorithm.HIGHWAYHASH256S: _highwayhash256,
    BitrotAlgorithm.BLAKE2B256S: _blake2b256,
    BitrotAlgorithm.MUR3X256S: _mur3x256,
}

#: Streaming algorithms with both a native CPU engine and a device kernel
#: (the fused verify+reconstruct set), with their native/pipeline.cpp ids.
def native_algo_id(algo: BitrotAlgorithm) -> int | None:
    from .. import native
    return {BitrotAlgorithm.HIGHWAYHASH256S: native.ALGO_HIGHWAY,
            BitrotAlgorithm.MUR3X256S: native.ALGO_MUR3}.get(algo)


def native_batch_hasher(algo_id: int):
    """CPU batch-hash entry for a native ALGO_* id — the ONE place the
    id -> hasher table lives for CPU-side verification (codec fallback,
    dispatch CPU route)."""
    from .. import native
    if algo_id == native.ALGO_MUR3:
        from ..native import mur3py
        return mur3py.hash256_batch
    from ..native import highwayhash
    return highwayhash.hash256_batch


#: native ALGO_* ids duplicated here so pure-hash helpers need not import
#: the native package (which may be unavailable without a toolchain)
ALGO_ID_HIGHWAY = 0
ALGO_ID_MUR3 = 1


def _algo_for_native_id(algo_id: int) -> BitrotAlgorithm:
    return BitrotAlgorithm.MUR3X256S if algo_id == ALGO_ID_MUR3 \
        else BitrotAlgorithm.HIGHWAYHASH256S


def shard_chunk_digests(shards: "np.ndarray", chunk: int,
                        algo_id: int = 0) -> "np.ndarray":
    """Per-chunk digests of each row of uint8 [k, shard_len] as uint8
    [k, n_chunks*32]: full ``chunk``-size pieces batched through the
    native hasher, a short tail piece (shard_len % chunk) digested last —
    exactly the [digest][chunk] framing order of the shard files and of
    mt_put_block, so this is the host half of both the fused-ETag
    reference and the host-fallback digest path."""
    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    k, shard_len = shards.shape
    n_full = shard_len // chunk
    tail = shard_len - n_full * chunk
    nc = n_full + (1 if tail else 0)
    out = np.empty((k, nc * 32), dtype=np.uint8)
    algo = _algo_for_native_id(algo_id)
    if n_full:
        full = _batch_digests(
            algo, shards[:, : n_full * chunk].tobytes(), k * n_full, chunk)
        out[:, : n_full * 32] = full.reshape(k, n_full * 32)
    if tail:
        for i in range(k):
            h = algo.new()
            h.update(shards[i, n_full * chunk:].tobytes())
            out[i, n_full * 32:] = np.frombuffer(h.digest(), dtype=np.uint8)
    return out


def frame_block_shards(shards: "np.ndarray", digs: "np.ndarray",
                       chunk: int, out: "np.ndarray | None" = None
                       ) -> "np.ndarray":
    """Interleave precomputed digests with shard payloads into the
    on-disk [digest][chunk] framing: uint8 [k, shard_len] + [k, nc*32]
    -> uint8 [k, framed_len]. One strided gather per block — the host's
    only payload pass when the hash side ran on device (the dispatch
    PUT path's framing step). ``out``, when given, is the [k, framed_len]
    destination (callers framing data+parity rows into one buffer)."""
    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    k, shard_len = shards.shape
    n_full = shard_len // chunk
    tail = shard_len - n_full * chunk
    nc = n_full + (1 if tail else 0)
    fl = nc * 32 + shard_len
    if out is None:
        out = np.empty((k, fl), dtype=np.uint8)
    elif out.shape != (k, fl):
        raise ValueError("frame_block_shards: out shape mismatch")
    h = 32
    if n_full:
        span = out[:, : n_full * (h + chunk)].reshape(k, n_full, h + chunk)
        span[:, :, :h] = digs[:, : n_full * h].reshape(k, n_full, h)
        span[:, :, h:] = shards[:, : n_full * chunk].reshape(
            k, n_full, chunk)
    if tail:
        pos = n_full * (h + chunk)
        out[:, pos: pos + h] = digs[:, n_full * h:]
        out[:, pos + h:] = shards[:, n_full * chunk:]
    return out


def default_bitrot_algo() -> BitrotAlgorithm:
    """HighwayHash256S when the native library is built — the reference's
    own default (cmd/bitrot.go:51), so digest-level parity comes free —
    else blake2b. Overridable with MINIO_TPU_BITROT_ALGO.

    Round-5 measurements settled the algorithm question in HighwayHash's
    favor on BOTH routes: its AVX2 asm ingests ~1.5x faster than the u32
    MUR3 kernel inside mt_put_block (1.08 vs 0.73 GiB/s e2e block rate),
    and on the TPU the r03/r04 '10 GiB/s fused ceiling' turned out to be
    a batch-flattening layout artifact in the device hash, not u64
    emulation cost — with the packet transpose built on the natural batch
    dims the fused verify+reconstruct runs 31.9 GiB/s (HH) vs 32.9
    (MUR3), a wash (BASELINE.md). MUR3X256S remains fully supported for
    parts recorded under it."""
    env = os.environ.get("MINIO_TPU_BITROT_ALGO", "")
    if env:
        try:
            a = BitrotAlgorithm(env)
            if a.streaming and a.available:
                return a
        except ValueError:
            pass
    from .. import native
    if native.available():
        return BitrotAlgorithm.HIGHWAYHASH256S
    return BitrotAlgorithm.BLAKE2B256S


DEFAULT_BITROT_ALGO = default_bitrot_algo()


def bitrot_shard_file_size(size: int, shard_size: int,
                           algo: BitrotAlgorithm) -> int:
    """On-disk size of a shard file of ``size`` logical bytes
    (cmd/bitrot.go:140-145)."""
    if not algo.streaming:
        return size
    if size == 0:
        return 0
    h = algo.digest_size
    return -(-size // shard_size) * h + size


def bitrot_logical_size(file_size: int, shard_size: int,
                        algo: BitrotAlgorithm) -> int:
    """Inverse of bitrot_shard_file_size: logical shard bytes in a file."""
    if not algo.streaming or file_size == 0:
        return file_size
    h = algo.digest_size
    chunks = -(-file_size // (shard_size + h))
    return file_size - chunks * h


# --- streaming writer/reader -------------------------------------------------


class StreamingBitrotWriter:
    """Writes ``[digest][chunk]`` per shard_size chunk into a byte sink.

    The sink is any object with write(bytes) and close(); buffering chunk
    alignment is handled here: callers may write() arbitrary sizes, digests
    are emitted every shard_size logical bytes (matching the reference, where
    the encode loop writes exactly one shard-block per call —
    cmd/bitrot-streaming.go:74-89).
    """

    def __init__(self, sink, algo: BitrotAlgorithm, shard_size: int):
        assert algo.streaming
        self.sink = sink
        self.algo = algo
        self.shard_size = shard_size
        self._buf = bytearray()

    def write(self, b: bytes):
        self._buf += b
        n = len(self._buf) // self.shard_size
        if n:
            blob = bytes(self._buf[: n * self.shard_size])
            del self._buf[: n * self.shard_size]
            self._emit_many(blob, n)

    def _emit_many(self, blob: bytes, n: int):
        """Digest + interleave n complete chunks with ONE hash call and ONE
        sink write — per-chunk Python/ctypes round-trips dominate the write
        path otherwise (a 64 MiB put is ~5k chunks at 16 KiB)."""
        digs = _batch_digests(self.algo, blob, n, self.shard_size)
        cs = self.shard_size
        h = self.algo.digest_size
        out = np.empty((n, h + cs), dtype=np.uint8)
        out[:, :h] = digs
        out[:, h:] = np.frombuffer(blob, dtype=np.uint8).reshape(n, cs)
        self.sink.write(out.tobytes())

    def _emit(self, chunk: bytes):
        h = self.algo.new()
        h.update(chunk)
        self.sink.write(h.digest())
        self.sink.write(chunk)

    def write_framed(self, framed) -> None:
        """Pass pre-framed ``[digest][chunk]`` bytes straight to the sink —
        the native fused pipeline (native/pipeline.cpp mt_put_block) computes
        digests and interleaving in the same pass as the erasure encode, so
        re-hashing here would double the work. Only legal on chunk
        boundaries (no partial chunk buffered)."""
        if self._buf:
            raise ValueError("write_framed with partial chunk buffered")
        self.sink.write(framed if isinstance(
            framed, (bytes, bytearray, memoryview)) else memoryview(framed))

    def finish(self):
        """Everything written is in the sink; closing it is what is left
        (``close_writers`` closes the sinks of a PUT's drives together)."""
        if self._buf:
            self._emit(bytes(self._buf))
            self._buf.clear()

    def close(self):
        self.finish()
        self.sink.close()

    def abort(self):
        if hasattr(self.sink, "abort"):
            self.sink.abort()
        else:
            self.sink.close()


class StreamingBitrotReader:
    """Chunk-aligned verified reads over a ``[digest][chunk]`` stream.

    ``src`` exposes read_at(offset, length) over the *physical* file.
    read_at() here takes *logical* shard offsets; offset must be chunk
    aligned (the erasure decode path always reads whole shard blocks —
    cmd/bitrot-streaming.go:115-151).
    """

    def __init__(self, src, till_offset: int, algo: BitrotAlgorithm,
                 shard_size: int):
        assert algo.streaming
        self.src = src
        self.algo = algo
        self.shard_size = shard_size
        self.till_offset = till_offset  # logical end offset we may read to

    @property
    def fusable(self) -> bool:
        """True when chunk digests can be verified on device in the fused
        verify+reconstruct launch (minio_tpu.ops.fused): HighwayHash and
        MUR3X256 have device kernels (MUR3X256 additionally needs 16-byte
        packets)."""
        if self.algo is BitrotAlgorithm.HIGHWAYHASH256S:
            return True
        return self.algo is BitrotAlgorithm.MUR3X256S \
            and self.shard_size % 16 == 0

    def _read_phys_span(self, offset: int, length: int) -> bytes:
        """Shared guard + physical-span read for the three read entries:
        offset must be chunk-aligned, the span must not pass till_offset,
        and a span ending mid-chunk is only legal at stream end (a short
        final chunk is only ever stored there — hashing a prefix of a full
        stored chunk would report spurious corruption). Returns the raw
        framed blob covering ceil(length/chunk) digests + length payload
        bytes."""
        if offset % self.shard_size:
            raise ValueError(f"unaligned bitrot read at {offset}")
        if offset + length > self.till_offset:
            raise errors.FileCorrupt(
                f"bitrot read [{offset}, {offset + length}) past shard end "
                f"{self.till_offset}")
        if length % self.shard_size and offset + length != self.till_offset:
            raise ValueError(
                f"bitrot read [{offset}, {offset + length}) ends mid-chunk "
                f"before stream end {self.till_offset}")
        h = self.algo.digest_size
        n_chunks = -(-length // self.shard_size) if length else 0
        phys = (offset // self.shard_size) * (self.shard_size + h)
        blob = self.src.read_at(phys, n_chunks * h + length)
        if len(blob) < n_chunks * h + length:
            raise errors.FileCorrupt("short bitrot stream")
        return blob

    def read_at_raw(self, offset: int, length: int) -> tuple[bytes, bytes]:
        """Read (digests, payload) without verifying — the fused device path
        (ops/fused.py) checks the digests in the same launch as the
        reconstruct. offset must be chunk-aligned; ``digests`` is the
        concatenation of the per-chunk digests covering the read (all chunks
        full-size except possibly the last)."""
        blob = self._read_phys_span(offset, length)
        h = self.algo.digest_size
        digests = bytearray()
        payload = bytearray()
        pos = 0
        left = length
        while left > 0:
            clen = min(self.shard_size, left)
            digests += blob[pos: pos + h]
            payload += blob[pos + h: pos + h + clen]
            pos += h + clen
            left -= clen
        return bytes(digests), bytes(payload)

    def read_framed(self, offset: int, length: int) -> bytes:
        """Raw physical read covering logical [offset, offset+length) with
        the digest headers left in place — the native fused read path
        (native/pipeline.cpp mt_get_block) verifies and strips them in one
        pass. offset must be chunk-aligned."""
        return self._read_phys_span(offset, length)

    def fileno(self) -> int:
        """Underlying fd when the source is a local file (fused pread
        path); raises AttributeError for RPC sources."""
        return self.src.fileno()

    def phys_offset(self, offset: int) -> int:
        """Physical file offset of chunk-aligned logical ``offset``
        (the [digest][chunk] interleaving stride)."""
        return (offset // self.shard_size) * (
            self.shard_size + self.algo.digest_size)

    def read_at(self, offset: int, length: int) -> bytes:
        if length == 0:
            return b""
        # ONE backing read for the whole span (a chunk-per-call loop would
        # turn a block read into n_chunks IO round-trips — ruinous when the
        # source is a remote-disk RPC), then verify all full-size chunks
        # with one batched hash call; only a short tail chunk goes through
        # the per-chunk path.
        blob = self._read_phys_span(offset, length)
        h = self.algo.digest_size
        cs = self.shard_size
        n_full = length // cs
        out = bytearray()
        if n_full:
            framed = np.frombuffer(blob[: n_full * (h + cs)],
                                   dtype=np.uint8).reshape(n_full, h + cs)
            payload = np.ascontiguousarray(framed[:, h:])  # ONE gather
            digs = _batch_digests(self.algo, payload.data, n_full, cs)
            if not np.array_equal(digs, framed[:, :h]):
                raise errors.FileCorrupt("bitrot hash mismatch")
            out += payload.data
        tail = length - n_full * cs
        if tail:
            pos = n_full * (h + cs)
            digest = blob[pos: pos + h]
            chunk = blob[pos + h: pos + h + tail]
            hh = self.algo.new()
            hh.update(chunk)
            if hh.digest() != digest:
                raise errors.FileCorrupt("bitrot hash mismatch")
            out += chunk
        return bytes(out)


# --- whole-file writer/reader ------------------------------------------------


class WholeBitrotWriter:
    """Raw passthrough writer accumulating one digest for xl.meta
    (cmd/bitrot-whole.go)."""

    def __init__(self, sink, algo: BitrotAlgorithm):
        self.sink = sink
        self._h = algo.new()

    def write(self, b: bytes):
        self._h.update(b)
        self.sink.write(b)

    def digest(self) -> bytes:
        return self._h.digest()

    def finish(self):
        pass

    def close(self):
        self.sink.close()


class WholeBitrotReader:
    """Reads the whole shard once, verifies against the stored digest, then
    serves read_at from memory (the reference verifies lazily on first read —
    cmd/bitrot-whole.go:55-80)."""

    def __init__(self, src, expected_digest: bytes, algo: BitrotAlgorithm,
                 file_size: int):
        self.src = src
        self.expected = expected_digest
        self.algo = algo
        self.file_size = file_size
        self._data: bytes | None = None

    def read_at(self, offset: int, length: int) -> bytes:
        if self._data is None:
            data = self.src.read_at(0, self.file_size)
            h = self.algo.new()
            h.update(data)
            if self.expected and h.digest() != self.expected:
                raise errors.FileCorrupt("bitrot whole-file hash mismatch")
            self._data = data
        if offset + length > len(self._data):
            raise errors.FileCorrupt("bitrot read past end")
        return self._data[offset: offset + length]


@dataclass
class ChecksumInfo:
    """Per-part checksum record persisted in xl.meta (reference
    ChecksumInfo, cmd/erasure-metadata.go)."""
    part_number: int
    algorithm: str
    hash: bytes


def new_bitrot_writer(sink, algo: BitrotAlgorithm, shard_size: int):
    if algo.streaming:
        return StreamingBitrotWriter(sink, algo, shard_size)
    return WholeBitrotWriter(sink, algo)


def new_bitrot_reader(src, algo: BitrotAlgorithm, till_offset: int,
                      shard_size: int, expected_digest: bytes = b"",
                      file_size: int = 0):
    if algo.streaming:
        return StreamingBitrotReader(src, till_offset, algo, shard_size)
    return WholeBitrotReader(src, expected_digest, algo, file_size)
