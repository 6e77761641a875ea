"""Zero-copy pipeline equivalence locks (ROADMAP item 1): the
device/native-computed fused ETag and bitrot digests must match the host
``hashlib``/``utils/hashreader.py`` reference BYTE FOR BYTE across every
execution path — single PUT (native fd pipeline and forced-dispatch
device hash lane), multipart parts, the SSE (ciphertext) path, and the
host fallback — property-tested over sizes including non-lane-aligned
tails. Also pins the Pallas MUR3X256 kernel against the pure-Python
implementation (three independent implementations must agree: C++,
Pallas, Python) and the zero-copy ingest/egress plumbing."""
import hashlib
import io
import os
import shutil
import tempfile

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.erasure.bitrot import HIGHWAY_KEY
from minio_tpu.utils.hashreader import (HashReader, PipelineETag,
                                        pipeline_etag_reference)

RNG = np.random.default_rng(0xE7A6)

# sizes chosen to hit: sub-chunk, chunk-aligned, odd tails, multi-block,
# non-4-byte-aligned shard tails
SIZES = [17, 16384, 16400, (1 << 20), (1 << 20) + 12345, (3 << 20) - 7]


def _algo_id(ol) -> int:
    return bitrot.native_algo_id(ol.bitrot_algo) or 0


@pytest.fixture()
def layer(tmp_path):
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.storage import XLStorage
    disks = [XLStorage(os.path.join(tmp_path, f"d{i}")) for i in range(6)]
    ol = ErasureObjects(disks, default_parity=2)
    ol.make_bucket("b")
    yield ol


# --------------------------------------------------------------------------
# Pallas MUR3X256 kernel vs the pure-Python reference


# Off-TPU the kernel runs in interpret mode, where XLA:CPU fuses one grid
# step's unrolled packet chain into a single loop fusion and re-evaluates
# every shared hash-state word at each use: the time to RUN a step grows
# ~2.2x per unrolled packet (0.4 s at 32 packets a step, 6 s at 36, 137 s
# at 40; with fusion off, 3 ms). `_pb_for` takes the largest divisor of
# the packet count <= 64, so the shapes below pick small unrolls and still
# cover what the kernel has: state carried in the VMEM scratch over many
# packet-axis grid steps (65 packets = 13 x 5 steps, 134 = 2 x 67), more
# than one lane tile (1100 chunks pad to 2 x 1024 lanes) and lane padding.
# The production shape (16 KiB chunks, 64 packets a step) is compiled for
# a v5e with interpret=False in
# tests/test_chip_compile.py::test_mur3_pallas_hash_lane, and run on the
# chip by chip_smoke.py.
@pytest.mark.parametrize("n,length", [(1, 16), (5, 48), (8, 1040),
                                      (130, 64), (257, 2144),
                                      (1100, 1040)])
def test_mur3_pallas_matches_reference(n, length):
    from minio_tpu.native import mur3py
    from minio_tpu.ops import mur3_pallas
    chunks = RNG.integers(0, 256, (n, length), dtype=np.uint8)
    want = mur3py.hash256_batch(HIGHWAY_KEY, chunks)
    got = mur3_pallas.hash256_chunks(HIGHWAY_KEY, chunks)
    assert (got == want).all()


def test_mur3_pallas_multidim_batch_matches_jnp():
    import jax.numpy as jnp

    from minio_tpu.ops import mur3_jax, mur3_pallas
    kw = mur3_pallas._key_words(HIGHWAY_KEY)
    data = RNG.integers(0, 2 ** 32, (3, 4, 2, 16), dtype=np.uint32)
    want = np.asarray(mur3_jax.hash256_device_words(kw, 64,
                                                    jnp.asarray(data)))
    got = np.asarray(mur3_pallas.hash256_device_words(kw, 64,
                                                      jnp.asarray(data)))
    assert (got == want).all()


def test_fused_rebuild_uses_pallas_hash_and_verifies():
    """fused_fn_for with algo=1 must resolve the Pallas kernel (default)
    and still produce correct verdicts + rebuilds."""
    import jax.numpy as jnp

    from minio_tpu.native import mur3py
    from minio_tpu.ops import fused, rs_jax
    K, M, C, B, shard = 4, 2, 64, 2, 256
    codec = rs_jax.get_codec(K, M)
    data = RNG.integers(0, 256, (B, K, shard), dtype=np.uint8)
    present = tuple(i for i in range(K + M) if i != 1)[:K]
    masks = codec.target_masks_np(present, (1,))
    mb = np.ascontiguousarray(np.broadcast_to(masks, (B,) + masks.shape))
    gathered = np.stack([
        np.stack([d[i] if i < K else codec.encode(d)[i - K]
                  for i in present]) for d in data])
    digs = np.stack([
        mur3py.hash256_batch(HIGHWAY_KEY, g.reshape(-1, C))
        .reshape(K, -1).view(np.uint32) for g in gathered])
    out, valid = fused.fused_rebuild(
        HIGHWAY_KEY, jnp.asarray(mb),
        jnp.asarray(rs_jax.pack_shards(gathered)), jnp.asarray(digs),
        codec._mm_batch_per, C, 1)
    assert np.asarray(valid).all()
    for b in range(B):
        assert (rs_jax.unpack_shards(np.asarray(out[b]))[0]
                == data[b][1]).all()
    # corruption in one source chunk -> that shard's lane reads invalid
    bad = digs.copy()
    bad[0, 2, 0] ^= 1
    _, valid = fused.fused_rebuild(
        HIGHWAY_KEY, jnp.asarray(mb),
        jnp.asarray(rs_jax.pack_shards(gathered)), jnp.asarray(bad),
        codec._mm_batch_per, C, 1)
    v = np.asarray(valid)
    assert not v[0, 2] and v.sum() == v.size - 1


# --------------------------------------------------------------------------
# fused encode+hash flush: digests == native batch hasher reference


@pytest.mark.parametrize("algo_id", [0, 1])
def test_encode_hashed_async_matches_host_reference(algo_id):
    from minio_tpu.erasure.codec import Erasure
    er = Erasure(4, 2, 1 << 20)
    C = 16384
    buf = RNG.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    data2d, parity2d, digs = er.encode_hashed_async(buf, C,
                                                    algo_id).result()
    ref_shards = er.encode_data(buf)
    both = np.concatenate([data2d, parity2d])
    for i in range(6):
        assert (both[i] == ref_shards[i]).all()
    want = bitrot.shard_chunk_digests(both, C, algo_id)
    assert (digs == want).all()


# --------------------------------------------------------------------------
# fused ETag: every path vs the from-raw-bytes reference


def _put_and_check(ol, name: str, body: bytes):
    oi = ol.put_object("b", name, io.BytesIO(body), len(body))
    assert ol.get_object_bytes("b", name) == body
    if len(body) >= (1 << 20):
        want = pipeline_etag_reference(body, 4, ol.block_size, 16384,
                                       _algo_id(ol))
        assert oi.etag == want, name
    else:
        assert oi.etag == hashlib.md5(body).hexdigest(), name
    return oi


@pytest.mark.parametrize("size", SIZES)
def test_put_etag_native_path(layer, size):
    body = RNG.integers(0, 256, size, dtype=np.uint8).tobytes()
    _put_and_check(layer, f"o{size}", body)


@pytest.mark.parametrize("size", [(1 << 20) + 12345, (3 << 20) - 7])
def test_put_etag_dispatch_path_matches(layer, size, monkeypatch):
    """The forced-dispatch path (device hash lane + host framing) must
    produce the same bytes on disk AND the same fused ETag as the
    native path and the reference."""
    monkeypatch.setenv("MINIO_TPU_PUT_PATH", "dispatch")
    body = RNG.integers(0, 256, size, dtype=np.uint8).tobytes()
    oi = layer.put_object("b", f"d{size}", io.BytesIO(body), size)
    assert layer.get_object_bytes("b", f"d{size}") == body
    want = pipeline_etag_reference(body, 4, layer.block_size, 16384,
                                   _algo_id(layer))
    assert oi.etag == want


def test_etag_config_md5_mode(layer, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_PIPELINE_ETAG", "md5")
    body = RNG.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    oi = layer.put_object("b", "md5mode", io.BytesIO(body), len(body))
    assert oi.etag == hashlib.md5(body).hexdigest()


def test_etag_content_md5_keeps_payload_hash(layer):
    """A client-sent Content-MD5 forces the compat path: the payload is
    verified AND the classic MD5 becomes the ETag."""
    body = RNG.integers(0, 256, 2 << 20, dtype=np.uint8).tobytes()
    md5 = hashlib.md5(body).hexdigest()
    hr = HashReader(io.BytesIO(body), len(body), md5_hex=md5)
    assert hr.disable_payload_hash() is False
    oi = layer.put_object("b", "cmd5", hr, len(body))
    assert oi.etag == md5
    # and a WRONG digest is rejected before commit
    from minio_tpu.utils.hashreader import BadDigestError
    bad = HashReader(io.BytesIO(body), len(body),
                     md5_hex="0" * 32)
    with pytest.raises(Exception) as ei:
        layer.put_object("b", "cmd5bad", bad, len(body))
    assert isinstance(ei.value.__cause__ or ei.value,
                      (BadDigestError, Exception))


def test_multipart_part_etags_fused(layer):
    bodies = [RNG.integers(0, 256, n, dtype=np.uint8).tobytes()
              for n in ((5 << 20) + 999, (1 << 20) + 7)]
    up = layer.new_multipart_upload("b", "mp")
    etags = []
    for n, part in enumerate(bodies, start=1):
        pi = layer.put_object_part("b", "mp", up, n,
                                   io.BytesIO(part), len(part))
        want = pipeline_etag_reference(part, 4, layer.block_size, 16384,
                                       _algo_id(layer))
        assert pi.etag == want
        etags.append(pi)
    oi = layer.complete_multipart_upload("b", "mp", up, etags)
    from minio_tpu.utils.hashreader import etag_from_parts
    assert oi.etag == etag_from_parts([p.etag for p in etags])
    assert layer.get_object_bytes("b", "mp") == b"".join(bodies)


def _have_cryptography() -> bool:
    import importlib.util
    return importlib.util.find_spec("cryptography") is not None


@pytest.mark.parametrize("cipher_name", [
    "CHACHA20-POLY1305",   # self-contained — runs on EVERY build
    pytest.param("AES256-GCM", marks=pytest.mark.skipif(
        not _have_cryptography(), reason="cryptography wheel absent")),
])
def test_sse_path_etag_matches_ciphertext_reference(layer, cipher_name,
                                                    monkeypatch):
    """SSE PUTs stream ciphertext into the erasure pipeline; the fused
    ETag must equal the reference computed over the SAME ciphertext
    (deterministic EncryptReader: fixed OEK + IV). UNGATED by the
    ChaCha20 package cipher (ISSUE 8): SSE rides the pipeline path with
    no optional crypto dependency."""
    from minio_tpu.crypto import EncryptReader, enc_size
    # numpy package lane: identical bytes, skips the full-package
    # interpret kernel's one-off XLA compile on CPU hosts
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "off")
    body = RNG.integers(0, 256, (1 << 20) + 777, dtype=np.uint8).tobytes()
    oek, iv = b"\x11" * 32, b"\x07" * 12
    cipher = EncryptReader(io.BytesIO(body), oek, iv,
                           cipher=cipher_name).read()
    assert len(cipher) == enc_size(len(body))
    oi = layer.put_object("b", f"sse-{cipher_name}",
                          EncryptReader(io.BytesIO(body), oek, iv,
                                        cipher=cipher_name),
                          enc_size(len(body)))
    want = pipeline_etag_reference(cipher, 4, layer.block_size, 16384,
                                   _algo_id(layer))
    assert oi.etag == want
    assert layer.get_object_bytes("b", f"sse-{cipher_name}") == cipher


def test_sse_body_etag_mode_selection(layer, monkeypatch):
    """Fused-vs-compat-MD5 selection is driven by the CIPHERTEXT size
    like any body: a large encrypted body gets the fused ETag, a body
    under pipeline.etag_min_bytes keeps the classic MD5 chain — over
    the ciphertext either way (the stored bytes ARE the object)."""
    from minio_tpu.crypto.sse import (CIPHER_CHACHA20, EncryptReader,
                                      enc_size)
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "off")
    oek, iv = b"\x13" * 32, b"\x05" * 12
    big = RNG.integers(0, 256, (2 << 20) + 99, dtype=np.uint8).tobytes()
    ct_big = EncryptReader(io.BytesIO(big), oek, iv,
                           cipher=CIPHER_CHACHA20).read()
    oi = layer.put_object("b", "sse-big",
                          EncryptReader(io.BytesIO(big), oek, iv,
                                        cipher=CIPHER_CHACHA20),
                          enc_size(len(big)))
    assert oi.etag == pipeline_etag_reference(
        ct_big, 4, layer.block_size, 16384, _algo_id(layer))
    assert oi.etag != hashlib.md5(ct_big).hexdigest()   # really fused
    small = big[:1000]
    ct_small = EncryptReader(io.BytesIO(small), oek, iv,
                             cipher=CIPHER_CHACHA20).read()
    oi2 = layer.put_object("b", "sse-small",
                           EncryptReader(io.BytesIO(small), oek, iv,
                                         cipher=CIPHER_CHACHA20),
                           enc_size(len(small)))
    assert oi2.etag == hashlib.md5(ct_small).hexdigest()  # compat MD5


def test_host_fallback_path_same_etag(layer, monkeypatch):
    """Chaos runs force the Python framed path (host digest fallback);
    the ETag must not change."""
    from minio_tpu import fault
    body = RNG.integers(0, 256, (2 << 20) + 4321, dtype=np.uint8).tobytes()
    want = pipeline_etag_reference(body, 4, layer.block_size, 16384,
                                   _algo_id(layer))
    fault.arm("disk:__no_such_disk__:read_at:delay(0)")
    try:
        oi = layer.put_object("b", "chaos", io.BytesIO(body), len(body))
    finally:
        fault.clear()
    assert oi.etag == want
    assert layer.get_object_bytes("b", "chaos") == body


def test_pipeline_etag_empty_equals_md5_empty():
    assert PipelineETag().etag() == hashlib.md5(b"").hexdigest()


def test_arm_gate_rejects_unaligned_foreign_chunk(layer):
    """A stored (foreign/legacy multipart) bitrot chunk that does not
    divide this upload's shard must keep the MD5 chain — arming a
    collector erasure_encode would never feed yields the constant
    empty-stream ETag (review finding; the starved-collector guard in
    the put paths backstops it)."""
    body = b"x" * (2 << 20)
    hr = HashReader(io.BytesIO(body), len(body))
    col = layer._arm_pipeline_etag(hr, len(body), chunk=10_000,
                                   shard_size=262_144)
    assert col is None
    assert hr._payload_hash  # MD5 chain still live -> hr.etag() works


# --------------------------------------------------------------------------
# zero-copy plumbing


def test_hashreader_readinto_matches_read():
    body = RNG.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    hr = HashReader(io.BytesIO(body), len(body))
    buf = np.empty(100_000, np.uint8)
    got = bytearray()
    while True:
        n = hr.readinto(buf)
        if not n:
            break
        got += buf[:n].tobytes()
    assert bytes(got) == body
    assert hr.md5_hex() == hashlib.md5(body).hexdigest()


def test_hashreader_readinto_after_disable():
    body = RNG.integers(0, 256, 65536, dtype=np.uint8).tobytes()
    hr = HashReader(io.BytesIO(body), len(body))
    assert hr.disable_payload_hash() is True
    buf = np.empty(65536, np.uint8)
    assert hr.readinto(buf) == 65536
    assert buf.tobytes() == body
    assert hr.readinto(buf) == 0  # clean EOF, size enforced


def test_get_object_buffer_zero_copy(layer):
    """getbuffer hands back a view of the sink's own array — no final
    tobytes pass (the round-5 par8 residual serializer)."""
    from minio_tpu.erasure.streaming import PreallocSink
    body = RNG.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    layer.put_object("b", "zc", io.BytesIO(body), len(body))
    sink = PreallocSink()
    layer.get_object("b", "zc", sink)
    view = sink.getbuffer()
    assert view == body
    assert view.obj is sink.arr  # the SAME backing memory, not a copy
