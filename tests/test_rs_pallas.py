"""Pallas kernel golden tests (interpret mode on the CPU mesh; the same
kernel compiles natively on TPU — tests/test_chip_compile.py compiles it for
the chip)."""
import numpy as np
import pytest

from minio_tpu.ops import gf256, rs_jax, rs_pallas


def rand(k, size, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (k, size), dtype=np.uint8)


@pytest.mark.parametrize("k,m,size", [
    (4, 2, 128),          # sub-tile (heavy padding path)
    (16, 4, 8192),        # one 2048-word tile ((8, 256) layout)
    (16, 4, 65536),       # 16384 words: the (16, 512) sublane layout
    (8, 4, 8192 * 2 + 4),  # multi-tile + ragged tail
    (8, 4, 32768 + 2048),  # 8192-multiple + partial quantum
])
def test_pallas_matmul_matches_reference(k, m, size):
    rs = rs_jax.ReedSolomon(k, m)
    data = rand(k, size, seed=k + m)
    import jax.numpy as jnp
    masks = jnp.asarray(gf256.coeff_masks(rs.parity_rows))
    w = jnp.asarray(rs_jax.pack_shards(np.ascontiguousarray(data[:, :size - size % 4])))
    got = rs_jax.unpack_shards(np.asarray(rs_pallas.gf_matmul(masks, w)))
    want = gf256.gf_matmul_ref(rs.parity_rows, data[:, :size - size % 4])
    assert np.array_equal(got, want)


def test_pallas_codec_end_to_end():
    rs = rs_jax.ReedSolomon(4, 2, backend="pallas")
    data = rand(4, 4096, seed=5)
    parity = rs.encode(data)
    assert np.array_equal(parity, gf256.gf_matmul_ref(rs.parity_rows, data))
    full = np.concatenate([data, parity])
    shards = [None, full[1], full[2], full[3], full[4], None]
    out = rs.reconstruct(shards)
    assert np.array_equal(out[0], full[0]) and np.array_equal(out[5], full[5])
    assert rs.verify(full)


def test_pallas_batched():
    rs = rs_jax.ReedSolomon(4, 2, backend="pallas")
    batch = np.stack([rand(4, 1024, seed=s) for s in range(3)])
    got = rs.encode_batch(batch)
    ref = rs_jax.ReedSolomon(4, 2, backend="xla")
    for b in range(3):
        assert np.array_equal(got[b], ref.encode(batch[b]))


def test_pallas_batched_small_shard_coalescing():
    """Even batch + small shard drives the nb>1 coalesced grid (several
    batch elements per pallas step) for BOTH the shared-mask and the
    per-element-mask kernels — a block-index regression here would
    rebuild from the wrong element's matrices."""
    import jax.numpy as jnp
    B, size = 8, 2048  # W=512 words -> wpad 2048 -> nb>1
    rs = rs_jax.ReedSolomon(4, 2, backend="pallas")
    batch = np.stack([rand(4, size, seed=100 + s) for s in range(B)])
    got = rs.encode_batch(batch)
    ref = rs_jax.ReedSolomon(4, 2, backend="xla")
    for b in range(B):
        assert np.array_equal(got[b], ref.encode(batch[b])), b
    # per-element masks: a DIFFERENT loss pattern per element; the
    # multiply input is each element's chosen PRESENT shards
    fulls = [np.concatenate([batch[s], ref.encode(batch[s])])
             for s in range(B)]
    presents = [tuple(j for j in range(6) if j != (s % 4))[:4]
                for s in range(B)]
    gathered = np.stack([fulls[s][list(presents[s])] for s in range(B)])
    masks = np.stack([
        np.asarray(rs.target_masks_np(presents[s], (s % 4,)))
        for s in range(B)])
    out = np.asarray(rs_pallas.gf_matmul_batch_per(
        jnp.asarray(masks), jnp.asarray(rs_jax.pack_shards(gathered))))
    for s in range(B):
        want = fulls[s][s % 4]  # the lost data shard, rebuilt
        assert np.array_equal(
            rs_jax.unpack_shards(np.ascontiguousarray(out[s]))[0],
            want), s


@pytest.mark.parametrize("k,m,size", [
    (4, 2, 1024),          # padded sub-tile
    (16, 4, 65536),        # north-star shard: (16, 512) layout
    (8, 4, 8192 * 2 + 4),  # ragged tail
])
def test_pallas_static_encode_matches_reference(k, m, size):
    """The compile-time-specialized encode kernel (coefficients baked in)
    is bit-identical to the table reference, including the c hook."""
    import jax.numpy as jnp
    rs = rs_jax.ReedSolomon(k, m)
    data = rand(k, size, seed=k * m)
    aligned = np.ascontiguousarray(data[:, :size - size % 4])
    w = jnp.asarray(rs_jax.pack_shards(aligned))
    got = rs_jax.unpack_shards(np.asarray(
        rs_pallas.gf_matmul_static(rs.parity_rows, w)))
    want = gf256.gf_matmul_ref(rs.parity_rows, aligned)
    assert np.array_equal(got, want)
    # batch form: element 0 matches the reference, element 1 the single call
    wb = jnp.stack([w, w ^ np.uint32(0x01010101)])
    got_b = np.asarray(rs_pallas.gf_matmul_static_batch(rs.parity_rows, wb))
    assert np.array_equal(got_b[0], rs_jax.pack_shards(want))
    assert np.array_equal(got_b[1], np.asarray(
        rs_pallas.gf_matmul_static(rs.parity_rows, wb[1])))
    # the c dependency hook only perturbs word 0's row
    got_c = np.asarray(rs_pallas.gf_matmul_static(
        rs.parity_rows, w, c=np.uint32(0xDEADBEEF)))
    assert np.array_equal(got_c[1:], rs_jax.pack_shards(want)[1:])
