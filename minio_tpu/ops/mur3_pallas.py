"""MUR3X256 as a Pallas TPU kernel — the hash half of the fused
verify+reconstruct launch, and the hash lane of the fused encode+hash PUT
flush (BENCH config 4 / ROADMAP item 1).

Why a third implementation: the jnp kernel (mur3_jax) is correct but stuck
at ~41-47 GiB/s standalone and ~34 fused, which was the whole fused
ceiling (reconstruct alone ran 183; round-5 record, a set-up that is
gone: git history). Its limiting shape is the
scan state: every h lane is a ``[2, N]`` array — 2 seed instances on the
sublane axis — so each VPU op runs at 2/8 sublane occupancy, and the
per-packet tuple-of-streams slicing adds relayout traffic. Here the batch
lanes are tiled ``(RT, 128)`` — full (8, 128) vregs — each of the 8 hash
state words (2 instances x h1..h4) is its own full tile, and the packet
chain runs as the innermost grid dimension with state carried in VMEM
scratch, so the only HBM traffic is ONE read of the packet stream.

Layout: chunks are lanes. The packet stream is built on the natural batch
dims exactly like mur3_jax (minor split -> one transpose -> major collapse,
the form measured NOT to hit XLA's bad-relayout lowering), then lane-padded
to the (RT x 128) tile and reshaped ``[nblocks, 4, R, 128]``. A grid step
loads ``PB`` packets for one lane tile (``(PB, 4, RT, 128)`` block, ~1 MiB)
and unrolls the 2x26-op u32 packet body PB times.

Bit-identical to native/mur3.cpp, native/mur3py.py and ops/mur3_jax.py
(pinned in tests/test_pipeline.py). Falls back to interpreter mode off-TPU;
MINIO_TPU_MUR3_PALLAS=0 (config KVS ``pipeline.device_hash=jnp``) routes
the fused launch back to the jnp kernel.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_C1 = np.uint32(0x239B961B)
_C2 = np.uint32(0xAB0E9789)
_C3 = np.uint32(0x38B34AE5)
_C4 = np.uint32(0xA1E38B93)
_F1 = np.uint32(0x85EBCA6B)
_F2 = np.uint32(0xC2B2AE35)
_FIVE = np.uint32(5)
_A1 = np.uint32(0x561CCD1B)
_A2 = np.uint32(0x0BCAA747)
_A3 = np.uint32(0x96CD1C35)
_A4 = np.uint32(0x32AC3B17)

#: lane-tile sublanes (full-vreg quantum is 8) and max packets per grid step
RT = 8
PB_MAX = 64


def enabled() -> bool:
    """Pallas device hash on unless pipeline.device_hash=jnp /
    MINIO_TPU_MUR3_PALLAS=0 routes back to the jnp kernel (escape hatch
    for a bad Mosaic lowering on some future toolchain)."""
    try:
        from ..config import get_config_sys
        v = get_config_sys().get("pipeline", "device_hash")
        if v:
            return v not in ("jnp", "0", "off")
    except Exception:  # noqa: BLE001 — registry unavailable: env/default
        pass
    return os.environ.get("MINIO_TPU_MUR3_PALLAS", "1") not in ("0", "jnp")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _fmix(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _F1
    h = h ^ (h >> np.uint32(13))
    h = h * _F2
    return h ^ (h >> np.uint32(16))


def _update(h, k1, k2, k3, k4):
    """One 16-byte packet into one instance's (h1..h4) state tiles."""
    h1, h2, h3, h4 = h
    k1 = _rotl(k1 * _C1, 15) * _C2
    h1 = h1 ^ k1
    h1 = (_rotl(h1, 19) + h2) * _FIVE + _A1
    k2 = _rotl(k2 * _C2, 16) * _C3
    h2 = h2 ^ k2
    h2 = (_rotl(h2, 17) + h3) * _FIVE + _A2
    k3 = _rotl(k3 * _C3, 17) * _C4
    h3 = h3 ^ k3
    h3 = (_rotl(h3, 15) + h4) * _FIVE + _A3
    k4 = _rotl(k4 * _C4, 18) * _C1
    h4 = h4 ^ k4
    h4 = (_rotl(h4, 13) + h1) * _FIVE + _A4
    return [h1, h2, h3, h4]


def _pb_for(nblocks: int) -> int:
    """Packets per grid step: the largest divisor of nblocks <= PB_MAX
    (pow2 chunks get 64; odd chunk sizes degrade gracefully)."""
    for pb in range(min(PB_MAX, nblocks), 0, -1):
        if nblocks % pb == 0:
            return pb
    return 1


def _make_kernel(seeds: tuple[int, int], nbytes: int, pb: int,
                 n_psteps: int):
    ln = np.uint32(nbytes)

    def kernel(x_ref, out_ref, st_ref):
        p = pl.program_id(1)

        @pl.when(p == 0)
        def _init():
            for inst in range(2):
                st_ref[inst * 4: inst * 4 + 4] = jnp.full(
                    (4, RT, 128), np.uint32(seeds[inst]), jnp.uint32)

        st = st_ref[:]
        h = [[st[i * 4 + j] for j in range(4)] for i in range(2)]
        x = x_ref[:]  # (pb, 4, RT, 128)
        for b in range(pb):
            k1, k2, k3, k4 = x[b, 0], x[b, 1], x[b, 2], x[b, 3]
            for inst in range(2):
                h[inst] = _update(h[inst], k1, k2, k3, k4)
        st_ref[:] = jnp.stack(h[0] + h[1])

        @pl.when(p == n_psteps - 1)
        def _finalize():
            rows = []
            for inst in range(2):
                h1, h2, h3, h4 = (v ^ ln for v in h[inst])
                h1 = h1 + h2 + h3 + h4
                h2, h3, h4 = h2 + h1, h3 + h1, h4 + h1
                h1, h2, h3, h4 = _fmix(h1), _fmix(h2), _fmix(h3), _fmix(h4)
                h1 = h1 + h2 + h3 + h4
                rows += [h1, h2 + h1, h3 + h1, h4 + h1]
            out_ref[:] = jnp.stack(rows)

    return kernel


@functools.lru_cache(maxsize=64)
def _jitted(seeds: tuple[int, int], nbytes: int, n_lanes_padded: int,
            interpret: bool):
    """Jitted [nblocks, 4, R, 128] -> digests [8, R, 128] for one (seed
    pair, chunk size, padded lane count)."""
    nblocks = nbytes // 16
    pb = _pb_for(nblocks)
    n_psteps = nblocks // pb
    r = n_lanes_padded // 128
    kernel = _make_kernel(seeds, nbytes, pb, n_psteps)
    from ..obs.device import tracked_jit

    @functools.partial(tracked_jit, op="hash.mur3_pallas")
    def run(ks: jnp.ndarray) -> jnp.ndarray:
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8, r, 128), jnp.uint32),
            grid=(r // RT, n_psteps),
            in_specs=[
                pl.BlockSpec((pb, 4, RT, 128),
                             lambda t, p: (p, 0, t, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((8, RT, 128), lambda t, p: (0, t, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((8, RT, 128), jnp.uint32)],
            interpret=interpret,
        )(ks)

    return run


def hash256_device_words(key_words: tuple[int, int], nbytes: int, data32):
    """Digest chunks of ``nbytes`` bytes given as uint32 LE words
    [..., nbytes//4] -> uint32 digests [..., 8]; same contract as
    mur3_jax.hash256_device_words, traceable into larger jitted programs
    (the fused verify+reconstruct and encode+hash launches)."""
    if nbytes % 16:
        raise ValueError("device MUR3X256 needs 16-byte-multiple chunks")
    batch = data32.shape[:-1]
    nblocks = nbytes // 16
    n = 1
    for d in batch:
        n *= int(d)
    if n == 0:
        return jnp.zeros(batch + (8,), jnp.uint32)
    # packet stream on the NATURAL dims (one transpose, no pre-flatten —
    # the relayout rule mur3_jax measured), then lane-pad to the tile
    nb = len(batch)
    x = data32.reshape(*batch, nblocks, 4)
    ks = jnp.transpose(x, (nb, nb + 1, *range(nb))).reshape(nblocks, 4, n)
    quantum = RT * 128
    npad = -(-n // quantum) * quantum
    if npad != n:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, npad - n)))
    ks = ks.reshape(nblocks, 4, npad // 128, 128)
    out = _jitted(tuple(key_words), nbytes, npad, not on_tpu())(ks)
    # [8, R, 128] -> [npad, 8] -> live lanes; tiny tensor (32 B/chunk)
    dig = jnp.transpose(out.reshape(8, npad), (1, 0))[:n]
    return dig.reshape(batch + (8,))


def _key_words(key: bytes) -> tuple[int, int]:
    from ..native.mur3py import seeds_from_key
    return seeds_from_key(key)


def hash256_chunks(key: bytes, chunks: np.ndarray) -> np.ndarray:
    """Hash every row of uint8 [N, L] -> digests uint8 [N, 32] on device
    (test/bench convenience; production paths trace hash256_device_words
    into fused launches)."""
    chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
    n, ln = chunks.shape
    out = hash256_device_words(_key_words(key), ln,
                               jnp.asarray(chunks.view(np.uint32)))
    return np.asarray(out).view(np.uint8).reshape(n, 32)
