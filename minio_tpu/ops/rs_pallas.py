"""Pallas TPU kernels for the bit-sliced GF(256) shard-matrix multiply.

Same math as rs_jax.gf_matmul_packed (SWAR x2 chains + per-bit full-word
masks), hand-tiled for the TPU VPU. Replaces the reference's AVX2 galois-mul
assembly (klauspost/reedsolomon, used via cmd/erasure-coding.go:70-113).

Round-5 kernel design (rates are r05 readings on one v5e chip, 16+4 @1 MiB
shards, batch 128 — superseded, not measured on the current tree):

* **Sublane-full layout.** The shard word stream is viewed as
  ``[rows, lanes]`` with ``lanes`` ∈ {256, 512} instead of one flat vector,
  so every vector op covers full (8, 128) vregs. The old flat (o, 2048)
  blocks left 4 of 8 sublanes idle for o=4 encode: 90 GiB/s → 122.
* **Horner accumulation.** parity = Σ_b Σ_j bit_b(a_rj)·x2^b(data_j) is
  evaluated Horner-style over the accumulator: acc = x2(acc) ^ Σ_j m[b]&p_j,
  b = 7..0. The x2 chain then runs on the o output rows instead of the i
  input rows (o=4 vs i=16 for encode): 122 GiB/s → 139.
* **Static specialization** (encode only). The encode matrix is fixed per
  (k, m), so the kernel is generated with the coefficient BITS as
  compile-time constants: the AND disappears and only set bits emit an XOR
  (~50% density): 139 GiB/s → ~195. Reconstruct/heal keep the dynamic-mask
  kernel (per-loss-pattern masks arrive as arrays).

Falls back to interpreter mode off-TPU so the same code paths are
unit-tested on the CPU mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rs_jax import gf2x_packed
from ..obs.device import tracked_jit

# Flat fallback tile (words per grid step) for shard sizes not divisible by
# the sublane layouts' 2048-word quantum.
TILE_W = 2048


def _layout(w: int) -> tuple[int, int, int]:
    """(wpad, tile_rows, lanes) for a shard of w words: pad to a 2048
    multiple, then prefer the (16, 512) block (8192-word quantum) measured
    fastest; smaller shards take (8, 256)."""
    wpad = -(-w // TILE_W) * TILE_W
    if wpad % 8192 == 0:
        return wpad, 16, 512
    return wpad, 8, 256


def _dyn_kernel(masks_ref, x_ref, out_ref):
    """One (i, tile_rows, lanes) block -> (o, tile_rows, lanes) block.

    Horner over bit planes, statically unrolled (Mosaic has no lowering for
    reduce_xor, and straight-line AND/XOR on full-vreg tiles is what the
    VPU wants anyway)."""
    i = x_ref.shape[0]
    p = x_ref[:]
    acc = jnp.zeros(out_ref.shape, dtype=jnp.uint32)
    for b in range(7, -1, -1):
        if b != 7:
            acc = gf2x_packed(acc)
        m = masks_ref[b]  # (o, i) full-word masks
        for j in range(i):
            acc = acc ^ (m[:, j][:, None, None] & p[j][None, :, :])
    out_ref[:] = acc


@functools.partial(tracked_jit, op="pallas.gf_matmul",
                   static_argnames=("interpret",))
def gf_matmul_pallas(masks: jnp.ndarray, x: jnp.ndarray,
                     interpret: bool = False) -> jnp.ndarray:
    """masks uint32 [8, o, i], x uint32 [i, W] -> [o, W].

    W is padded internally; callers see exact shapes.
    """
    _, o, i = masks.shape
    w = x.shape[-1]
    wpad, tl, lanes = _layout(w)
    if wpad != w:
        x = jnp.pad(x, ((0, 0), (0, wpad - w)))
    rows = wpad // lanes
    x3 = x.reshape(i, rows, lanes)
    out = pl.pallas_call(
        _dyn_kernel,
        out_shape=jax.ShapeDtypeStruct((o, rows, lanes), jnp.uint32),
        grid=(rows // tl,),
        in_specs=[
            pl.BlockSpec((8, o, i), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((i, tl, lanes), lambda t: (0, t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((o, tl, lanes), lambda t: (0, t, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(masks, x3)
    out = out.reshape(o, wpad)
    return out[:, :w] if wpad != w else out


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def gf_matmul(masks: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Pallas matmul with automatic interpret fallback off-TPU."""
    return gf_matmul_pallas(masks, x, interpret=not on_tpu())


def _dyn_batch_kernel(masks_ref, x_ref, out_ref):
    """nb batch elements per grid step, per-element masks: small shards
    coalesce so each step still moves ~16K words (mirrors the static
    kernel's _batch_block; the old per-element vmap grid was DMA-bound
    at 64 KiB blocks)."""
    i = x_ref.shape[1]
    p = x_ref[:]
    acc = jnp.zeros(out_ref.shape, dtype=jnp.uint32)
    for b in range(7, -1, -1):
        if b != 7:
            acc = gf2x_packed(acc)
        m = masks_ref[:, b]  # (nb, o, i)
        for j in range(i):
            acc = acc ^ (m[:, :, j][:, :, None, None]
                         & p[:, j][:, None, :, :])
    out_ref[:] = acc


@functools.partial(tracked_jit, op="pallas.matmul_batched",
                   static_argnames=("interpret",))
def _gf_matmul_batched(masks: jnp.ndarray, x: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """masks uint32 [B, 8, o, i], x uint32 [B, i, W] -> [B, o, W]."""
    bsz, _, o, i = masks.shape
    w = x.shape[-1]
    wpad, tl, lanes = _layout(w)
    if wpad != w:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, wpad - w)))
    rows = wpad // lanes
    nb = _batch_block(bsz, wpad)
    x4 = x.reshape(bsz, i, rows, lanes)
    out = pl.pallas_call(
        _dyn_batch_kernel,
        out_shape=jax.ShapeDtypeStruct((bsz, o, rows, lanes), jnp.uint32),
        grid=(bsz // nb, rows // tl),
        in_specs=[
            pl.BlockSpec((nb, 8, o, i), lambda e, t: (e, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nb, i, tl, lanes), lambda e, t: (e, 0, t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((nb, o, tl, lanes),
                               lambda e, t: (e, 0, t, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(masks, x4)
    out = out.reshape(bsz, o, wpad)
    return out[..., :w] if wpad != w else out


@functools.partial(tracked_jit, op="pallas.encode_batch")
def gf_matmul_batch(masks: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """One shared matrix across the batch (encode-shape path): masks
    [8, o, i], x [B, i, W] -> [B, o, W]."""
    b = x.shape[0]
    mb = jnp.broadcast_to(masks, (b,) + masks.shape)
    return _gf_matmul_batched(mb, x, interpret=not on_tpu())


@functools.partial(tracked_jit, op="pallas.rebuild_batch")
def gf_matmul_batch_per(masks: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Per-element matrices (heal path): masks [B, 8, o, i],
    x [B, i, W] -> [B, o, W]."""
    return _gf_matmul_batched(masks, x, interpret=not on_tpu())


# --- static-specialized encode ----------------------------------------------


def _make_static_kernel(bits: tuple, o: int, i: int, tl: int, lanes: int):
    """Kernel with compile-time coefficient bits: only set bits emit an XOR
    (no AND at all). ``bits`` is a hashable ((plane, row, col) -> bool)
    nested tuple, [8][o][i]."""
    def kernel(c_ref, x_ref, out_ref):
        p = x_ref[:]
        zero = jnp.zeros((tl, lanes), jnp.uint32)
        acc: list = [None] * o
        for b in range(7, -1, -1):
            for r in range(o):
                if b != 7 and acc[r] is not None:
                    acc[r] = gf2x_packed(acc[r])
                for j in range(i):
                    if bits[b][r][j]:
                        acc[r] = p[j] if acc[r] is None else acc[r] ^ p[j]
        rows = [a if a is not None else zero for a in acc]
        # dependency hook for chained micro-benchmarks (pass c=0 in
        # production; one vreg XOR per tile)
        rows[0] = rows[0] ^ c_ref[0]
        out_ref[:] = jnp.stack(rows)
    return kernel


@functools.lru_cache(maxsize=256)
def _static_call(mat_bytes: bytes, o: int, i: int, w: int, interpret: bool):
    """Jitted [i, W] -> [o, W] multiply for one fixed matrix."""
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(o, i)
    bits = tuple(tuple(tuple(bool((mat[r, j] >> b) & 1)
                             for j in range(i)) for r in range(o))
                 for b in range(8))
    wpad, tl, lanes = _layout(w)
    rows = wpad // lanes
    kernel = _make_static_kernel(bits, o, i, tl, lanes)

    @functools.partial(tracked_jit, op="pallas.static_encode")
    def mm(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
        if wpad != w:
            x = jnp.pad(x, ((0, 0), (0, wpad - w)))
        x3 = x.reshape(i, rows, lanes)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((o, rows, lanes), jnp.uint32),
            grid=(rows // tl,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((i, tl, lanes), lambda t: (0, t, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((o, tl, lanes), lambda t: (0, t, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
        )(c.reshape(1), x3)
        out = out.reshape(o, wpad)
        return out[:, :w] if wpad != w else out
    return mm


def gf_matmul_static(mat: np.ndarray, x: jnp.ndarray,
                     c: jnp.ndarray | int = 0) -> jnp.ndarray:
    """x uint32 [i, W] times the FIXED uint8 matrix mat [o, i] (compile-time
    specialized; cached per matrix+shape)."""
    o, i = mat.shape
    fn = _static_call(np.ascontiguousarray(mat).tobytes(), o, i,
                      int(x.shape[-1]), not on_tpu())
    return fn(x, jnp.asarray(c, dtype=jnp.uint32))


def _batch_block(b: int, wpad: int) -> int:
    """Batch elements per grid step: small shards coalesce so each step
    still moves ~16K words (per-step DMA overhead dominated the old
    per-element grid at 64 KiB blocks: 126 -> 183 GiB/s with nb=8)."""
    want = max(1, 16384 // wpad)
    nb = 1
    while nb * 2 <= want and b % (nb * 2) == 0:
        nb *= 2
    return nb


def _make_static_batch_kernel(bits: tuple, nb: int, o: int, i: int,
                              tl: int, lanes: int):
    def kernel(c_ref, x_ref, out_ref):
        p = x_ref[:]  # (nb, i, tl, lanes)
        zero = jnp.zeros((nb, tl, lanes), jnp.uint32)
        acc: list = [None] * o
        for b in range(7, -1, -1):
            for r in range(o):
                if b != 7 and acc[r] is not None:
                    acc[r] = gf2x_packed(acc[r])
                for j in range(i):
                    if bits[b][r][j]:
                        acc[r] = p[:, j] if acc[r] is None \
                            else acc[r] ^ p[:, j]
        rows = [a if a is not None else zero for a in acc]
        rows[0] = rows[0] ^ c_ref[0]
        out_ref[:] = jnp.stack(rows, axis=1)
    return kernel


@functools.lru_cache(maxsize=256)
def _static_batch_call(mat_bytes: bytes, o: int, i: int, bsz: int, w: int,
                       interpret: bool):
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(o, i)
    bits = tuple(tuple(tuple(bool((mat[r, j] >> b) & 1)
                             for j in range(i)) for r in range(o))
                 for b in range(8))
    wpad, tl, lanes = _layout(w)
    rows = wpad // lanes
    nb = _batch_block(bsz, wpad)
    kernel = _make_static_batch_kernel(bits, nb, o, i, tl, lanes)

    @functools.partial(tracked_jit, op="pallas.static_encode_batch")
    def mm(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
        if wpad != w:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, wpad - w)))
        x4 = x.reshape(bsz, i, rows, lanes)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((bsz, o, rows, lanes),
                                           jnp.uint32),
            grid=(bsz // nb, rows // tl),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((nb, i, tl, lanes), lambda e, t: (e, 0, t, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((nb, o, tl, lanes),
                                   lambda e, t: (e, 0, t, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
        )(c.reshape(1), x4)
        out = out.reshape(bsz, o, wpad)
        return out[..., :w] if wpad != w else out
    return mm


def gf_matmul_static_batch(mat: np.ndarray, x: jnp.ndarray,
                           c: jnp.ndarray | int = 0) -> jnp.ndarray:
    """Batched static multiply: x uint32 [B, i, W] -> [B, o, W]."""
    o, i = mat.shape
    fn = _static_batch_call(np.ascontiguousarray(mat).tobytes(), o, i,
                            int(x.shape[0]), int(x.shape[-1]), not on_tpu())
    return fn(x, jnp.asarray(c, dtype=jnp.uint32))
