"""Bit-sliced GF(256) Reed-Solomon on device (pure jax.numpy; Pallas version
in rs_pallas.py shares the same math).

Design (SURVEY.md §7.1, the TPU-native replacement for the reference's AVX2
galois-mul kernels behind cmd/erasure-coding.go:70-113):

Shard bytes are packed 4-per-lane into uint32 words. Multiplying every byte of
a packed word by the field generator (x2 in GF(256)) is a SWAR shift/xor with
cross-byte carry masking. A GF multiply by an arbitrary constant ``a`` is the
XOR of the x2-chains selected by the bits of ``a``; with the coefficient bits
pre-expanded to full-word masks (gf256.coeff_masks) the whole shard x matrix
product becomes 8 rounds of AND/XOR on wide integer vectors — no gathers, no
log/antilog tables, exactly the layout the TPU VPU wants.

All entry points are shape-static and jit-cached per (geometry, shard words).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256
from ..obs.device import tracked_jit

_HI = np.uint32(0x80808080)
_LO7 = np.uint32(0xFEFEFEFE)
_RED = np.uint32(0x1D)  # 0x11D mod x^8


def gf2x_packed(x: jnp.ndarray) -> jnp.ndarray:
    """Multiply every byte of uint32-packed data by 2 in GF(256)."""
    hi = x & _HI
    lo = (x << 1) & _LO7
    return lo ^ ((hi >> 7) * _RED)


def pack_shards(shards: np.ndarray) -> np.ndarray:
    """uint8 [..., S] -> uint32 [..., S//4] (S must be a multiple of 4)."""
    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    if shards.shape[-1] % 4:
        raise ValueError(f"shard size {shards.shape[-1]} not a multiple of 4")
    return shards.view(np.uint32)


def unpack_shards(words: np.ndarray) -> np.ndarray:
    """uint32 [..., W] -> uint8 [..., 4W] (always writable: device transfers
    surface as read-only views, but heal/repair callers patch shard bytes)."""
    out = np.ascontiguousarray(words)
    if not out.flags.writeable:
        out = out.copy()
    return out.view(np.uint8)


def gf_matmul_packed(masks: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """GF(256) matrix multiply on packed shards: [8,o,i] masks x [i,W] -> [o,W].

    Statically unrolled over the 8 bit planes; the per-plane XOR reduction over
    input shards is a lax.reduce the compiler fuses with the AND.
    """
    o = masks.shape[1]
    acc = jnp.zeros((o, x.shape[-1]), dtype=jnp.uint32)
    p = x
    for b in range(8):
        t = masks[b][:, :, None] & p[None, :, :]  # [o, i, W]
        acc = acc ^ jax.lax.reduce(t, np.uint32(0), jax.lax.bitwise_xor, (1,))
        if b != 7:
            p = gf2x_packed(p)
    return acc


# vmapped variants; jit applied at call sites with stable shapes. All
# compile sites route through the device plane's tracked wrapper
# (obs/device.tracked_jit, GL017) so recompiles are counted and timed.
_matmul_j = tracked_jit(gf_matmul_packed, op="xla.gf_matmul")
# batch of shard groups, one shared matrix (encode path)
_matmul_batch_shared = tracked_jit(
    jax.vmap(gf_matmul_packed, in_axes=(None, 0)), op="xla.encode_batch")
# batch with per-element matrices (heal path: different loss patterns)
_matmul_batch_per = tracked_jit(
    jax.vmap(gf_matmul_packed, in_axes=(0, 0)), op="xla.rebuild_batch")


def _backend_name(backend: str) -> str:
    import os
    if backend == "auto":
        backend = os.environ.get("MINIO_TPU_RS_BACKEND", "auto")
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def _resolve_backend(backend: str):
    """Pick the device kernels: 'pallas' (hand-tiled, default on TPU),
    'xla' (pure jnp, default elsewhere), or 'auto'. Overridable via the
    MINIO_TPU_RS_BACKEND env knob — the analogue of the reference gating its
    accelerated codec behind config (cmd/config/, MINIO_ERASURE_*)."""
    backend = _backend_name(backend)
    if backend == "pallas":
        from . import rs_pallas
        return rs_pallas.gf_matmul, rs_pallas.gf_matmul_batch, \
            rs_pallas.gf_matmul_batch_per
    if backend == "xla":
        return _matmul_j, _matmul_batch_shared, _matmul_batch_per
    raise ValueError(f"unknown RS backend {backend!r}")


def _device_masks(mat: np.ndarray) -> jnp.ndarray:
    return jnp.asarray(gf256.coeff_masks(mat))


class ReedSolomon:
    """Systematic RS(k, m) codec with the reference Encoder's surface
    (Encode / ReconstructData / Reconstruct / Verify / Split — the interface
    consumed by cmd/erasure-coding.go:70-113), executing on the default JAX
    device. Shard arrays are uint8 [S] with S % 4 == 0 (callers pad; the
    erasure layer's shard-size math guarantees alignment).
    """

    def __init__(self, k: int, m: int, matrix_kind: str = "vandermonde",
                 backend: str = "auto"):
        if m < 1:
            raise ValueError(f"parity shard count must be >= 1, got {m}")
        self.k = k
        self.m = m
        self.n = k + m
        self.matrix = gf256.build_matrix(k, m, matrix_kind)
        self.parity_rows = self.matrix[k:]
        self._enc_masks = _device_masks(self.parity_rows)
        self._decode_cache: dict[tuple[int, ...], np.ndarray] = {}
        self._mask_cache: dict[tuple, jnp.ndarray] = {}
        self._np_mask_cache: dict[tuple, np.ndarray] = {}
        self._mm, self._mm_batch, self._mm_batch_per = _resolve_backend(backend)
        #: donated-input twin of _mm_batch_per, built lazily for the
        #: interactive device lane (batch_per_donated)
        self._batch_per_donated = None
        #: pallas backend: encode runs the static-specialized kernel (the
        #: matrix is fixed per (k, m) — coefficients become compile-time
        #: constants, ~1.4x the dynamic-mask kernel; see rs_pallas.py)
        self._static_encode = _backend_name(backend) == "pallas"

    # -- encode --------------------------------------------------------------

    def encode_words(self, w: jnp.ndarray) -> jnp.ndarray:
        """Device-level encode: uint32 words [k, W] -> [m, W] (no host
        round-trip; dispatch/bench building block)."""
        if self._static_encode:
            from . import rs_pallas
            return rs_pallas.gf_matmul_static(self.parity_rows, w)
        return self._mm(self._enc_masks, w)

    def encode_words_batch(self, w: jnp.ndarray) -> jnp.ndarray:
        """Batched device-level encode: uint32 [B, k, W] -> [B, m, W]."""
        if self._static_encode:
            from . import rs_pallas
            return rs_pallas.gf_matmul_static_batch(self.parity_rows, w)
        return self._mm_batch(self._enc_masks, w)

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data uint8 [k, S] -> parity uint8 [m, S]."""
        w = jnp.asarray(pack_shards(data))
        return unpack_shards(np.asarray(self.encode_words(w)))

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """data uint8 [B, k, S] -> parity uint8 [B, m, S] in one dispatch."""
        w = jnp.asarray(pack_shards(data))
        return unpack_shards(np.asarray(self.encode_words_batch(w)))

    # -- reconstruct ---------------------------------------------------------

    def batch_per_donated(self):
        """The per-element-mask batched rebuild kernel with the SHARD
        WORDS argument donated (``jax.jit(..., donate_argnums=(1,))``):
        the interactive device lane's heal/reconstruct launches hand
        their input HBM buffer to the output, so small latency-tuned
        flushes don't double-allocate device memory per round trip
        (ISSUE 13). Kept as a separate cached wrapper — donation makes
        the input buffer unusable after the call, so the bulk path
        (which may batch the same arrays into a later retry) keeps the
        plain kernel. Wrapping the already-jitted backend fn in an
        outer jit is fine: nested jits inline."""
        fn = self._batch_per_donated
        if fn is None:
            fn = self._batch_per_donated = tracked_jit(
                self._mm_batch_per, op="rebuild_batch_donated",
                donate_argnums=(1,))
        return fn

    def _decode_mat(self, present: tuple[int, ...]) -> np.ndarray:
        mat = self._decode_cache.get(present)
        if mat is None:
            mat = gf256.decode_matrix(self.matrix, self.k, present)
            self._decode_cache[present] = mat
        return mat

    def _cached_masks(self, key: tuple, build) -> jnp.ndarray:
        """Device-resident coefficient masks cached per loss pattern so
        repeated degraded reads skip matrix build + host->device upload."""
        masks = self._mask_cache.get(key)
        if masks is None:
            masks = _device_masks(build())
            self._mask_cache[key] = masks
        return masks

    def _decode_masks(self, present: tuple[int, ...],
                      rows: tuple[int, ...]) -> jnp.ndarray:
        return self._cached_masks(
            (present, rows),
            lambda: self._decode_mat(present)[list(rows), :])

    # -- arbitrary-target rebuild rows (for the dispatch queue) --------------

    def rebuild_rows(self, present: tuple[int, ...],
                     targets: tuple[int, ...]) -> np.ndarray:
        """[len(targets), k] matrix mapping the k chosen present shards to
        any target shards (data or parity): data rows come from the decode
        matrix, parity rows from parity_matrix @ decode_matrix."""
        dec = self._decode_mat(present)
        rows = np.empty((len(targets), self.k), dtype=np.uint8)
        for i, t in enumerate(targets):
            if t < self.k:
                rows[i] = dec[t]
            else:
                rows[i] = gf256.gf_matmul_ref(
                    self.parity_rows[t - self.k: t - self.k + 1], dec)[0]
        return rows

    def target_masks_np(self, present: tuple[int, ...],
                        targets: tuple[int, ...]) -> np.ndarray:
        """Host-side uint32 [8, o, k] masks (o = len(targets)) for
        rebuilding ``targets`` from ``present``. Rows are exact, not
        padded to m: the dispatch queue keys batches by o, and padded
        rows' readback is pure waste (2x the downlink bytes for the
        common 1-2-loss rebuild; link cost, re-measure on the attached
        chip). Cached per pattern."""
        if len(targets) > self.m:
            raise ValueError(
                f"{len(targets)} targets > parity {self.m}: unrecoverable")
        key = ("np-tgt", present, targets)
        masks = self._np_mask_cache.get(key)
        if masks is None:
            masks = gf256.coeff_masks(self.rebuild_rows(present, targets))
            self._np_mask_cache[key] = masks
        return masks

    def _choose_present(self, shards: list[np.ndarray | None]) -> tuple[int, ...]:
        present = tuple(i for i, s in enumerate(shards) if s is not None)
        if len(present) < self.k:
            raise ValueError(
                f"cannot reconstruct: {len(present)} shards present, need {self.k}")
        return present[: self.k]

    def reconstruct(self, shards: list[np.ndarray | None],
                    data_only: bool = False) -> list[np.ndarray]:
        """Fill in missing entries of a length-(k+m) shard list in place
        semantics (returns a new list). ``data_only`` mirrors the reference's
        ReconstructData (cmd/erasure-coding.go:89-104): parity gaps stay None.
        """
        shards = list(shards)
        if len(shards) != self.n:
            raise ValueError(f"expected {self.n} shards, got {len(shards)}")
        missing_data = [i for i in range(self.k) if shards[i] is None]
        missing_parity = [i for i in range(self.k, self.n) if shards[i] is None]
        if not missing_data and (data_only or not missing_parity):
            return shards

        if missing_data:
            chosen = self._choose_present(shards)
            w = jnp.asarray(pack_shards(np.stack([shards[i] for i in chosen])))
            masks = self._decode_masks(chosen, tuple(missing_data))
            out = unpack_shards(np.asarray(self._mm(masks, w)))
            for row, i in enumerate(missing_data):
                shards[i] = out[row]

        if missing_parity and not data_only:
            data = np.stack(shards[: self.k])
            masks = self._cached_masks(
                ("parity", tuple(missing_parity)),
                lambda: self.parity_rows[[i - self.k for i in missing_parity], :])
            out = unpack_shards(np.asarray(
                self._mm(masks, jnp.asarray(pack_shards(data)))))
            for row, i in enumerate(missing_parity):
                shards[i] = out[row]
        return shards

    def reconstruct_batch(self, shards: np.ndarray, present: np.ndarray,
                          ) -> np.ndarray:
        """Batched heal: reconstruct ALL shards for B objects in one dispatch.

        shards: uint8 [B, k+m, S] with garbage in missing slots; present:
        bool [B, k+m] validity. Per element, a full (k+m, k+m... actually
        (n, k)-derived) rebuild matrix maps its first-k present shards to all
        n shards. Per-element matrices differ, so this uses the per-element
        vmapped kernel (BASELINE config 5: 128-object global heal batches).
        """
        B = shards.shape[0]
        gathered = np.empty((B, self.k) + shards.shape[2:], dtype=np.uint8)
        masks = np.empty((B, 8, self.n, self.k), dtype=np.uint32)
        for b in range(B):
            idx = tuple(np.nonzero(present[b])[0][: self.k])
            if len(idx) < self.k:
                raise ValueError(f"batch element {b}: insufficient shards")
            gathered[b] = shards[b, list(idx)]
            dec = self._decode_mat(idx)  # [k, k] from chosen -> data
            full = np.zeros((self.n, self.k), dtype=np.uint8)
            full[: self.k] = dec
            # parity rows: parity = P @ data = (P @ dec) @ chosen
            full[self.k:] = gf256.gf_matmul_ref(self.parity_rows, dec)
            masks[b] = gf256.coeff_masks(full)
        out = self._mm_batch_per(jnp.asarray(masks), jnp.asarray(pack_shards(gathered)))
        return unpack_shards(np.asarray(out))

    # -- verify --------------------------------------------------------------

    def verify(self, shards: np.ndarray) -> bool:
        """shards uint8 [k+m, S] -> True iff parity matches data."""
        shards = np.asarray(shards, dtype=np.uint8)
        w = jnp.asarray(pack_shards(shards[: self.k]))
        par = self.encode_words(w)
        want = jnp.asarray(pack_shards(shards[self.k:]))
        return bool(jnp.all(par == want))

    # -- split (reference Encoder.Split: cmd/erasure-coding.go:74-79) --------

    def split(self, data: bytes | np.ndarray, shard_size: int | None = None
              ) -> np.ndarray:
        """Zero-pad ``data`` to k*shard_size and reshape into [k, shard_size].

        shard_size defaults to ceil(len/k) rounded up to 4-byte alignment.
        """
        buf = np.frombuffer(data, dtype=np.uint8) if isinstance(
            data, (bytes, bytearray, memoryview)) else np.asarray(data, np.uint8)
        if shard_size is None:
            shard_size = -(-len(buf) // self.k)
            shard_size += (-shard_size) % 4
        total = self.k * shard_size
        if len(buf) > total:
            raise ValueError("data longer than k * shard_size")
        out = np.zeros(total, dtype=np.uint8)
        out[: len(buf)] = buf
        return out.reshape(self.k, shard_size)


@functools.lru_cache(maxsize=64)
def get_codec(k: int, m: int, matrix_kind: str = "vandermonde",
              backend: str = "auto") -> ReedSolomon:
    """Process-wide codec cache (matrix build + mask upload amortized)."""
    return ReedSolomon(k, m, matrix_kind, backend)
