"""Plain reference for the ETag the store must return: HighwayHash-256 in
numpy (from the published algorithm, vectorised over equal-length chunks)
and the fold of the data shards' bitrot digests through MD5. Imports nothing
of the program; the geometry comes from the configuration file."""
from __future__ import annotations

import hashlib

import numpy as np

U = np.uint64
_INIT0 = np.array([0xdbe6d5d5fe4cce2f, 0xa4093822299f31d0,
                   0x13198a2e03707344, 0x243f6a8885a308d3], U)
_INIT1 = np.array([0x3bd39e10cb0ef593, 0xc0acf169b5f18a8c,
                   0xbe5466cf34e90c6c, 0x452821e638d01377], U)
_LO32 = U(0xffffffff)


def _rot32(x):
    return (x >> U(32)) | (x << U(32))


def _zipper(v1, v0):
    """(add1, add0) of ZipperMergeAndAdd for lane pairs (v1, v0)."""
    m = lambda x, mask: x & U(mask)  # noqa: E731
    add0 = (((m(v0, 0xff000000) | m(v1, 0xff00000000)) >> U(24))
            | ((m(v0, 0xff0000000000) | m(v1, 0xff000000000000)) >> U(16))
            | m(v0, 0xff0000) | (m(v0, 0xff00) << U(32))
            | (m(v1, 0xff00000000000000) >> U(8)) | (v0 << U(56)))
    add1 = (((m(v1, 0xff000000) | m(v0, 0xff00000000)) >> U(24))
            | m(v1, 0xff0000) | (m(v1, 0xff0000000000) >> U(16))
            | (m(v1, 0xff00) << U(24)) | (m(v0, 0xff000000000000) >> U(8))
            | (m(v1, 0xff) << U(48)) | m(v0, 0xff00000000000000))
    return add1, add0


class _State:
    def __init__(self, key: bytes, n: int):
        k = np.frombuffer(key, "<u8")
        self.mul0 = np.tile(_INIT0, (n, 1))
        self.mul1 = np.tile(_INIT1, (n, 1))
        self.v0 = np.tile(_INIT0 ^ k, (n, 1))
        self.v1 = np.tile(_INIT1 ^ _rot32(k), (n, 1))

    def update(self, lanes):
        s = self
        s.v1 += s.mul0 + lanes
        s.mul0 ^= (s.v1 & _LO32) * (s.v0 >> U(32))
        s.v0 += s.mul1
        s.mul1 ^= (s.v0 & _LO32) * (s.v1 >> U(32))
        for a, b in ((s.v0, s.v1), (s.v1, s.v0)):   # a += zipper(b)
            for lo in (0, 2):
                add1, add0 = _zipper(b[:, lo + 1], b[:, lo])
                a[:, lo] += add0
                a[:, lo + 1] += add1

    def remainder(self, tail: np.ndarray):
        """tail: uint8 [n, r] with 0 < r < 32."""
        n, r = tail.shape
        r4 = r & 3
        packet = np.zeros((n, 32), np.uint8)
        packet[:, : r & ~3] = tail[:, : r & ~3]
        rem = tail[:, r & ~3:]
        if r & 16:
            packet[:, 28:32] = tail[:, r - 4: r]
        elif r4:
            packet[:, 16] = rem[:, 0]
            packet[:, 17] = rem[:, r4 >> 1]
            packet[:, 18] = rem[:, r4 - 1]
        self.v0 += U((r << 32) + r)
        lo = (self.v1 & _LO32).astype(np.uint32)
        hi = (self.v1 >> U(32)).astype(np.uint32)
        rot = lambda x: (x << np.uint32(r)) | (x >> np.uint32(32 - r))  # noqa: E731
        self.v1 = rot(lo).astype(U) | (rot(hi).astype(U) << U(32))
        self.update(packet.view("<u8"))

    def finalize256(self) -> np.ndarray:
        for _ in range(10):
            v = self.v0
            self.update(np.stack([_rot32(v[:, 2]), _rot32(v[:, 3]),
                                  _rot32(v[:, 0]), _rot32(v[:, 1])], 1))
        out = np.empty((self.v0.shape[0], 4), U)
        for lo in (0, 2):
            a3 = (self.v1[:, lo + 1] + self.mul1[:, lo + 1]) \
                & U(0x3fffffffffffffff)
            a2 = self.v1[:, lo] + self.mul1[:, lo]
            a1 = self.v0[:, lo + 1] + self.mul0[:, lo + 1]
            a0 = self.v0[:, lo] + self.mul0[:, lo]
            out[:, lo + 1] = a1 ^ ((a3 << U(1)) | (a2 >> U(63))) \
                ^ ((a3 << U(2)) | (a2 >> U(62)))
            out[:, lo] = a0 ^ (a2 << U(1)) ^ (a2 << U(2))
        return out.view(np.uint8)


def hh256_rows(key: bytes, rows: np.ndarray) -> np.ndarray:
    """HighwayHash-256 of every row of uint8 [n, L] -> uint8 [n, 32]."""
    rows = np.ascontiguousarray(rows, np.uint8)
    n, length = rows.shape
    st = _State(key, n)
    full = length // 32
    if full:
        packets = rows[:, : full * 32].reshape(n, full, 32).view("<u8")
        for i in range(full):
            st.update(packets[:, i, :])
    if length & 31:
        st.remainder(rows[:, full * 32:])
    return st.finalize256()


def shard_digests(key: bytes, shards: np.ndarray, chunk: int) -> bytes:
    """Per-chunk digests of uint8 [k, shard_len], row by row, full chunks
    first and a short tail chunk last: the order of the shard files."""
    k, shard_len = shards.shape
    n_full = shard_len // chunk
    parts = []
    if n_full:
        parts.append(hh256_rows(key, shards[:, : n_full * chunk].reshape(
            k * n_full, chunk)).reshape(k, n_full * 32))
    if shard_len % chunk:
        parts.append(hh256_rows(key, shards[:, n_full * chunk:]))
    return np.concatenate(parts, axis=1).tobytes()


def reference_etags(bodies: list[bytes], geom: dict) -> list[str]:
    """The ETag a PUT of each body must return under ``geom`` (the
    configuration file's ``geometry``): plain MD5 below ``etag_min_bytes``,
    else MD5 over the data shards' bitrot digests, block by block, each
    block split into k zero-padded ceil(len/k) shards. Bodies of one length
    are hashed together (numpy's cost is per call, not per byte)."""
    k, block = geom["data"], geom["block_bytes"]
    full_shard = -(-block // k)
    chunk = geom["bitrot_chunk_bytes"]
    if full_shard % chunk:
        chunk = full_shard
    key = bytes.fromhex(geom["bitrot_key_hex"])
    out: list[str | None] = [None] * len(bodies)
    by_len: dict[int, list[int]] = {}
    for i, b in enumerate(bodies):
        if len(b) < geom["etag_min_bytes"]:
            out[i] = hashlib.md5(b).hexdigest()
        else:
            by_len.setdefault(len(b), []).append(i)
    for length, idx in by_len.items():
        for g in range(0, len(idx), 8):
            group = idx[g: g + 8]
            md5s = [hashlib.md5() for _ in group]
            for off in range(0, length, block):
                n = min(block, length - off)
                shard_len = -(-n // k)
                arr = np.zeros((len(group), k * shard_len), np.uint8)
                for j, i in enumerate(group):
                    arr[j, :n] = np.frombuffer(bodies[i], np.uint8, n, off)
                digs = shard_digests(key, arr.reshape(len(group) * k,
                                                      shard_len), chunk)
                per = len(digs) // len(group)
                for j, m in enumerate(md5s):
                    m.update(digs[j * per: (j + 1) * per])
            for i, m in zip(group, md5s):
                out[i] = m.hexdigest()
    return out
