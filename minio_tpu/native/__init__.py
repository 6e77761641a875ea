"""Native (C++) components: build-on-demand via g++, loaded through ctypes.

The reference keeps its hot math in assembly-backed Go modules (SURVEY.md
§2.10); here one combined libnative.so (pipeline.cpp, which includes
gf256_simd.cpp + highwayhash.cpp) provides:

- the CPU GF(256) codec (fallback path + the AVX2 baseline the device
  codec is compared with),
- AVX2 HighwayHash-256 (bitrot digests),
- the fused per-block data-plane calls ``mt_put_block`` / ``mt_get_block``
  (split+encode+hash+frame, verify+assemble) and
  ``mt_get_block_pread_degraded`` (pread+verify+rebuild+assemble) that
  carry the end-to-end object path on the CPU route,
- a PUT's per-drive file-system sequences ``mt_stage_file`` /
  ``mt_close_fds`` / ``mt_commit_version`` / ``mt_commit_inline`` /
  ``mt_commit_part`` (storage/xlstorage.py: a shard file staged, a version
  or a multipart part committed, in one call each), a read's
  ``mt_open_shard`` (open + fstat) and ``mt_read_file`` (a whole file, an
  ``xl.meta``: open, fstat, read, close).

All entry points release the GIL (plain ctypes CDLL calls), so concurrent
requests scale across cores where the host has them.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger("minio_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")
_LOCK = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_error: Exception | None = None

_SOURCES = ("pipeline.cpp", "gf256_simd.cpp", "highwayhash.cpp", "mur3.cpp",
            "md5_simd.cpp")

#: Bitrot algorithm ids shared with native/pipeline.cpp hash_many().
ALGO_HIGHWAY = 0
ALGO_MUR3 = 1


#: THE build command line (one fixed flag set: the binary must be the
#: same from host to host, so no -march=native and no fallback ladder —
#: a compiler that refuses these flags is an error, reported verbatim)
BUILD_FLAGS = ("-O3", "-mavx2", "-shared", "-fPIC")


def build_key() -> str:
    """Content key of the library: sha256 over the five sources' bytes
    plus the compiler flags. Stored beside the .so (``libnative.key``);
    a mismatch rebuilds. mtimes play no part — a copied or freshly
    checked-out tree carries none worth trusting."""
    h = hashlib.sha256(" ".join(("g++",) + BUILD_FLAGS).encode())
    for name in _SOURCES:
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(b"\0" + name.encode() + b"\0" + f.read())
    return h.hexdigest()


def _compile(src: str, out: str) -> None:
    """Build ``out`` from ``src`` with the one command line, into a
    temporary name inside ``_build/`` that is os.replace'd into place:
    several processes (test workers, two servers) may build at the same
    moment, and none may ever load a half-written file."""
    os.makedirs(_BUILD, exist_ok=True)
    tmp = f"{out}.tmp.{os.getpid()}.{threading.get_ident()}"
    cmd = ["g++", *BUILD_FLAGS, src, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build failed (rc {proc.returncode}): "
                f"{' '.join(cmd)}\n"
                f"{proc.stderr.decode(errors='replace')[-4000:]}")
        # a build artifact, not stored data: no fsync policy applies
        os.replace(tmp, out)  # graftlint: disable=GL009
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def load_native() -> ctypes.CDLL:
    """Build (once) and load the combined native library. A failure is
    cached: without this, every request on a host where the build fails
    would retry full g++ runs serialized under _LOCK instead of falling
    back to the Python path once.

    Lock-free fast path once loaded: the data plane calls this per block,
    and 8 concurrent PUT streams convoy measurably on the lock (sampled
    at ~1/3 the cost of the entire fused native call)."""
    global _lib, _load_error
    lib = _lib
    if lib is not None:
        return lib
    with _LOCK:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            raise _load_error
        try:
            # deliberate blocking-under-lock: one-time lazy build under
            # the double-checked init lock — concurrent first callers
            # MUST wait for the single compile rather than racing it
            return _load_native_locked()  # graftlint: disable=GL021
        except Exception as e:  # noqa: BLE001
            _load_error = e
            # once, loudly: available() turns this into False for
            # library users without a toolchain, and the on-disk bitrot
            # default then changes — nobody may discover that by luck
            log.error("native library unavailable (pure-Python "
                      "fallbacks engage): %s", e)
            raise


def _load_native_locked() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        out = os.path.join(_BUILD, "libnative.so")
        key = build_key()
        key_path = os.path.join(_BUILD, "libnative.key")
        try:
            with open(key_path) as f:
                built = f.read().strip()
        except OSError:
            built = ""
        if built != key or not os.path.exists(out):
            _compile(os.path.join(_DIR, "pipeline.cpp"), out)
            # after the .so, and plainly: a torn key only fails to match
            # and rebuilds
            with open(key_path, "w") as f:
                f.write(key + "\n")
        lib = ctypes.CDLL(out)
        c_u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.gf256_encode.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_long]
        lib.gf256_encode.restype = None
        lib.gf256_has_avx2.restype = ctypes.c_int
        lib.hh256.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                              ctypes.c_long, ctypes.c_char_p]
        lib.hh256.restype = None
        lib.hh256_batch.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                    ctypes.c_int, ctypes.c_long,
                                    ctypes.c_long, ctypes.c_char_p]
        lib.hh256_batch.restype = None
        lib.hh256_multi.argtypes = [ctypes.c_char_p,
                                    ctypes.POINTER(ctypes.c_void_p),
                                    ctypes.POINTER(ctypes.c_long),
                                    ctypes.c_int, ctypes.c_char_p]
        lib.hh256_multi.restype = None
        lib.hh256_ref.argtypes = lib.hh256.argtypes
        lib.hh256_ref.restype = None
        lib.hh64.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_long]
        lib.hh64.restype = ctypes.c_uint64
        lib.mt_framed_len.argtypes = [ctypes.c_long, ctypes.c_long]
        lib.mt_framed_len.restype = ctypes.c_long
        lib.mt_put_block.argtypes = [
            c_u8p, ctypes.c_long, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int, ctypes.c_long, ctypes.c_long, ctypes.c_char_p,
            c_u8p, ctypes.c_int]
        lib.mt_put_block.restype = None
        lib.mt_put_block_fds.argtypes = [
            c_u8p, ctypes.c_long, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int, ctypes.c_long, ctypes.c_long, ctypes.c_char_p,
            c_u8p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
            ctypes.c_long, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_double)]
        lib.mt_put_block_fds.restype = None
        lib.mt_get_block.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_long,
            ctypes.c_long, ctypes.c_char_p, c_u8p, ctypes.c_int]
        lib.mt_get_block.restype = ctypes.c_int
        lib.mt_verify_framed.argtypes = [c_u8p, ctypes.c_long, ctypes.c_long,
                                         ctypes.c_char_p, ctypes.c_int]
        lib.mt_verify_framed.restype = ctypes.c_long
        lib.mt_get_block_pread.argtypes = [
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_long),
            ctypes.c_int, ctypes.c_long, ctypes.c_long, ctypes.c_char_p,
            c_u8p, c_u8p, ctypes.c_int]
        lib.mt_get_block_pread.restype = ctypes.c_long
        lib.mt_get_block_pread_degraded.argtypes = [
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_long,
            ctypes.c_long, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int), ctypes.c_int, c_u8p, c_u8p,
            ctypes.c_int]
        lib.mt_get_block_pread_degraded.restype = ctypes.c_long
        lib.mur3x256.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                 ctypes.c_long, ctypes.c_char_p]
        lib.mur3x256.restype = None
        lib.mur3x256_batch.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                       ctypes.c_int, ctypes.c_long,
                                       ctypes.c_long, ctypes.c_char_p]
        lib.mur3x256_batch.restype = None
        lib.mur3x256_many.argtypes = [ctypes.c_char_p,
                                      ctypes.POINTER(ctypes.c_void_p),
                                      ctypes.POINTER(ctypes.c_long),
                                      ctypes.c_int, ctypes.c_char_p]
        lib.mur3x256_many.restype = None
        lib.md5_multi_segments.argtypes = [
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_int), ctypes.c_int]
        lib.md5_multi_segments.restype = None
        lib.md5_init_state.argtypes = [ctypes.POINTER(ctypes.c_uint32)]
        lib.md5_init_state.restype = None
        lib.md5_finish.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_char_p, ctypes.c_long,
            ctypes.c_ulonglong, c_u8p]
        lib.md5_finish.restype = None
        lib.mt_stage_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.mt_stage_file.restype = ctypes.c_int
        lib.mt_open_shard.argtypes = [ctypes.c_char_p]
        lib.mt_open_shard.restype = ctypes.c_int
        lib.mt_read_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                     ctypes.c_long,
                                     ctypes.POINTER(ctypes.c_long)]
        lib.mt_read_file.restype = ctypes.c_long
        lib.mt_close_fds.argtypes = [
            ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int)]
        lib.mt_close_fds.restype = None
        lib.mt_commit_version.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_long, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int)]
        lib.mt_commit_version.restype = ctypes.c_int
        lib.mt_commit_inline.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_long, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
        lib.mt_commit_inline.restype = ctypes.c_int
        lib.mt_commit_part.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_long, ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
        lib.mt_commit_part.restype = ctypes.c_int
        _lib = lib
    return _lib


def load_gf256() -> ctypes.CDLL:
    """Back-compat alias: the combined library serves the gf256 symbols."""
    return load_native()


def available() -> bool:
    try:
        load_native()
        return True
    except Exception:  # noqa: BLE001 — no toolchain: pure-Python fallbacks
        return False


def cpu_encode(matrix, data, rows_out: int):
    """numpy convenience wrapper: matrix [o,i] uint8, data [i,S] uint8 -> [o,S]."""
    lib = load_native()
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    o = rows_out
    out = np.empty((o, data.shape[1]), dtype=np.uint8)
    lib.gf256_encode(
        matrix.ctypes.data_as(ctypes.c_char_p), o, data.shape[0],
        data.ctypes.data_as(ctypes.c_char_p),
        out.ctypes.data_as(ctypes.c_char_p), data.shape[1])
    return out


_fl_cache: dict[tuple[int, int], int] = {}


def framed_len(shard_len: int, chunk: int) -> int:
    key = (shard_len, chunk)
    v = _fl_cache.get(key)
    if v is None:
        if len(_fl_cache) > 4096:
            _fl_cache.clear()
        v = _fl_cache[key] = load_native().mt_framed_len(shard_len, chunk)
    return v


_u8p = ctypes.POINTER(ctypes.c_uint8)


def put_block(data, data_len: int, pmat: np.ndarray, k: int, m: int,
              shard_len: int, chunk: int, key: bytes,
              algo: int = ALGO_HIGHWAY, out: np.ndarray | None = None
              ) -> np.ndarray:
    """Fused split+encode+hash+frame for one erasure block.

    ``data`` is a readable buffer of ``data_len`` bytes; returns a uint8
    array of (k+m)*framed_len bytes — shard i's framed bytes are
    ``out[i*framed_len:(i+1)*framed_len]`` (slice views, no copies).
    ``out``, when given, must be a uint8 array of exactly that size
    (bufpool recycling); it is filled and returned.
    """
    lib = load_native()
    if k + m > 256 or k <= 0 or m < 0 or chunk <= 0:
        raise ValueError(f"unsupported geometry k={k} m={m} chunk={chunk}")
    fl = lib.mt_framed_len(shard_len, chunk)
    if out is None:
        out = np.empty((k + m) * fl, dtype=np.uint8)
    elif out.nbytes != (k + m) * fl:
        raise ValueError("put_block: out buffer size mismatch")
    src = np.frombuffer(data, dtype=np.uint8, count=data_len)
    pmat = np.ascontiguousarray(pmat, dtype=np.uint8)
    lib.mt_put_block(
        src.ctypes.data_as(_u8p), data_len,
        pmat.ctypes.data_as(ctypes.c_char_p), k, m, shard_len, chunk, key,
        out.ctypes.data_as(_u8p), algo)
    return out


def put_block_fds(data, data_len: int, pmat: np.ndarray, k: int, m: int,
                  shard_len: int, chunk: int, key: bytes, fds: list[int],
                  offset: int, algo: int = ALGO_HIGHWAY,
                  scratch: np.ndarray | None = None,
                  times: np.ndarray | None = None) -> list[int]:
    """Fused split+encode+hash+frame+pwrite for one erasure block: shard
    i's framed bytes go to fds[i] at byte ``offset`` (fds[i] < 0 skips).
    Returns the per-shard error list (0 ok / errno / -1 short write).
    ``scratch`` is the (k+m)*framed_len staging buffer (bufpool);
    ``times``, when a float64[2] array, receives (encode+hash seconds,
    pwrite seconds) for stage attribution."""
    lib = load_native()
    if k + m > 256 or k <= 0 or m < 0 or chunk <= 0:
        raise ValueError(f"unsupported geometry k={k} m={m} chunk={chunk}")
    if len(fds) != k + m:
        raise ValueError("put_block_fds: need one fd slot per shard")
    fl = framed_len(shard_len, chunk)
    if scratch is None:
        scratch = np.empty((k + m) * fl, dtype=np.uint8)
    elif scratch.nbytes != (k + m) * fl:
        raise ValueError("put_block_fds: scratch buffer size mismatch")
    src = np.frombuffer(data, dtype=np.uint8, count=data_len)
    pmat = np.ascontiguousarray(pmat, dtype=np.uint8)
    cfds = (ctypes.c_int * (k + m))(*fds)
    errs = (ctypes.c_int * (k + m))()
    tptr = None
    if times is not None:
        if times.dtype != np.float64 or times.size != 2:
            raise ValueError("put_block_fds: times must be float64[2]")
        tptr = times.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    lib.mt_put_block_fds(
        src.ctypes.data_as(_u8p), data_len,
        pmat.ctypes.data_as(ctypes.c_char_p), k, m, shard_len, chunk, key,
        scratch.ctypes.data_as(_u8p), algo, cfds, offset, errs, tptr)
    return list(errs)


def get_block(framed: list, k: int, plen: int, chunk: int, key: bytes,
              algo: int = ALGO_HIGHWAY, out: np.ndarray | None = None
              ) -> tuple[np.ndarray, int]:
    """Fused verify+assemble: k framed shard buffers -> (block uint8
    [k*plen], bad_shard) where bad_shard is -1 on success. ``out``, when
    given, must be uint8 of exactly k*plen bytes (bufpool recycling)."""
    lib = load_native()
    if k <= 0 or k > 256 or chunk <= 0:
        raise ValueError(f"unsupported geometry k={k} chunk={chunk}")
    arrs = [np.frombuffer(f, dtype=np.uint8) for f in framed]
    ptrs = (ctypes.c_void_p * k)(*[a.ctypes.data for a in arrs])
    if out is None:
        out = np.empty(k * plen, dtype=np.uint8)
    elif out.nbytes != k * plen:
        raise ValueError("get_block: out buffer size mismatch")
    bad = lib.mt_get_block(ptrs, k, plen, chunk, key,
                           out.ctypes.data_as(_u8p), algo)
    return out, bad


def _pread_buffers(k: int, plen: int, chunk: int,
                   scratch: np.ndarray | None, out: np.ndarray | None
                   ) -> tuple[np.ndarray, np.ndarray]:
    """The pread calls' (scratch, out): k framed spans and the k*plen
    assembled block; a buffer the caller passes must have that size."""
    fl = framed_len(plen, chunk)
    if scratch is None:
        scratch = np.empty(k * fl, dtype=np.uint8)
    elif scratch.nbytes != k * fl:
        raise ValueError("pread block: scratch size mismatch")
    if out is None:
        out = np.empty(k * plen, dtype=np.uint8)
    elif out.nbytes != k * plen:
        raise ValueError("pread block: out size mismatch")
    return scratch, out


def get_block_pread(fds: list[int], offsets: list[int], k: int, plen: int,
                    chunk: int, key: bytes, algo: int = ALGO_HIGHWAY,
                    scratch: np.ndarray | None = None,
                    out: np.ndarray | None = None
                    ) -> tuple[np.ndarray, int]:
    """Fused pread+verify+assemble for one healthy-read block: shard i's
    framed span is read from fds[i] at offsets[i]. Returns (block uint8
    [k*plen], code) with code -1 ok, >=0 first corrupt shard, <=-10 a
    failed read on shard -(code+10). ``scratch``/``out`` recycle through
    the bufpool."""
    lib = load_native()
    if k <= 0 or k > 256 or chunk <= 0:
        raise ValueError(f"unsupported geometry k={k} chunk={chunk}")
    if len(fds) != k or len(offsets) != k:
        raise ValueError("get_block_pread: need one fd+offset per shard")
    scratch, out = _pread_buffers(k, plen, chunk, scratch, out)
    cfds = (ctypes.c_int * k)(*fds)
    coffs = (ctypes.c_long * k)(*offsets)
    code = lib.mt_get_block_pread(
        cfds, coffs, k, plen, chunk, key, scratch.ctypes.data_as(_u8p),
        out.ctypes.data_as(_u8p), algo)
    return out, int(code)


def get_block_pread_degraded(fds: list[int], offsets: list[int],
                             src_idx: tuple[int, ...], k: int, plen: int,
                             chunk: int, key: bytes, rows: np.ndarray,
                             missing: tuple[int, ...],
                             algo: int = ALGO_HIGHWAY,
                             scratch: np.ndarray | None = None,
                             out: np.ndarray | None = None
                             ) -> tuple[np.ndarray, int]:
    """Fused pread+verify+rebuild+assemble for one degraded-read block:
    source j (global shard ``src_idx[j]``, ascending, k of them) is read
    from fds[j] at offsets[j]; every source chunk digest is verified;
    the missing data shards ``missing`` are rebuilt with ``rows``
    (uint8 [len(missing), k] over the chosen sources). Returns (block
    uint8 [k*plen], code) with code -1 ok, >=0 the POSITION in
    ``src_idx`` of the first corrupt source, <=-10 a failed read on
    source -(code+10). ``scratch``/``out`` recycle through the bufpool
    exactly as in get_block_pread."""
    lib = load_native()
    if k <= 0 or k > 256 or chunk <= 0:
        raise ValueError(f"unsupported geometry k={k} chunk={chunk}")
    if len(fds) != k or len(offsets) != k or len(src_idx) != k:
        raise ValueError(
            "get_block_pread_degraded: need one fd+offset+index per source")
    # every data shard is written exactly once: copied from a source or
    # rebuilt (the native call indexes `out` by these without a check)
    data_src = [i for i in src_idx if i < k]
    if sorted(data_src + list(missing)) != list(range(k)) or \
            list(src_idx) != sorted(set(src_idx)) or \
            not 0 <= src_idx[0] <= src_idx[-1] < 256:
        raise ValueError("get_block_pread_degraded: sources (ascending, "
                         "distinct) and missing must cover the data "
                         "shards exactly")
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    if rows.shape != (len(missing), k):
        raise ValueError("get_block_pread_degraded: rows shape mismatch")
    scratch, out = _pread_buffers(k, plen, chunk, scratch, out)
    cfds = (ctypes.c_int * k)(*fds)
    coffs = (ctypes.c_long * k)(*offsets)
    cidx = (ctypes.c_int * k)(*src_idx)
    cmiss = (ctypes.c_int * max(1, len(missing)))(*missing)
    code = lib.mt_get_block_pread_degraded(
        cfds, coffs, cidx, k, plen, chunk, key,
        rows.ctypes.data_as(ctypes.c_char_p), cmiss, len(missing),
        scratch.ctypes.data_as(_u8p), out.ctypes.data_as(_u8p), algo)
    return out, int(code)


def verify_framed(framed, plen: int, chunk: int, key: bytes,
                  algo: int = ALGO_HIGHWAY) -> int:
    """Verify one framed span; returns -1 ok or the first corrupt chunk."""
    lib = load_native()
    arr = np.frombuffer(framed, dtype=np.uint8)
    return lib.mt_verify_framed(arr.ctypes.data_as(_u8p), plen, chunk, key,
                                algo)


# --- a request's file-system sequences (storage/xlstorage.py) ---------------

#: mt_commit_version's, mt_commit_inline's and mt_commit_part's steps, as the
#: result names the one that failed
COMMIT_OBJECT_DIR = 1
COMMIT_STAGED = 2
COMMIT_DATA_RENAME = 3
COMMIT_META_WRITE = 4
COMMIT_META_RENAME = 5
COMMIT_FSYNC = 6


def stage_file(base: str, rel: str) -> int:
    """mkdir the directories of ``rel`` below ``base`` (which has to be
    there) and open the file for writing, in one call. Returns the fd,
    or ``-errno``."""
    return load_native().mt_stage_file(os.fsencode(base), os.fsencode(rel))


def open_shard(path: str) -> int:
    """Open a shard file for reading and see that it is no directory, in
    one call. Returns the fd, or ``-errno`` (``-EISDIR`` for a
    directory)."""
    return load_native().mt_open_shard(os.fsencode(path))


#: what ``read_file`` reads in ONE call: an ``xl.meta`` that carries an
#: inline shard is 8.5 KiB a drive for a 64 KiB object at 8+4 and ~33 KiB
#: at the 128 KiB threshold on 4+2
READ_FILE_ONE_CALL = 64 << 10

_read_buf = threading.local()


def read_file(path: str) -> bytes | int:
    """A whole file (open, fstat, read to the size fstat gave, close) in
    one call: its bytes, or ``-errno`` (``-EISDIR`` for a directory). A
    file of up to ``READ_FILE_ONE_CALL`` bytes (64 KiB) lands in a buffer
    the thread keeps; a larger one costs a second call, into a buffer of
    its size."""
    buf = getattr(_read_buf, "buf", None)
    if buf is None:
        buf = _read_buf.buf = ctypes.create_string_buffer(READ_FILE_ONE_CALL)
    lib, raw, size = load_native(), os.fsencode(path), ctypes.c_long()
    while True:
        n = lib.mt_read_file(raw, buf, len(buf), ctypes.byref(size))
        if n < 0:
            return n
        if size.value <= len(buf):
            return ctypes.string_at(buf, n)
        buf = ctypes.create_string_buffer(size.value)


def close_fds(fds: list[int], do_fsync: bool) -> list[int]:
    """Close ``fds`` in one call, each fsynced first when ``do_fsync``; an
    fd below 0 is passed over. Returns the fsyncs' errnos (0 ok); every
    fd is closed either way."""
    n = len(fds)
    errs = (ctypes.c_int * n)()
    load_native().mt_close_fds((ctypes.c_int * n)(*fds), n, int(do_fsync),
                               errs)
    return list(errs)


def commit_version(vol: str, obj: str, ddir: str, src: str, tmp_parent: str,
                   meta: bytes, purge: list[str], do_fsync: bool
                   ) -> list[int]:
    """The file-system half of one drive's ``rename_data`` in one call
    (native/pipeline.cpp mt_commit_version has the steps). Returns [step
    (0, or the ``COMMIT_*`` step that failed), its errno, the kind of a
    failed fsync (0 file, 1 dir), file fsyncs made, dir fsyncs made, data
    directories not purged, tmp parent not removed]."""
    out = (ctypes.c_int * 7)()
    names = b"".join(os.fsencode(n) + b"\0" for n in purge)
    load_native().mt_commit_version(
        os.fsencode(vol), os.fsencode(obj), os.fsencode(ddir),
        os.fsencode(src), os.fsencode(tmp_parent), meta, len(meta), names,
        len(purge), int(do_fsync), out)
    return list(out)


def commit_inline(vol: str, obj: str, tmp: str, meta: bytes,
                  purge: list[str], do_fsync: bool) -> list[int]:
    """``commit_version`` for a version whose shard rides in ``meta``
    (xl.meta's ``Data``): the object directory, ``meta`` written to
    ``tmp`` and renamed over ``xl.meta``, the replaced data directories
    removed; no data directory, nothing staged. The result reads as
    ``commit_version``'s."""
    out = (ctypes.c_int * 7)()
    names = b"".join(os.fsencode(n) + b"\0" for n in purge)
    load_native().mt_commit_inline(
        os.fsencode(vol), os.fsencode(obj), os.fsencode(tmp), meta,
        len(meta), names, len(purge), int(do_fsync), out)
    return list(out)


def commit_part(vol: str, part: str, src: str, tmp: str, tmp_parent: str,
                meta: bytes, do_fsync: bool) -> list[int]:
    """The file-system half of one drive's ``commit_part`` in one call
    (native/pipeline.cpp mt_commit_part has the steps): the staged shard
    file ``src`` renamed to ``<vol>/<part>``, ``meta`` written to ``tmp``
    and renamed to ``<vol>/<part>.meta``, the emptied ``tmp_parent``
    removed. The result reads as ``commit_version``'s."""
    out = (ctypes.c_int * 7)()
    load_native().mt_commit_part(
        os.fsencode(vol), os.fsencode(part), os.fsencode(src),
        os.fsencode(tmp), os.fsencode(tmp_parent), meta, len(meta),
        int(do_fsync), out)
    return list(out)
