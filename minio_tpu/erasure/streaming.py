"""Streaming blockwise erasure pipeline: encode (write fan-out), decode
(minimal-read gather + reconstruct), heal — the TPU rebuild of the
reference's hot loops (cmd/erasure-encode.go:73-109, cmd/erasure-decode.go:
102-283, cmd/erasure-lowlevel-heal.go:28-48).

Parallelism note (SURVEY.md §2.2 table): the reference's per-disk goroutines
become a shared thread pool here — shard I/O (local file or remote RPC) is
the blocking part and overlaps across disks; the GF(256) math itself runs as
one device dispatch per block (and batches across concurrent requests via
minio_tpu.runtime.dispatch), which replaces `WithAutoGoroutines` CPU
sharding.
"""
from __future__ import annotations

import io
import os
import time
from collections import deque
from concurrent.futures import (FIRST_COMPLETED, Future, ThreadPoolExecutor,
                                wait)
from dataclasses import dataclass, field

import numpy as np

from .. import fault as _fault
from ..obs import metrics as _mx
from ..obs import spans as _spans
from ..obs import stages as _stages
from ..runtime import completion as _compl
from ..utils import errors
from .codec import Erasure, ceil_div

# Shared I/O pool for shard fan-out. Sized for several concurrent requests
# over 16-20-disk sets; pure-I/O tasks so oversubscription is fine.
_io_pool: ThreadPoolExecutor | None = None


def io_pool() -> ThreadPoolExecutor:
    global _io_pool
    if _io_pool is None:
        # scale with the host: local-disk "IO" on tmpfs/page-cache is
        # really CPU (memcpy), so a 64-thread pool on a small host only
        # buys GIL churn; remote-RPC deployments can raise the floor via
        # MINIO_TPU_IO_THREADS
        workers = int(os.environ.get(
            "MINIO_TPU_IO_THREADS",
            str(min(64, max(8, 4 * (os.cpu_count() or 1))))))
        _io_pool = ThreadPoolExecutor(max_workers=workers,
                                      thread_name_prefix="minio-tpu-io")
    return _io_pool


# Pool for the GIL-releasing native per-block calls (mt_put_block /
# mt_get_block): sized to the host so pipelined blocks from one stream and
# concurrent streams both scale across cores.
_encode_pool: ThreadPoolExecutor | None = None


def encode_pool() -> ThreadPoolExecutor:
    global _encode_pool
    if _encode_pool is None:
        _encode_pool = ThreadPoolExecutor(
            max_workers=max(4, os.cpu_count() or 1),
            thread_name_prefix="minio-tpu-encode")
    return _encode_pool


def shutdown_pools() -> None:
    """Drain and drop the shared IO/encode pools (minio_tpu.shutdown());
    they are rebuilt lazily on next use."""
    global _io_pool, _encode_pool
    io_p, _io_pool = _io_pool, None
    enc_p, _encode_pool = _encode_pool, None
    for p in (io_p, enc_p):
        if p is not None:
            p.shutdown(wait=True)


def _native_put_eligible(erasure: Erasure, writers: list) -> bool:
    """True when the whole block pipeline (split+encode+hash+frame) can run
    as one native GIL-releasing call per block (native/pipeline.cpp
    mt_put_block) with on-disk output bit-identical to the Python path.
    The chunk-divides-shard condition (via _framed_writers) makes
    per-block framing equal stream framing (pick_bitrot_chunk guarantees
    it for new objects)."""
    if os.environ.get("MINIO_TPU_PUT_PATH", "auto") == "dispatch":
        return False
    if _fault.armed("disk"):
        # chaos runs take the interpretable Python path: the native
        # pwrite pipeline bypasses the per-op injection points
        return False
    if _framed_writers(erasure, writers) is None:
        return False
    from .. import native
    return native.available()


def _framed_writers(erasure: Erasure, writers: list):
    """(chunk, algo_id) when every live writer is a StreamingBitrotWriter
    on one native-id algorithm with one chunk size dividing the full-block
    shard — the precondition for digest-reuse framing (write_framed with
    digests from the native call, the dispatch encode+hash flush, or the
    host fallback helper). None otherwise."""
    from .bitrot import StreamingBitrotWriter, native_algo_id
    live = [w for w in writers if w is not None]
    if not live:
        return None
    if not all(isinstance(w, StreamingBitrotWriter)
               and native_algo_id(w.algo) is not None
               and not w._buf for w in live):
        return None
    chunks = {w.shard_size for w in live}
    if len(chunks) != 1:
        return None
    (chunk,) = chunks
    if erasure.shard_size() % chunk:
        return None
    return chunk, native_algo_id(live[0].algo)


def _native_get_eligible(erasure: Erasure, readers: list):
    """(chunk, algo_id) when reads can run as ONE native GIL-released call
    per block (mt_get_block / mt_get_block_pread, and
    mt_get_block_pread_degraded when a data shard is missing): at least
    k live readers, every live one a StreamingBitrotReader on one
    native-id algorithm with one chunk size dividing the shard. None
    otherwise. Which k of them serve a block is chosen per block from
    reader liveness (erasure_decode.submit)."""
    if os.environ.get("MINIO_TPU_GET_PATH", "auto") == "dispatch":
        return None
    if _fault.armed("disk"):
        # chaos runs need the Python shard reads (where read_at faults
        # inject and hedging mitigates); the fused C pread would bypass
        # both
        return None
    from .bitrot import StreamingBitrotReader, native_algo_id
    live = [r for r in readers if r is not None]
    if len(live) < erasure.data_blocks:
        return None
    if not all(isinstance(r, StreamingBitrotReader)
               and native_algo_id(r.algo) is not None for r in live):
        return None
    if len({r.algo for r in live}) != 1:
        return None
    chunks = {r.shard_size for r in live}
    if len(chunks) != 1:
        return None
    (chunk,) = chunks
    if erasure.shard_size() % chunk:
        return None
    from .. import native
    if not native.available():
        return None
    return chunk, native_algo_id(live[0].algo)


@dataclass
class DecodeStats:
    """Per-call telemetry: which shard sources failed (for heal-on-read,
    cmd/erasure-object.go:325-336)."""
    errs: list = field(default_factory=list)  # per-reader exception or None
    bytes_written: int = 0
    hedged: int = 0           # hedge reads fired across the call's blocks


# --- hedged reads (Dean & Barroso, "The Tail at Scale", CACM 2013) -----------
#
# A GET launches exactly k data-shard reads; when none of the in-flight
# reads completes within the hedge threshold, one replacement (parity)
# read is issued WITHOUT declaring the straggler dead, and the first k
# distinct shards to arrive reconstruct the block through the normal TPU
# decode path. The threshold tracks the p95 of the last-minute shard-read
# latency window (obs/latency.py), clamped to [floor, ceil].

#: hedging master switch ("0" disables; default on)
HEDGE_ENV = "MINIO_TPU_HEDGE"
#: fixed threshold override in ms (skips the p95 computation entirely)
HEDGE_MS_ENV = "MINIO_TPU_HEDGE_MS"
HEDGE_FLOOR_MS_ENV = "MINIO_TPU_HEDGE_FLOOR_MS"
HEDGE_CEIL_MS_ENV = "MINIO_TPU_HEDGE_CEIL_MS"
#: threshold = max(floor, MULT * p95(shard_read window)) — the multiple
#: keeps normal jitter from firing wasted parity reads
HEDGE_P95_MULT = 3.0

#: latency-window family fed by every shard read and consumed by
#: hedge_threshold_s() (one unlabeled series: the threshold is global,
#: per-disk skew is exactly what hedging routes around)
_HEDGE_FAMILY = "hedge"


def _hedge_knob(key: str, env: str, default: str) -> str:
    """Resolve a ``fault.hedge*`` knob through the config registry
    (env > stored > default) so dynamic config changes take effect
    without env mutation; pure-library use falls back to env."""
    try:
        from ..config import get_config_sys
        return get_config_sys().get("fault", key)
    except Exception:  # noqa: BLE001 — registry unavailable/unloaded
        return os.environ.get(env, default)


def hedging_enabled() -> bool:
    return _hedge_knob("hedge", HEDGE_ENV, "1") not in ("0", "off")


#: adaptive threshold cache: the p95 scan walks the window's slots in
#: Python, and a GET calls this once per block wave — recompute at most
#: every THRESHOLD_TTL_S instead (value, monotonic stamp)
_THRESHOLD_TTL_S = 0.5
_threshold_cache: tuple[float, float] = (0.0, -1.0)


def hedge_threshold_s() -> float:
    """Current hedge trigger in seconds."""
    global _threshold_cache
    ms = _hedge_knob("hedge_ms", HEDGE_MS_ENV, "")
    if ms:
        try:
            return max(1e-3, float(ms) / 1e3)
        except ValueError:
            pass
    val, stamp = _threshold_cache
    now = time.monotonic()
    if 0.0 <= now - stamp < _THRESHOLD_TTL_S:
        return val
    from ..obs import latency as _lat
    win = _lat.get_window(_HEDGE_FAMILY, op="shard_read")
    p95 = win.percentiles((0.95,))[0.95]
    # floor/ceil read per refresh (not at import) so dynamic config /
    # tests changing them actually move the clamp
    try:
        floor = float(_hedge_knob("hedge_floor_ms",
                                  HEDGE_FLOOR_MS_ENV, "25"))
        ceil = float(_hedge_knob("hedge_ceil_ms",
                                 HEDGE_CEIL_MS_ENV, "1000"))
    except ValueError:
        floor, ceil = 25.0, 1000.0
    val = min(ceil / 1e3, max(floor / 1e3, HEDGE_P95_MULT * p95))
    _threshold_cache = (val, now)
    return val


def _observe_shard_read(dur_s: float, nbytes: int) -> None:
    from ..obs import latency as _lat
    _lat.observe(_HEDGE_FAMILY, dur_s, nbytes, op="shard_read")


def parallel_write_shards(writers: list, shards: list[np.ndarray],
                          write_quorum: int) -> None:
    """Write shard i to writers[i] concurrently; offline/failed writers are
    nulled out so later blocks skip them; enforce write quorum per block
    (reference parallelWriter.Write, cmd/erasure-encode.go:29-71)."""
    futs = {}
    errs: list[BaseException | None] = [None] * len(writers)
    for i, w in enumerate(writers):
        if w is None:
            errs[i] = errors.DiskNotFound()
            continue
        futs[i] = io_pool().submit(_spans.wrap_ctx(w.write),
                                   shards[i].tobytes())
    for i, f in futs.items():
        try:
            f.result()
        except Exception as e:  # noqa: BLE001 — disk errors become votes
            errs[i] = e if isinstance(e, errors.StorageError) \
                else errors.FaultyDisk(str(e))
            writers[i] = None
    err = errors.reduce_write_quorum_errs(
        errs, errors.BASE_IGNORED_ERRS, write_quorum)
    if err is not None:
        raise err


#: Blocks in flight per stream: deep enough to fill a dispatch batch from a
#: single hot PUT, shallow enough to bound buffering (window * block_size
#: bytes live at once).
ENCODE_WINDOW = int(os.environ.get("MINIO_TPU_ENCODE_WINDOW", "16"))

#: The native per-block path doesn't batch into device launches, so its
#: window only needs to cover pipeline overlap (encode pool + write chains).
#: A deep window on a small host is pure thread churn — measured 4.5x worse
#: 8-way-parallel PUT at window 16 vs 4 on one core.
NATIVE_WINDOW = min(ENCODE_WINDOW, max(4, 2 * (os.cpu_count() or 1)))

#: cap on per-stream in-flight payload BYTES for the native window —
#: the window is denominated in blocks, so a bigger default block must
#: not silently multiply peak memory per hot stream
NATIVE_WINDOW_BYTES = int(os.environ.get(
    "MINIO_TPU_NATIVE_WINDOW_BYTES", str(16 << 20)))


def native_window_for(block_size: int) -> int:
    return max(2, min(NATIVE_WINDOW,
                      NATIVE_WINDOW_BYTES // max(1, block_size)))


class _OrderedWriter:
    """Serializes one shard writer's writes while letting different
    writers (and different blocks) proceed concurrently: each write chains
    onto the previous one's future, so block N+1's shard write starts the
    moment block N's finishes on THAT disk — no per-block barrier across
    disks (the reference gets this from one goroutine per disk,
    cmd/erasure-encode.go:36-54)."""

    def __init__(self, writer):
        self.writer = writer
        self._last: Future | None = None
        self._dead: BaseException | None = None

    def write_async(self, data: bytes) -> Future:
        return self._chain(lambda: self.writer.write(data))

    def write_framed_async(self, framed) -> Future:
        """Chain a pre-framed write (native fast path: digests already
        interleaved by mt_put_block)."""
        return self._chain(lambda: self.writer.write_framed(framed))

    def _chain(self, op) -> Future:
        out: Future = Future()
        if self._dead is not None:
            # A prior write on this disk already failed; don't keep paying
            # for up to a window of doomed writes to a known-dead sink.
            out.set_exception(self._dead)
            return out

        def run():
            try:
                out.set_result(op())
            except Exception as e:  # noqa: BLE001
                self._dead = e
                out.set_exception(e)

        # bind the span context at ENQUEUE time — by the time the chained
        # callback fires, the executing thread is an arbitrary pool one
        wrapped = _spans.wrap_ctx(run)
        prev, self._last = self._last, out
        if prev is None:
            io_pool().submit(wrapped)
        else:
            # always hop to the pool: add_done_callback runs inline in the
            # CALLING thread when prev is already done, which would pull
            # the blocking write onto the encoder thread and serialize the
            # whole fan-out
            prev.add_done_callback(
                lambda _f: io_pool().submit(wrapped))
        return out


class _DirectWriter:
    """``_OrderedWriter``'s twin for a sink in memory (``BufferSink``: an
    inline version's shard, objectlayer/erasure_objects.py): the write is
    a copy of a few KiB, made where it is asked for. Nothing waits on a
    drive, so nothing goes to the io pool: a hop there is a turn at the
    interpreter lock a drive, which is what an inline PUT saves."""

    def __init__(self, writer):
        self.writer = writer

    def write_async(self, data: bytes) -> Future:
        return _run_now(self.writer.write, data)

    def write_framed_async(self, framed) -> Future:
        return _run_now(self.writer.write_framed, framed)


def _run_now(op, *args) -> Future:
    """A pool's ``submit``, run at once on the caller's thread."""
    out: Future = Future()
    try:
        out.set_result(op(*args))
    except Exception as e:  # noqa: BLE001 — a vote, as on the pool
        out.set_exception(e)
    return out


def _in_memory(writers: list) -> bool:
    """True when every live writer's sink is a ``BufferSink``."""
    live = [w for w in writers if w is not None]
    return bool(live) and all(
        isinstance(getattr(w, "sink", None), BufferSink) for w in live)


def erasure_encode(erasure: Erasure, stream, writers: list,
                   write_quorum: int, etag=None) -> int:
    """Read the stream block by block, erasure-encode on device, fan shards
    out to ``writers`` (bitrot writers or None for offline disks). Returns
    total bytes consumed (reference Erasure.Encode,
    cmd/erasure-encode.go:73-109).

    Pipelined twice over: up to ENCODE_WINDOW blocks are in flight through
    the dispatch queue (so one stream's blocks batch into few device
    launches), and shard writes ride per-disk ordered chains so disks never
    barrier on each other between blocks; write-quorum errors are harvested
    per block as its writes drain.

    Block bodies are read into POOLED buffers via the stream's readinto
    (zero-copy ingest: no per-block ``bytes`` materialization between the
    socket and the encode call); streams without readinto keep the legacy
    bytes path.

    When every live writer is HighwayHash-framed and the native library is
    built, each block instead runs as ONE GIL-releasing mt_put_block call
    (split+encode+hash+frame fused, native/pipeline.cpp) on encode_pool —
    block-level pipelining then scales across cores, which the per-stage
    Python path cannot (the round-2 e2e wall). Without the native build,
    framed writers route through the dispatch queue's fused encode+hash
    flush (device-side hash lane) and the host only interleaves the
    returned digests; only tail/unaligned blocks fall back to host
    hashing (counted in minio_tpu_pipeline_host_fallback_total).

    ``etag``, when given, is a utils.hashreader.PipelineETag collector:
    every block's data-shard chunk digests are folded into it IN STREAM
    ORDER no matter which path produced them, so the fused ETag is
    deterministic across native/device/fallback execution. Callers arm it
    only when _framed_writers matches (the object layer's eligibility
    gate)."""
    total = 0
    #: sinks in memory (an inline version's shards): the block is encoded
    #: and its spans are copied out on the caller's thread, pool-free
    in_memory = _in_memory(writers)
    owriters = [None if w is None else
                _DirectWriter(w) if in_memory else _OrderedWriter(w)
                for w in writers]
    # per-block entries: [kind, fut, shard_len, buf, digs]
    enc_window: deque = deque()
    write_window: deque = deque()  # per-block (kind, payload)
    stc = _stages.active()

    from ..runtime.bufpool import global_pool
    pool = global_pool()
    k, m = erasure.data_blocks, erasure.parity_blocks
    native_path = _native_put_eligible(erasure, writers)
    framed = _framed_writers(erasure, writers)
    chunk = algo_id = None
    if framed is not None:
        from .bitrot import HIGHWAY_KEY
        chunk, algo_id = framed
    fd_path = False
    if native_path:
        from .. import native
        pmat = np.ascontiguousarray(erasure.codec.parity_rows)
        # fused-write eligibility: every live sink is a local file (has a
        # real fd) — then the whole block, shard writes included, runs as
        # ONE native call and Python never touches the framed bytes
        fds = []
        for w in writers:
            try:
                fds.append(-1 if w is None else w.sink.fileno())
            except (AttributeError, OSError):
                fds = []
                break
        fd_path = bool(fds)
        fd_offset = 0
    # dispatch-framed path: the device (or CPU completer) computes parity
    # AND per-chunk digests in one coalesced flush; eligibility per block
    # checked in encode_block (full chunk-aligned shards only)
    dispatch_framed = (not native_path) and framed is not None \
        and not _fault.armed("disk")

    def _collect(digs: np.ndarray) -> None:
        """Fold one block's data-shard digests into the fused-ETag
        collector (stream order is the caller's responsibility)."""
        if etag is not None:
            with _stages.timed(stc, "etag"):
                etag.add_digests(np.ascontiguousarray(digs[:k]).data)

    def _extract_digests(fr2d: np.ndarray, shard_len: int) -> np.ndarray:
        """Data-shard digest slots out of framed shard spans
        (uint8 [k, framed_len]) — one strided gather, ~0.2% of payload."""
        h = 32
        n_full = shard_len // chunk
        tail = shard_len - n_full * chunk
        nc = n_full + (1 if tail else 0)
        digs = np.empty((k, nc * h), dtype=np.uint8)
        if n_full:
            digs[:, : n_full * h] = fr2d[:k, : n_full * (h + chunk)] \
                .reshape(k, n_full, h + chunk)[:, :, :h].reshape(k, -1)
        if tail:
            pos = n_full * (h + chunk)
            digs[:, n_full * h:] = fr2d[:k, pos: pos + h]
        return digs

    def fd_block(buf, buf_len: int, shard_len: int, offset: int):
        fl = native.framed_len(shard_len, chunk)
        scratch = pool.get((k + m) * fl)
        try:
            use = [fds[i] if writers[i] is not None else -1
                   for i in range(len(writers))]
            times = np.zeros(2, dtype=np.float64) if stc is not None \
                else None
            # one native call: it says how its time split between the
            # encode and the writes, and the boundary hands that share on
            with _stages.timed(stc, "encode_hash") as b:
                codes = native.put_block_fds(
                    buf, buf_len, pmat, k, m, shard_len, chunk, HIGHWAY_KEY,
                    use, offset, algo_id, scratch=scratch, times=times)
                if times is not None and times[0] > 0.0:
                    b.split("shard_write",
                            float(times[1] / (times[0] + times[1])))
            digs = _extract_digests(scratch.reshape(k + m, fl), shard_len) \
                if etag is not None else None
            return codes, digs
        finally:
            pool.put(scratch)

    def nat_block(buf, buf_len: int, shard_len: int, out: np.ndarray):
        with _stages.timed(stc, "encode_hash"):
            return native.put_block(buf, buf_len, pmat, k, m, shard_len,
                                    chunk, HIGHWAY_KEY, algo_id, out=out)

    def _plain_writes_fallback(shards, shard_len: int) -> dict:
        """Sanctioned host fallback (GL010): non-framed writers (whole-
        file bitrot, no-native blake2b) take per-shard bytes writes —
        the writers hash internally — and an armed ETag collector is fed
        host-computed digests so the fused ETag stays defined."""
        if etag is not None and shard_len and chunk:
            from .bitrot import shard_chunk_digests
            _collect(shard_chunk_digests(
                np.stack(shards[:k]), chunk, algo_id))
        futs = {}
        for i, ow in enumerate(owriters):
            if ow is None or writers[i] is None:
                continue
            futs[i] = ow.write_async(shards[i].tobytes())
        return futs

    def encode_block(buf, buf_arr=None):
        """One block into the pipeline; ``buf_arr`` is the pooled backing
        buffer to recycle once the block's bytes are consumed."""
        buf_len = len(buf) if not isinstance(buf, np.ndarray) else buf.size
        if native_path:
            if not buf_len:
                return ["nat", None, 0, buf_arr, None]
            shard_len = ceil_div(buf_len, k)
            if fd_path:
                nonlocal fd_offset
                off = fd_offset
                fd_offset += native.framed_len(shard_len, chunk)
                # pure CPU kernel work — records no spans, no ctx handoff
                return ["fd", encode_pool().submit(fd_block, buf, buf_len,  # graftlint: disable=GL005
                                                   shard_len, off),
                        shard_len, buf_arr, None]
            # sinks in memory: a few KiB, encoded where they are asked for
            run = _run_now if in_memory else encode_pool().submit
            fut = run(  # graftlint: disable=GL005 — pure kernel compute
                nat_block, buf, buf_len, shard_len,
                pool.get((k + m) * native.framed_len(shard_len, chunk)))
            return ["nat", fut, shard_len, buf_arr, None]
        shard_len = ceil_div(buf_len, k) if buf_len else 0
        align = 16 if algo_id == 1 else 4  # device-hash chunk quantum
        if dispatch_framed and buf_len and shard_len % chunk == 0 \
                and chunk % align == 0:
            # device-side hash lane: parity + all-shard digests in one
            # coalesced flush; the host only interleaves frames
            fut = erasure.encode_hashed_async(buf, chunk, algo_id)
            entry = ["pyh", fut, shard_len, buf_arr, None]
        elif framed is not None and buf_len:
            # framed writers but an ineligible block (tail / unaligned /
            # chaos run): host digest fallback, framing still reuses the
            # digests so nothing is hashed twice. The reason label keeps
            # the cases apart: a short final block vs a chunk failing the
            # device-hash quantum (every block, a config smell) vs the
            # non-dispatch (chaos) route
            if not dispatch_framed:
                reason = "path"
            elif shard_len % chunk:
                reason = "tail_block"
            else:
                reason = "unaligned_chunk"
            _mx.inc("minio_tpu_pipeline_host_fallback_total",
                    reason=reason)
            entry = ["pyf", erasure.encode_data_async(buf), shard_len,
                     buf_arr, None]
        else:
            entry = ["py", erasure.encode_data_async(buf), shard_len,
                     buf_arr, None]
        # the async encode paths copied the payload during split():
        # the pooled block buffer is free the moment submit returns
        if buf_arr is not None:
            pool.put(buf_arr)
            entry[3] = None
        return entry

    def start_writes(entry):
        kind, fut, shard_len, buf_arr, digs = entry
        futs = {}
        framed_buf = None
        if kind == "fd":
            # shard writes already ride inside the native call
            write_window.append(("fd", (fut, buf_arr)))
            return
        if kind in ("py", "pyf", "pyh"):
            with _stages.timed(stc, "encode_hash"):
                res = fut.result()
            if kind == "pyh":
                # 2-D data/parity straight from the flush: framing below
                # is the host's ONLY payload pass (no restack)
                data2d, parity2d, digs = res
            elif kind == "pyf":
                shards = res
                # host digest fallback over ALL k+m shards (parity
                # frames need digests too), in the framing order
                from .bitrot import shard_chunk_digests
                with _stages.timed(stc, "encode_hash"):
                    data2d = np.stack(shards[:k])
                    parity2d = np.stack(shards[k:])
                    digs = np.concatenate([
                        shard_chunk_digests(data2d, chunk, algo_id),
                        shard_chunk_digests(parity2d, chunk, algo_id)])
            if kind in ("pyh", "pyf"):
                _collect(digs)
                from .bitrot import frame_block_shards
                fl = digs.shape[1] + data2d.shape[1]
                framed_all = np.empty((k + m, fl), dtype=np.uint8)
                frame_block_shards(data2d, digs[:k], chunk,
                                   out=framed_all[:k])
                frame_block_shards(parity2d, digs[k:], chunk,
                                   out=framed_all[k:])
                for i, ow in enumerate(owriters):
                    if ow is None or writers[i] is None:
                        continue
                    futs[i] = ow.write_framed_async(framed_all[i])
            else:
                futs = _plain_writes_fallback(res, shard_len)
        else:  # "nat"
            framed_buf = fut.result() if fut is not None else None
            fl = native.framed_len(shard_len, chunk) \
                if framed_buf is not None else 0
            if framed_buf is not None and etag is not None:
                _collect(_extract_digests(
                    framed_buf.reshape(k + m, fl), shard_len))
            if buf_arr is not None:
                pool.put(buf_arr)  # native call done: block buffer free
                entry[3] = None
            for i, ow in enumerate(owriters):
                if ow is None or writers[i] is None:
                    continue
                span = framed_buf[i * fl:(i + 1) * fl] \
                    if framed_buf is not None else b""
                futs[i] = ow.write_framed_async(span)
        write_window.append(("w", (futs, framed_buf)))

    def harvest_writes():
        kind, payload = write_window.popleft()
        errs: list[BaseException | None] = [None] * len(writers)
        for i in range(len(writers)):
            if writers[i] is None:
                errs[i] = errors.DiskNotFound()
        if kind == "fd":
            fut, buf_arr = payload
            try:
                codes, digs = fut.result()
                if digs is not None:
                    _collect(digs)
            except Exception as e:  # noqa: BLE001 — whole block failed:
                # every live disk gets a vote, quorum math decides
                codes = None
                for i in range(len(writers)):
                    if writers[i] is not None:
                        errs[i] = errors.FaultyDisk(str(e))
                        writers[i] = None
            pool.put(buf_arr)  # native call done: block buffer free
            if codes is not None:
                for i, code in enumerate(codes):
                    if code and writers[i] is not None:
                        errs[i] = errors.FaultyDisk(
                            f"pwrite failed: {os.strerror(code)}"
                            if code > 0 else "pwrite: short write")
                        writers[i] = None
        else:
            futs, framed_buf = payload
            with _stages.timed(stc, "shard_write"):
                for i, f in futs.items():
                    try:
                        f.result()
                    except Exception as e:  # noqa: BLE001 — errors are votes
                        errs[i] = e if isinstance(e, errors.StorageError) \
                            else errors.FaultyDisk(str(e))
                        writers[i] = None
            if framed_buf is not None:
                # all shard writes for this block are done (results
                # harvested above); its framed buffer can carry the next
                pool.put(framed_buf)
        err = errors.reduce_write_quorum_errs(
            errs, errors.BASE_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise err

    bs = erasure.block_size
    use_readinto = hasattr(stream, "readinto")

    def read_block():
        """One block's payload: (buf, backing pooled array or None)."""
        if not use_readinto:
            with _stages.timed(stc, "body_read"):
                b = _read_full(stream, bs)
            return b, None
        arr = pool.get(bs)
        try:
            with _stages.timed(stc, "body_read"):
                got = _read_full_into(stream, arr)
        except BaseException:
            # client disconnect mid-read must not leak the pooled
            # buffer: each drop refills the pool via fresh allocations
            pool.put(arr)
            raise
        if got == 0:
            pool.put(arr)
            return b"", None
        _mx.inc("minio_tpu_pipeline_zero_copy_bytes_total", got,
                path="put")
        return arr[:got], arr

    win = native_window_for(erasure.block_size) if native_path \
        else ENCODE_WINDOW
    eof = False
    try:
        while not eof or enc_window or write_window:
            while not eof and len(enc_window) < win:
                buf, buf_arr = read_block()
                blen = len(buf) if not isinstance(buf, np.ndarray) \
                    else buf.size
                if not blen:
                    eof = True
                    if total == 0 and not enc_window:
                        # empty object: one empty block for quorum
                        # accounting
                        enc_window.append(encode_block(b""))
                    break
                if blen < bs:
                    eof = True
                total += blen
                enc_window.append(encode_block(buf, buf_arr))
            if enc_window:
                start_writes(enc_window.popleft())
            while len(write_window) > (win if enc_window or not eof
                                       else 0):
                harvest_writes()
    except BaseException:
        # quiesce in-flight writes before propagating: the caller will
        # abort/close the writers, and a background write racing an abort
        # corrupts writer state (or, on the fd path, pwrites into a
        # recycled file descriptor)
        for entry in enc_window:
            if entry[0] == "fd" and entry[1] is not None:
                try:
                    entry[1].result()
                except Exception:  # noqa: BLE001
                    pass
        for kind, payload in write_window:
            if kind == "fd":
                try:
                    payload[0].result()
                except Exception:  # noqa: BLE001
                    pass
                continue
            for f in payload[0].values():
                try:
                    f.result()
                except Exception:  # noqa: BLE001
                    pass
        raise
    return total


def _read_full(stream, n: int) -> bytes:
    """Read up to n bytes, looping over short reads (io.ReadFull)."""
    chunks = []
    got = 0
    while got < n:
        b = stream.read(n - got)
        if not b:
            break
        chunks.append(b)
        got += len(b)
    return b"".join(chunks)


def _read_full_into(stream, arr: np.ndarray) -> int:
    """readinto form of _read_full: fill ``arr`` from the stream, looping
    over short reads; returns bytes read. The zero-copy ingest leg —
    block payloads land directly in pooled buffers, no intermediate
    ``bytes`` object per block."""
    mv = memoryview(arr)
    got = 0
    n = len(mv)
    while got < n:
        r = stream.readinto(mv[got:])
        if not r:
            break
        got += r
    return got


class _ParallelReader:
    """Minimal-read shard gather: exactly ``data_blocks`` concurrent reads,
    replacement reads fired only on failure, preferring earlier (data) shards
    (reference parallelReader + readTriggerCh, cmd/erasure-decode.go:30-188).
    """

    def __init__(self, readers: list, erasure: Erasure):
        self.readers = list(readers)
        self.erasure = erasure
        self.errs: list[BaseException | None] = [None] * len(readers)
        self.last_digests: list[bytes | None] = [None] * len(readers)
        self.hedged = 0  # hedge reads fired across this reader's blocks
        for i, r in enumerate(self.readers):
            if r is None:
                self.errs[i] = errors.DiskNotFound()

    def fusable(self, shard_len: int) -> bool:
        """True when this block's source digests can be verified on device
        (fused verify+reconstruct): every live reader supports raw chunk
        reads, all share one bitrot chunk size, and the read covers whole
        word-aligned chunks (tail blocks fall back to the CPU verify)."""
        live = [r for r in self.readers if r is not None]
        if not live or not all(getattr(r, "fusable", False) for r in live):
            return False
        chunks = {r.shard_size for r in live}
        if len(chunks) != 1 or len({r.algo for r in live}) != 1:
            return False
        (c,) = chunks
        return shard_len > 0 and c % 4 == 0 and shard_len % c == 0

    def fuse_chunk(self) -> int:
        return next(r.shard_size for r in self.readers if r is not None)

    def fuse_algo(self) -> int:
        """Native ALGO_* id of the live readers' bitrot algorithm (the
        fusable gate guarantees one exists)."""
        from .bitrot import native_algo_id
        a = native_algo_id(
            next(r.algo for r in self.readers if r is not None))
        return 0 if a is None else a

    def read_block(self, shard_offset: int, shard_len: int, raw: bool = False
                   ) -> list[np.ndarray | None]:
        """Return a k+m shard list with >= k filled entries or raise
        ErasureReadQuorum. With raw=True, chunk digests are NOT verified on
        the CPU — they are collected into self.last_digests for the fused
        device verify (cmd/bitrot-streaming.go:151's per-chunk CPU check
        moved into the reconstruct launch)."""
        k = self.erasure.data_blocks
        n = len(self.readers)
        shards: list[np.ndarray | None] = [None] * n
        digests: list[bytes | None] = [None] * n
        pending: dict[object, int] = {}  # future -> reader index
        t_launch: dict[object, float] = {}
        next_idx = 0

        def launch_one() -> int | None:
            nonlocal next_idx
            while next_idx < n:
                i = next_idx
                next_idx += 1
                if self.readers[i] is None:
                    continue
                fn = self.readers[i].read_at_raw if raw \
                    else self.readers[i].read_at
                f = io_pool().submit(_spans.wrap_ctx(fn), shard_offset,
                                     shard_len)
                pending[f] = i
                t_launch[f] = time.monotonic()
                return i
            return None

        for _ in range(k):
            if launch_one() is None:
                break
        done = 0
        hedge_t = hedge_threshold_s() if hedging_enabled() else None
        hedged_idx: set[int] = set()
        while pending and done < k:
            # first-completed order so a fast failure fires its replacement
            # read while slower disks are still in flight (the readTriggerCh
            # overlap property of the reference)
            ready, _ = wait(list(pending), timeout=hedge_t,
                            return_when=FIRST_COMPLETED)
            if not ready:
                # hedge trigger: nothing completed within the threshold —
                # fire ONE replacement (parity) read without declaring the
                # stragglers dead ("The Tail at Scale"); first k distinct
                # shards win, abandoned stragglers are simply not consumed
                i = launch_one()
                if i is None:
                    hedge_t = None  # nothing left to hedge with: wait out
                    continue
                hedged_idx.add(i)
                self.hedged += 1
                self._note_hedge(i)
                continue
            for f in ready:
                i = pending.pop(f)
                try:
                    # already done (came back from wait()): the helper
                    # keeps the GL015 funnel uniform at ~zero wall
                    data = _compl.await_result(f, op="shard_read")
                    _observe_shard_read(
                        time.monotonic() - t_launch.pop(f, 0.0), shard_len)
                    if raw:
                        digests[i], data = data
                    shards[i] = np.frombuffer(data, dtype=np.uint8)
                    done += 1
                except Exception as e:  # noqa: BLE001
                    t_launch.pop(f, None)
                    self.errs[i] = e if isinstance(e, errors.StorageError) \
                        else errors.FaultyDisk(str(e))
                    self.readers[i] = None
                    launch_one()
        if done < k:
            err = errors.reduce_read_quorum_errs(
                self.errs, errors.BASE_IGNORED_ERRS, k)
            raise err if err is not None else errors.ErasureReadQuorum()
        if hedged_idx:
            from ..obs import metrics as mx
            won = any(shards[i] is not None for i in hedged_idx)
            mx.inc("minio_tpu_hedged_reads_total",
                   outcome="won" if won else "lost")
        self.last_digests = digests
        return shards

    @staticmethod
    def _note_hedge(idx: int) -> None:
        """Count the fired hedge and annotate the live span tree (the
        hedged/tripped paths must be visible in a chaos run's traces)."""
        from ..obs import metrics as mx
        mx.inc("minio_tpu_hedged_reads_total", outcome="fired")
        try:
            from ..obs import spans as sp
            ctx = sp.current()
            if ctx is None or not ctx.sampled:
                return
            sp.record({
                "name": "hedge.read", "trace_id": ctx.trace_id,
                "span_id": sp.new_span_id(),
                "parent_span_id": ctx.span_id, "time": time.time(),
                "duration_s": 0.0, "error": "",
                "attrs": {"shard": idx}})
        except Exception:  # noqa: BLE001 — obs must never break reads
            pass

    def drop_corrupt(self, corrupt: tuple[int, ...]) -> None:
        """Mark sources whose device-verified digests mismatched as failed
        so subsequent blocks use replacements (heal-on-read will see the
        FileCorrupt votes in self.errs)."""
        for i in corrupt:
            self.errs[i] = errors.FileCorrupt("bitrot hash mismatch")
            self.readers[i] = None


def erasure_decode(erasure: Erasure, writer, readers: list, offset: int,
                   length: int, total_length: int) -> DecodeStats:
    """Gather-and-reconstruct read path (reference Erasure.Decode,
    cmd/erasure-decode.go:205-283): stream [offset, offset+length) of the
    original object into ``writer``; readers are bitrot shard readers (None
    = offline). Returns per-reader error stats for heal-on-read."""
    if offset < 0 or length < 0 or offset + length > total_length:
        raise ValueError("invalid decode range")
    stats = DecodeStats()
    preader = _ParallelReader(readers, erasure)
    stats.errs = preader.errs
    if length == 0:
        return stats
    # standing GET attribution (obs/attribution.py): shard_read /
    # decode / write_out charge the armed per-request collector; free
    # when nothing is armed
    stc = _stages.active()

    k = erasure.data_blocks
    bs = erasure.block_size
    start_block = offset // bs
    end_block = (offset + length) // bs

    native_get = _native_get_eligible(erasure, readers)
    if native_get:
        from .. import native
        from ..runtime.bufpool import global_pool
        from .bitrot import HIGHWAY_KEY
        fuse_chunk, get_algo_id = native_get
        pool = global_pool()
        #: rebuild rows of the degraded native call, per chosen sources
        #: (which fix the missing data shards; the matrix inversion
        #: behind them is cached on the codec)
        rows_cache: dict = {}

    def pread_block(fds, offs, shard_len, out=None, rebuild=None):
        """One native call: pread k framed spans + verify + assemble.
        ``out`` may be a reserved view into the sink's final buffer
        (zero-copy scatter); otherwise a pooled buffer is used.
        ``rebuild`` = (present, missing, rows) on a degraded block: the
        spans are those of the k chosen sources ``present`` and the
        ``missing`` data shards are rebuilt from them by ``rows`` in the
        same call."""
        scratch = pool.get(k * native.framed_len(shard_len, fuse_chunk))
        if out is None:
            out = pool.get(k * shard_len)
        try:
            if rebuild is None:
                return native.get_block_pread(
                    fds, offs, k, shard_len, fuse_chunk, HIGHWAY_KEY,
                    get_algo_id, scratch=scratch, out=out)
            present, missing, rows = rebuild
            return native.get_block_pread_degraded(
                fds, offs, present, k, shard_len, fuse_chunk, HIGHWAY_KEY,
                rows, missing, get_algo_id, scratch=scratch, out=out)
        finally:
            pool.put(scratch)

    def read_framed_k(shard_offset: int, shard_len: int):
        """Concurrently read the k data shards' framed spans; on any read
        failure mark the reader dead and return None (the caller falls back
        to the generic replacement-read path for this block)."""
        futs = {io_pool().submit(
                    _spans.wrap_ctx(preader.readers[i].read_framed),
                    shard_offset, shard_len): i
                for i in range(k)}
        out: list = [None] * k
        failed = False
        for f, i in futs.items():
            try:
                # sanctioned async-completion helper (GL015): the ONLY
                # blocking-wait form on the interactive-class GET path
                out[i] = _compl.await_result(f, op="shard_read")
            except Exception as e:  # noqa: BLE001 — disk errors become votes
                preader.errs[i] = e if isinstance(e, errors.StorageError) \
                    else errors.FaultyDisk(str(e))
                preader.readers[i] = None
                failed = True
        return None if failed else out

    window: deque = deque()
    #: zero-copy sink protocol: a writer exposing reserve(n) hands out
    #: sequential views of its final buffer; the native path scatters
    #: assembled blocks straight into them, skipping the per-block
    #: GIL-held copy that dominates parallel GET on few cores (round-5
    #: verdict item 1: the 4+2 parallel-GET collapse was this copy
    #: serializing 8 streams on the GIL)
    reserve = getattr(writer, "reserve", None)

    def submit(b: int, dest: np.ndarray | None = None):
        """Read block b's shards and return a window entry, or None when
        the block contributes no bytes to the requested range. ``dest``
        re-attaches an already-reserved destination on resubmits (the
        bitrot-recovery path) — reservations are strictly in block
        order, so reserving twice would corrupt the layout."""
        block_data_len = min(bs, total_length - b * bs)
        if block_data_len <= 0:
            return None
        boff = offset % bs if b == start_block else 0
        if b == end_block:
            blen = (offset + length) - b * bs - boff
        else:
            blen = block_data_len - boff
        if blen <= 0:
            return None
        if dest is None and reserve is not None:
            dest = reserve(blen)
        shard_len = ceil_div(block_data_len, k)
        shard_offset = b * erasure.shard_size()
        # Native library + bitrot-framed sources -> ONE GIL-releasing
        # call a block. Healthy (the k data shards alive): verify every
        # chunk digest and scatter payloads (replaces the numpy
        # per-chunk verify); when every source is a local file the k
        # span reads fuse into the same call (pread in C,
        # mt_get_block_pread) — zero Python reads per block; RPC sources
        # keep the pooled-read form. Degraded (a data shard missing) and
        # every chosen source a local file: the same call with a GF(256)
        # rebuild step (mt_get_block_pread_degraded). The sources are
        # re-chosen per block — the first k live readers, data before
        # parity (read_block's preference) — so one lost mid-object is
        # replaced by the next live one.
        present = tuple(i for i, r in enumerate(preader.readers)
                        if r is not None)[:k]
        if native_get and len(present) == k:
            healthy = present[-1] == k - 1
            # a full aligned block whose assembled length equals the
            # reserved span can scatter DIRECTLY into the sink buffer
            out_dest = dest if dest is not None and boff == 0 and \
                blen == k * shard_len and \
                dest.flags["C_CONTIGUOUS"] else None
            try:
                fds = [preader.readers[i].fileno() for i in present]
                offs = [preader.readers[i].phys_offset(shard_offset)
                        for i in present]
            except (AttributeError, OSError):
                fds = None
            if out_dest is not None and (healthy or fds is not None):
                # block assembles straight into the caller's final buffer
                _mx.inc("minio_tpu_pipeline_zero_copy_bytes_total", blen,
                        path="get")
            if fds is not None:
                rebuild = None
                if not healthy:
                    missing = tuple(i for i in range(k) if i not in present)
                    rows = rows_cache.get(present)
                    if rows is None:
                        rows = rows_cache[present] = \
                            erasure.codec.rebuild_rows(present, missing)
                    rebuild = (present, missing, rows)
                _mx.inc("minio_tpu_pipeline_get_blocks_total",
                        route="native_fd" if healthy else "native_degraded")
                # pure CPU kernel work — records no spans
                fut = encode_pool().submit(  # graftlint: disable=GL005
                    _stages.pooled(pread_block, "decode"), fds, offs,
                    shard_len, out_dest, rebuild)
                return ["native", fut, b, block_data_len, boff, blen,
                        dest, present]
            framed = None
            if healthy:
                with _stages.timed(stc, "shard_read"):
                    framed = read_framed_k(shard_offset, shard_len)
            if framed is not None:
                _mx.inc("minio_tpu_pipeline_get_blocks_total",
                        route="native")
                fut = encode_pool().submit(  # graftlint: disable=GL005 — pure kernel compute
                    _stages.pooled(native.get_block, "decode"), framed, k,
                    shard_len, fuse_chunk,
                    HIGHWAY_KEY, get_algo_id,
                    out=out_dest if out_dest is not None
                    else pool.get(k * shard_len))
                return ["native", fut, b, block_data_len, boff, blen,
                        dest, present]
        # Degraded data read + device-hash-capable sources -> fused
        # verify+reconstruct: one launch hashes every source shard AND
        # rebuilds the missing ones (BASELINE config 4). Healthy streams
        # keep the CPU per-chunk verify inside read_at (no rebuild launch
        # to fuse into). A dead reader among the first k means read_block
        # fills a replacement index instead, so >=1 data shard is always
        # missing in the fused case and the rebuild is never wasted.
        degraded = any(preader.readers[i] is None for i in range(k))
        if degraded and preader.fusable(shard_len):
            _mx.inc("minio_tpu_pipeline_get_blocks_total", route="fused")
            with _stages.timed(stc, "shard_read"):
                shards = preader.read_block(shard_offset, shard_len,
                                            raw=True)
            fut = erasure.decode_data_blocks_verified_async(
                shards, preader.last_digests, preader.fuse_chunk(),
                preader.fuse_algo())
            return ["fused", fut, b, block_data_len, boff, blen, dest,
                    None]
        _mx.inc("minio_tpu_pipeline_get_blocks_total", route="plain")
        with _stages.timed(stc, "shard_read"):
            shards = preader.read_block(shard_offset, shard_len)
        return ["plain", erasure.decode_data_blocks_async(shards), b,
                block_data_len, boff, blen, dest, None]

    def recover_block(corrupt: tuple[int, ...], b: int,
                      block_data_len: int) -> list:
        """Shared bitrot-mismatch recovery for the device-verified paths
        (native and fused): the rebuilt/assembled data is garbage — drop
        the corrupt sources, redo this block via CPU-verified replacement
        reads, then RESUBMIT the pending window entries (their reads also
        carried the corrupt shard) so the pipeline recovers in one batch
        instead of stalling block by block (the reference's
        readTriggerCh-on-bitrot behavior)."""
        preader.drop_corrupt(corrupt)
        return _redo_block(b, block_data_len)

    def _redo_block(b: int, block_data_len: int) -> list:
        blocks = erasure.decode_data_blocks(preader.read_block(
            b * erasure.shard_size(), ceil_div(block_data_len, k)))
        pending = list(window)
        window.clear()
        for e in pending:
            if e[0] == "plain":
                window.append(e)
                continue
            # drain the abandoned future BEFORE resubmitting: a native
            # entry may have been submitted with out= a reserved view of
            # the sink buffer — letting it keep running would race the
            # resubmit writing the same memory (silent corruption when
            # the garbage-assembling call finishes last). Its pooled
            # buffer (non-zero-copy case) is recycled here too.
            try:
                res = _compl.await_result(e[1], op="decode")
                if e[0] == "native":
                    out_arr = res[0]
                    if out_arr is not e[6]:
                        pool.put(out_arr)
            except Exception:  # noqa: BLE001 — failed either way: redo
                pass
            # resubmits re-attach the entry's reserved destination —
            # reserving again would shift every later block's layout
            window.append(submit(e[2], dest=e[6]))
        return blocks

    def emit(entry):
        kind, fut, b, block_data_len, boff, blen, dest, present = entry
        with _stages.timed(stc, "decode"):
            res = _compl.await_result(fut, op="decode")
        if kind == "native":
            out_arr, bad = res
            if bad == -1:
                if dest is None:
                    # memoryview, not .tobytes(): the sink (BytesIO /
                    # socket) copies once anyway — a bytes() here doubled
                    # the GIL-held memcpy work per block, the main cost
                    # of 8-way reads on few cores
                    with _stages.timed(stc, "write_out"):
                        writer.write(
                            memoryview(out_arr)[boff: boff + blen])
                elif out_arr is not dest:
                    # reserved sink but a pooled buffer was used (tail /
                    # unaligned block): one copy into the final buffer
                    with _stages.timed(stc, "write_out"):
                        dest[:] = out_arr[boff: boff + blen]
                # else: zero-copy — the native call assembled straight
                # into the reserved view
                if out_arr is not dest:
                    pool.put(out_arr)
                stats.bytes_written += blen
                return
            if out_arr is not dest:
                pool.put(out_arr)
            # the native calls name a source by its position among the
            # block's chosen k (the shard index itself on healthy reads)
            if bad <= -10:
                # a fused pread failed on source -(bad+10): mark it
                # dead (a vote, like any disk read error) and redo via
                # replacement reads
                i = present[-(bad + 10)]
                preader.errs[i] = errors.FaultyDisk("pread failed")
                preader.readers[i] = None
                blocks = _redo_block(b, block_data_len)
            else:
                blocks = recover_block((present[bad],), b, block_data_len)
        elif kind == "fused":
            blocks, corrupt = res
            if corrupt:
                blocks = recover_block(corrupt, b, block_data_len)
        else:
            blocks = res
        block = np.concatenate(blocks[:k])
        with _stages.timed(stc, "write_out"):
            if dest is None:
                writer.write(memoryview(block)[boff: boff + blen])
            else:
                dest[:] = block[boff: boff + blen]
        stats.bytes_written += blen

    # native entries need only pipeline overlap; queued rebuilds want a
    # window deep enough to fill a dispatch batch
    native_win = native_window_for(erasure.block_size)
    for b in range(start_block, end_block + 1):
        entry = submit(b)
        if entry is None:
            break
        window.append(entry)
        if len(window) >= (native_win if entry[0] == "native"
                           else ENCODE_WINDOW):
            emit(window.popleft())
    while window:
        emit(window.popleft())
    stats.hedged = preader.hedged
    return stats


def erasure_decode_inline(erasure: Erasure, writer, shards: list,
                          offset: int, length: int, total_length: int,
                          algo, chunk: int) -> DecodeStats:
    """``erasure_decode`` for a version whose shards came with the
    metadata pass (xl.meta's ``Data``, a shard a drive): ``shards[i]`` is
    shard i's bitrot-framed bytes, what ``part.1`` would hold, or None.
    Every chunk digest of a shard used is verified; a data shard that is
    missing or corrupt is rebuilt from parity (on the dispatch queue, as
    the ``plain`` route of ``erasure_decode``: its CPU route compiles and
    loads nothing); a range is cut from the decoded block. Nothing is
    opened and nothing waits on a drive, so a whole version is served on
    the caller's thread. Returns the per-shard error votes for
    heal-on-read (``FileCorrupt`` for a digest mismatch)."""
    if offset < 0 or length < 0 or offset + length > total_length:
        raise ValueError("invalid decode range")
    from .bitrot import HIGHWAY_KEY, native_algo_id, new_bitrot_reader
    stats = DecodeStats()
    k, n = erasure.data_blocks, len(shards)
    bs = erasure.block_size
    logical = erasure.shard_file_size(total_length)
    readers = [None if s is None else new_bitrot_reader(
        BufferSource(s), algo, logical, chunk) for s in shards]
    errs = stats.errs = [errors.DiskNotFound() if r is None else None
                         for r in readers]
    if length == 0:
        return stats
    algo_id = native_algo_id(algo)
    from .. import native
    fast = algo_id is not None and erasure.shard_size() % chunk == 0 \
        and native.available()
    for b in range(offset // bs, (offset + length - 1) // bs + 1):
        block_data_len = min(bs, total_length - b * bs)
        boff = max(offset - b * bs, 0)
        blen = min(offset + length - b * bs, block_data_len) - boff
        shard_len = ceil_div(block_data_len, k)
        shard_offset = b * erasure.shard_size()
        _mx.inc("minio_tpu_pipeline_get_blocks_total", route="inline")
        block = None
        if fast and all(r is not None for r in readers[:k]):
            # the k data shards are there: verify + assemble in one call
            try:
                block, bad = native.get_block(
                    [r.read_framed(shard_offset, shard_len)
                     for r in readers[:k]],
                    k, shard_len, chunk, HIGHWAY_KEY, algo_id)
            except errors.StorageError:
                bad = -2  # a shard cut short: the loop below names it
            if bad >= 0:
                errs[bad] = errors.FileCorrupt("bitrot hash mismatch")
                readers[bad] = None
            if bad != -1:
                block = None
        if block is None:
            got: list = [None] * n
            have = 0
            for i in range(n):  # data before parity
                if have == k:
                    break
                if readers[i] is None:
                    continue
                try:
                    got[i] = np.frombuffer(
                        readers[i].read_at(shard_offset, shard_len),
                        dtype=np.uint8)
                    have += 1
                except Exception as e:  # noqa: BLE001 — a vote
                    errs[i] = e if isinstance(e, errors.StorageError) \
                        else errors.FaultyDisk(str(e))
                    readers[i] = None
            if have < k:
                err = errors.reduce_read_quorum_errs(
                    errs, errors.BASE_IGNORED_ERRS, k)
                raise err if err is not None else errors.ErasureReadQuorum()
            blocks = got[:k] if all(g is not None for g in got[:k]) \
                else erasure.decode_data_blocks_async(got).result()
            block = np.concatenate(blocks[:k])
        writer.write(memoryview(block)[boff: boff + blen])
        stats.bytes_written += blen
    return stats


def erasure_heal(erasure: Erasure, writers: list, readers: list,
                 total_length: int) -> list:
    """Rebuild the shards owned by the non-None writers (outdated/offline
    disks being healed) blockwise and stream them out; write quorum 1
    (reference Erasure.Heal, cmd/erasure-lowlevel-heal.go:28-48).
    Returns the per-reader error votes (the caller re-enqueues a deep
    MRF heal when a SOURCE shard turned out bitrot-corrupt mid-heal).

    Only the target shards are computed (targets <= parity count or the
    object would be unrecoverable) and rebuilds ride the dispatch queue, so
    concurrent heals of many objects coalesce into batched device launches
    (BASELINE config 5)."""
    if total_length == 0:
        # still commit empty shard files through the writers
        close_writers(writers)
        return [None] * len(readers)
    k = erasure.data_blocks
    bs = erasure.block_size
    targets = tuple(i for i, w in enumerate(writers) if w is not None)
    if not targets:
        return [None] * len(readers)
    preader = _ParallelReader(readers, erasure)
    n_blocks = ceil_div(total_length, bs)

    window: deque = deque()
    # standing heal attribution (obs/attribution.py): shard_read /
    # rebuild / shard_write; free when no collector is armed
    stc = _stages.active()

    def submit(b: int):
        block_data_len = min(bs, total_length - b * bs)
        shard_len = ceil_div(block_data_len, k)
        shard_offset = b * erasure.shard_size()
        if preader.fusable(shard_len):
            # fused verify+rebuild: source digests checked in the same
            # launch as the reconstruct (BASELINE config 4); a mismatch
            # falls back to CPU-verified replacement reads for that block
            with _stages.timed(stc, "shard_read"):
                shards = preader.read_block(shard_offset, shard_len,
                                            raw=True)
            fut = erasure.rebuild_targets_verified_async(
                shards, preader.last_digests, targets, preader.fuse_chunk(),
                preader.fuse_algo())
            return ["fused", fut, b]
        with _stages.timed(stc, "shard_read"):
            shards = preader.read_block(shard_offset, shard_len)
        return ["plain", erasure.rebuild_targets_async(shards, targets), b]

    def emit(entry):
        kind, fut, b = entry
        with _stages.timed(stc, "rebuild"):
            res = _compl.await_result(fut, op="rebuild")
        if kind == "fused":
            rebuilt, corrupt = res
            if corrupt:
                # drop corrupt sources, redo this block via CPU-verified
                # replacement reads, resubmit the pending fused window
                # (its raw reads also carried the corrupt shard)
                preader.drop_corrupt(corrupt)
                block_data_len = min(bs, total_length - b * bs)
                rebuilt = _compl.await_result(
                    erasure.rebuild_targets_async(
                        preader.read_block(b * erasure.shard_size(),
                                           ceil_div(block_data_len, k)),
                        targets), op="rebuild")
                pending = list(window)
                window.clear()
                for e in pending:
                    window.append(e if e[0] == "plain" else submit(e[2]))
        else:
            rebuilt = res
        errs: list[BaseException | None] = [None] * len(writers)
        wrote = 0
        for t, arr in zip(targets, rebuilt):
            w = writers[t]
            if w is None:
                continue
            try:
                with _stages.timed(stc, "shard_write"):
                    w.write(arr.tobytes())
                wrote += 1
            except Exception as e:  # noqa: BLE001
                errs[t] = e
                writers[t] = None
        if wrote == 0:
            err = errors.reduce_write_quorum_errs(
                errs, errors.BASE_IGNORED_ERRS, 1)
            raise err if err is not None else errors.ErasureWriteQuorum()

    for b in range(n_blocks):
        window.append(submit(b))
        if len(window) >= ENCODE_WINDOW:
            emit(window.popleft())
    while window:
        emit(window.popleft())
    close_writers(writers)
    return preader.errs


def close_writers(writers: list) -> None:
    """Per-writer close with per-disk demotion: close() can raise under
    fsync=always (strict writeback errors), and one disk's EIO must stay
    that disk's vote — nulling its slot tells the caller to skip its
    commit — not abort the write on every healthy disk (PUT, part PUT
    and heal alike; heal write quorum is 1). Sinks that can close
    together (``close_many``: the staged files of the local drives,
    storage/xlstorage.py) do so in ONE call, a turn at the interpreter
    lock a request where a close a drive is one a drive."""
    together: dict = {}
    for t, w in enumerate(writers):
        if w is None:
            continue
        try:
            # on the TYPE: a proxy around a sink closes by its own close
            if hasattr(type(w.sink), "close_many"):
                w.finish()
                together.setdefault(type(w.sink), []).append(t)
            else:
                w.close()
        except Exception:  # noqa: BLE001 — demoted to a per-disk vote
            writers[t] = None
    for kind, slots in together.items():
        errs = kind.close_many([writers[t].sink for t in slots])
        for t, e in zip(slots, errs):
            if e is not None:
                writers[t] = None


def close_readers(readers: list) -> None:
    """Close the shard sources of one read (``None`` slots and readers
    without a source pass). Local shard files hand over their fds
    (``detach_fd``, storage/xlstorage.py) and are closed in ONE native
    call, a turn at the interpreter lock a read where a close a shard is
    one a shard; any other source closes by its own ``close``."""
    fds = []
    for r in readers:
        src = getattr(r, "src", None)
        if src is None:
            continue
        detach = getattr(src, "detach_fd", None)
        if detach is not None:
            fds.append(detach())
        elif hasattr(src, "close"):
            src.close()
    if not fds:
        return
    from .. import native
    if native.available():
        native.close_fds(fds, False)
    else:
        for fd in fds:
            if fd >= 0:
                os.close(fd)


class BufferSink:
    """In-memory byte sink with the writer interface (tests, inlined data)."""

    def __init__(self):
        self.buf = io.BytesIO()
        self.closed = False

    def write(self, b: bytes):
        self.buf.write(b)

    def close(self):
        self.closed = True

    def getvalue(self) -> bytes:
        return self.buf.getvalue()


class PreallocSink:
    """Zero-copy in-memory sink: one preallocated buffer, filled either
    through the writer interface (write) or by handing erasure_decode
    sequential ``reserve(n)`` views the native path assembles blocks
    straight into. Replaces BufferSink under get_object_bytes — the
    BytesIO sink cost TWO GIL-held copies per object (per-block write +
    getvalue), which serialized 8-way parallel GETs on few cores (the
    round-5 4+2 get_par8 collapse)."""

    def __init__(self, nbytes: int | None = None):
        self.arr = np.empty(nbytes, np.uint8) if nbytes is not None \
            else None
        self.pos = 0
        self.closed = False
        self._reserved = False  # any reserve() handed out a live view

    def hint_total(self, n: int) -> None:
        """Called by the read path once the object size is known."""
        if self.arr is None:
            self.arr = np.empty(n, np.uint8)

    def _ensure(self, n: int) -> None:
        if self.arr is not None and self.pos + n <= self.arr.nbytes:
            return
        if self._reserved:
            # growing would reallocate the backing array while earlier
            # reserve() views (possibly being filled by in-flight native
            # calls) still point at the OLD memory — their bytes would
            # be silently lost. The read path always hint_total()s the
            # exact length first, so this firing means a caller broke
            # the contract: fail loudly instead of corrupting data.
            raise RuntimeError(
                "PreallocSink buffer exhausted with reservations "
                "outstanding — hint_total() must size the buffer before "
                "reserve() is used")
        if self.arr is None:
            self.arr = np.empty(max(n, 64 << 10), np.uint8)
        else:
            grown = np.empty(max(self.arr.nbytes * 2, self.pos + n),
                             np.uint8)
            grown[:self.pos] = self.arr[:self.pos]
            self.arr = grown

    def reserve(self, n: int) -> np.ndarray:
        """The next n bytes of the buffer as a writable view; the caller
        fills it (possibly out of order relative to other reservations)."""
        self._ensure(n)
        self._reserved = True
        v = self.arr[self.pos: self.pos + n]
        self.pos += n
        return v

    def write(self, b) -> None:
        n = len(b)
        if n == 0:
            return
        self._ensure(n)
        self.arr[self.pos: self.pos + n] = np.frombuffer(b, dtype=np.uint8)
        self.pos += n

    def close(self):
        self.closed = True

    def getvalue(self) -> bytes:
        if self.arr is None:
            return b""
        return self.arr[: self.pos].tobytes()

    def getbuffer(self) -> memoryview:
        """Zero-copy view of the filled buffer — getvalue() without the
        full-object GIL-held tobytes() pass (the last per-object copy
        the round-5 parallel-GET collapse left on this path; callers
        that only compare/stream/slice should prefer this)."""
        if self.arr is None:
            return memoryview(b"")
        return memoryview(self.arr)[: self.pos]


class BufferSource:
    """read_at over an in-memory bytes blob (tests, inlined data)."""

    def __init__(self, data: bytes):
        self.data = data

    def read_at(self, offset: int, length: int) -> bytes:
        if offset >= len(self.data):
            raise errors.FileCorrupt("read past end of shard file")
        return self.data[offset: offset + length]
