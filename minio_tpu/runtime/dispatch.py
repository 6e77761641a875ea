"""DispatchQueue — batches GF(256) shard work across concurrent requests
into single device launches (SURVEY.md §7.2: "the piece MinIO lacks").

Why: on TPU the per-launch cost (dispatch + host↔device transfer) can
exceed the math for a single 1 MiB block (link cost: re-measure on the
attached chip — the probe below does, per process). The reference
amortizes SIMD cost with goroutines per request
(cmd/erasure-coding.go:56 WithAutoGoroutines); the TPU-native equivalent is
request coalescing: N in-flight blocks with the same geometry become one
[B, k, W] batched kernel call.

Mechanics:
- submit encode/rebuild work → Future; requests bucket by
  (op, geometry, shard words).
- a dispatcher thread flushes a bucket when it reaches ``max_batch`` or its
  oldest entry exceeds ``max_delay`` (p99-aware flush, default 1 ms).
- batch B pads up to the next power of two (bounds jit recompiles); padding
  lanes replicate row 0 and are dropped on unpack.
- device results are handed to completer threads so the next batch launches
  while the previous one's host readback is still in flight (the link
  round-trip overlaps with compute).

Hybrid routing: each flush is costed against a one-time link profile
(round-trip latency + host<->device bandwidth, measured lazily) and the
native AVX2 GF(256) kernel's throughput; the flush runs wherever the model
predicts it finishes sooner: where the link is cheap that is the device
for everything beyond a couple of blocks; where it is dear single hot PUTs
fall back to the same CPU-SIMD-per-request behavior as the reference
instead of paying a link round-trip. What ``auto`` picks on a directly
attached chip is reported by chip_smoke.py leg a, not assumed here.
Override with MINIO_TPU_DISPATCH_MODE=device|cpu|auto.

QoS (minio_tpu.qos): every flush consults the deadline-aware scheduler
PER ITEM — items whose predicted device completion (backlog + transfer)
exceeds ~N x their CPU estimate, their class latency budget, or the
device queued-bytes cap SPILL to the CPU executor, even in forced-device
mode, so a saturated link yields bounded latency instead of a backlog.
Work class (interactive vs background) rides a context variable set by
the scanners/healers; interactive buckets flush first.

Interactive device lane (ISSUE 13, ROADMAP item 2): the coalescing
discipline above is throughput-tuned — at conc 128 it put device
heal-shard p99 at 20.3 s vs 14 ms on CPU (round-5 record, a set-up
that is gone: git history), because every
flush blocks toward max-batch buckets and the readback parks a
completer thread. Heal-shard rebuilds and degraded-GET reconstruct
('masked'/'fused' ops, overridable via ``qos.device_stream``) therefore
ride a SECOND, latency-tuned lane:

* small bounded batches (``dispatch.interactive_batch``, default <=8)
  collected by a DEDICATED dispatcher thread, so an interactive flush
  never queues behind a bulk flush's stack/launch work;
* deadline-aware batch sizing — ``QosScheduler.deadline_batch`` computes
  how many items fit under the oldest item's remaining ``qos.budget``
  given the LinkProfile and cuts the batch there instead of waiting for
  coalescing;
* async dispatch with completion callbacks instead of blocking flushes:
  the on_ready poller (``_AsyncCompleter``) polls ``jax.Array.is_ready``
  and runs the host readback only once the transfer landed, completing
  futures in submission order per bucket — no thread ever parks inside
  a device wait;
* donated input buffers (``ReedSolomon.batch_per_donated``) on a TPU
  backend, so the small HBM round trips don't double-allocate.

Bulk PUT/encode and the device workloads keep the coalescing lane
untouched; healthy GETs never reach the queue at all (CPU-native path),
nor do degraded GETs whose chosen sources are all local shard files (one
native pread+verify+rebuild call a block, erasure/streaming.py): the
lane's callers are heal and degraded reads from sources without an fd.

Enable/disable batching entirely with MINIO_TPU_DISPATCH=1/0 (default: on).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from ..obs import device as _dev
from ..obs import latency as _lat
from ..obs import lockrank as _lr
from ..obs import slo as _slo
from ..obs import spans as _sp
from ..obs import timeline as _tl
from ..obs import trace as _trc
from .. import qos as _qos

log = logging.getLogger("minio_tpu.dispatch")

#: dispatch op -> the kernel-metrics op name exported as
#: minio_tpu_kernel_op_latency_seconds{op=...}. Every op string passed
#: to _submit MUST appear here — graftlint GL006 enforces it, so a new
#: dispatch entry point cannot dodge the fault-injection funnel (every
#: flush passes the kernel-layer inject hook in _flush) or ship
#: unnamed in the kernel metrics/trace planes.
_OP_NAME = {"encode": "encode", "masked": "reconstruct", "fused": "fused",
            "encode_hashed": "encode_hashed",
            "select_scan": "select_scan", "sse_xor": "sse_xor"}

#: ops exempt from the mesh-route contract (graftlint GL013): every
#: ``b.op`` branch in ``_flush_device`` must either call
#: ``sharded_batched`` under a ``mesh``-guarded arm or appear here —
#: EMPTY because all six registered ops now carry a mesh route; a new
#: op PR that ships device-only (the way select_scan did in PR 8) must
#: either grow its route or register itself here, visibly.
_MESH_SINGLE_DEVICE_OPS: frozenset = frozenset()

#: per-device flush lanes: "auto" = one lane per local mesh device,
#: an integer caps the lane count, "1"/"0" disables per-lane placement
#: (every device flush rides the SPMD all-lanes route again)
DISPATCH_LANES = os.environ.get("MINIO_TPU_DISPATCH_LANES", "auto")

MAX_BATCH = int(os.environ.get("MINIO_TPU_DISPATCH_BATCH", "128"))
MAX_DELAY_S = float(os.environ.get("MINIO_TPU_DISPATCH_DELAY_MS", "1.0")) / 1e3
#: Link profile age after which a background re-probe is kicked (a one-shot
#: probe would pin the device/CPU routing decision to one possibly-transient
#: measurement forever).
PROBE_TTL_S = float(os.environ.get("MINIO_TPU_PROBE_TTL_S", "60"))

#: device flushes allowed in flight before the loop HOLDS further
#: device-bound buckets so arrivals coalesce into larger batches. The cap
#: matters where many small flushes can outpace the drain; 16 is a bound,
#: not a tuned value (link cost: re-measure on the attached chip) — watch
#: hold_events/hold_seconds in stats(). Each in-flight flush also holds
#: its programs' device memory: DEVICE_PIPELINE x the largest program is
#: what the chip must fit.
DEVICE_PIPELINE = int(os.environ.get("MINIO_TPU_DEVICE_PIPELINE", "16"))
#: safety cap on how long a held bucket may coalesce (model drift must
#: not stall requests)
MAX_HOLD_S = float(os.environ.get("MINIO_TPU_DISPATCH_HOLD_MS",
                                  "2000")) / 1e3
#: CPU-route completer threads; sized to the host so the CPU fallback's
#: aggregate is not capped below the per-core kernel rate.
COMPLETERS = int(os.environ.get(
    "MINIO_TPU_COMPLETERS", str(max(4, os.cpu_count() or 4))))

#: ops that ride the INTERACTIVE device lane by default: heal-shard
#: rebuilds and degraded-GET reconstruct ('masked') plus their fused
#: verify+rebuild twin. Bulk PUT/encode and the device workloads keep
#: the coalescing lane. ``qos.device_stream(...)`` overrides per
#: context (the bench forces heal work through the bulk lane to
#: measure both disciplines).
_INTERACTIVE_LANE_OPS = frozenset({"masked", "fused"})


def dispatch_enabled() -> bool:
    return os.environ.get("MINIO_TPU_DISPATCH", "1") != "0"


def interactive_lane_enabled() -> bool:
    """dispatch.interactive_lane / MINIO_TPU_DISPATCH_INTERACTIVE_LANE:
    0 sends every op down the bulk coalescing lane (the pre-ISSUE-13
    behavior)."""
    from ..qos.budget import _config_float
    return _config_float("dispatch", "interactive_lane",
                         "MINIO_TPU_DISPATCH_INTERACTIVE_LANE", 1.0) != 0.0


def interactive_batch() -> int:
    """Bound on items per interactive-lane flush (deadline sizing may
    cut below it, never above)."""
    from ..qos.budget import _config_float
    return max(1, int(_config_float(
        "dispatch", "interactive_batch",
        "MINIO_TPU_DISPATCH_INTERACTIVE_BATCH", 8.0)))


def interactive_delay_s() -> float:
    """Max coalescing wait on the interactive lane (microseconds knob —
    the lane trades batch fill for latency, so this is ~200us, not the
    bulk lane's milliseconds)."""
    from ..qos.budget import _config_float
    return max(0.0, _config_float(
        "dispatch", "interactive_delay_us",
        "MINIO_TPU_DISPATCH_INTERACTIVE_DELAY_US", 200.0)) / 1e6


def interactive_poll_s() -> float:
    """on_ready poll interval of the async completer."""
    from ..qos.budget import _config_float
    return max(1e-6, _config_float(
        "dispatch", "interactive_poll_us",
        "MINIO_TPU_DISPATCH_INTERACTIVE_POLL_US", 100.0)) / 1e6


def _donate_active() -> bool:
    """Whether interactive-lane rebuild launches use the donated-input
    kernel: ``auto`` only on a TPU backend (CPU/GPU jax warns and
    ignores donation), ``1`` forces it (tests), ``0`` disables."""
    v = os.environ.get("MINIO_TPU_DISPATCH_INTERACTIVE_DONATE")
    if v is None:
        try:
            from ..config import get_config_sys
            v = get_config_sys().get("dispatch", "interactive_donate")
        except Exception:  # noqa: BLE001 — registry not wired
            v = None
    v = v if v not in (None, "") else "auto"
    if v == "0":
        return False
    if v == "1":
        return True
    try:
        import jax
        return jax.default_backend() == "tpu"
    except Exception:  # noqa: BLE001 — no jax: no device flushes either
        return False


#: how many times SLOWER than the profiled native GF(256) rate each
#: op's CPU route runs — the QoS cost model's cpu estimate multiplies
#: by this, or it would happily spill a Select scan to a pure-Python
#: row loop it models as a 3 GiB/s kernel. Erasure ops are 1.0 (the
#: probe measures exactly their native kernel); select_scan's CPU
#: route is the pure-Python reference (~MB/s), sse_xor's the numpy
#: ChaCha lane (~tens of MB/s). Rough, order-of-magnitude-right
#: constants — the observed-vs-predicted EWMA corrects drift.
_CPU_ROUTE_SCALE = {"select_scan": 2000.0, "sse_xor": 30.0}


class LinkProfile:
    """Measurement of the host<->device link + CPU kernel rate, feeding the
    device-vs-CPU routing decision. Re-measured every PROBE_TTL_S in the
    background (see DispatchQueue._get_profile) so one transient slow probe
    can't pin the route forever."""

    def __init__(self, rt_s: float, up_gibs: float, down_gibs: float,
                 cpu_gibs: float):
        self.rt_s = rt_s
        self.up_gibs = max(up_gibs, 1e-4)
        self.down_gibs = max(down_gibs, 1e-4)
        self.cpu_gibs = max(cpu_gibs, 1e-4)
        self.measured_at = time.monotonic()

    @classmethod
    def probe(cls) -> "LinkProfile":
        import jax
        import jax.numpy as jnp
        nbytes = 4 << 20
        buf = np.zeros(nbytes, np.uint8)
        # warm the EXACT jitted shapes used below, so no compile lands
        # inside a timed section
        warm = jnp.asarray(buf)
        _ = jax.device_get(jnp.sum(warm[:1]))
        _ = np.asarray(warm)
        t0 = time.monotonic()
        for _ in range(3):
            _ = jax.device_get(jnp.sum(warm[:1]))
        rt = (time.monotonic() - t0) / 3
        t0 = time.monotonic()
        dev = jnp.asarray(buf)
        _ = jax.device_get(jnp.sum(dev[:1]))
        up = nbytes / max(time.monotonic() - t0 - rt, 1e-4) / (1 << 30)
        t0 = time.monotonic()
        _ = np.asarray(dev)
        down = nbytes / max(time.monotonic() - t0, 1e-4) / (1 << 30)
        # CPU kernel rate: one 16+4 encode of 1 MiB on the native kernel
        from .. import native
        from ..ops import gf256
        pmat = gf256.build_matrix(16, 4)[16:]
        d = np.zeros((16, 65536), np.uint8)
        native.cpu_encode(pmat, d, 4)  # warm/build
        t0 = time.monotonic()
        for _ in range(8):
            native.cpu_encode(pmat, d, 4)
        cpu = 8 * (1 << 20) / max(time.monotonic() - t0, 1e-6) / (1 << 30)
        prof = cls(rt, up, down, cpu)
        log.info("dispatch link probe: rt=%.1fms up=%.3fGiB/s "
                 "down=%.3fGiB/s cpu=%.2fGiB/s",
                 rt * 1e3, up, down, cpu)
        return prof

    def device_flush_s(self, bytes_in: int, bytes_out: int,
                       kernel_s: float = 2e-3) -> float:
        """Predicted wall seconds for one device flush (link + kernel)."""
        return self.rt_s + bytes_in / self.up_gibs / (1 << 30) \
            + bytes_out / self.down_gibs / (1 << 30) + kernel_s

    def device_wins(self, bytes_in: int, bytes_out: int, n_items: int = 1,
                    cpu_workers: int = COMPLETERS,
                    kernel_s: float = 2e-3, backlog_s: float = 0.0) -> bool:
        """Predicted device time vs CPU time for one flush. The device
        route pays the current queue of already-dispatched flushes
        (``backlog_s``) before its own transfer — routing on one flush's
        cost alone let a saturated link build an unbounded queue (r03:
        12.5 s p99 at conc 128). The CPU route runs per-item on
        ``cpu_workers`` completer threads (the native kernel releases the
        GIL), so its wall time divides by the effective parallelism — the
        model must agree with the executor it models."""
        t_dev = backlog_s + self.device_flush_s(bytes_in, bytes_out,
                                                kernel_s)
        par = max(1, min(n_items, cpu_workers))
        t_cpu = (bytes_in + bytes_out) / self.cpu_gibs / (1 << 30) / par
        return t_dev < t_cpu


@dataclass
class _Pending:
    words: np.ndarray            # [k, W] packed input shards
    masks: np.ndarray | None     # [8, o, k] per-element masks (rebuild only)
    digests: np.ndarray | None = None  # [k, 8] expected digests (fused only)
    future: Future = field(default_factory=Future)
    t: float = field(default_factory=time.monotonic)
    #: span context of the submitting request (None when untraced) —
    #: a flush serves items from MANY requests, so the kernel span
    #: links back to each item's context instead of pretending the
    #: batch belongs to one trace
    ctx: object | None = None
    #: op-specific per-ITEM parameters (sse_xor: (key, nonces, seq0) —
    #: package keys are per object, so they cannot live on the bucket;
    #: select_scan: (program, cols, delim, max_rows), equal for every
    #: item of a bucket because they ride the bucket key)
    params: tuple | None = None
    #: the submitting request's armed stage collector (obs/stages), or
    #: None — lets the flush charge queue_wait / dev_flush / readback
    #: into the standing PR 9 attribution, so "where the 20 s heal-p99
    #: goes" is a per-stage answer, not a guess
    stc: object | None = None


class _Bucket:
    def __init__(self, codec, op: str, hash_key: bytes | None = None,
                 chunk_size: int = 0, hash_algo: int = 0,
                 cls: str = _qos.CLASS_INTERACTIVE,
                 affinity: int | None = None,
                 stream: str = _qos.STREAM_BULK):
        self.codec = codec
        self.op = op  # 'encode' | 'masked' | 'fused'
        self.hash_key = hash_key
        self.chunk_size = chunk_size
        self.hash_algo = hash_algo  # native ALGO_* id for 'fused'
        self.cls = cls  # QoS class: buckets never mix classes, so the
        # loop can flush interactive work ahead of heal/scanner batches
        #: erasure-set lane affinity (qos.current_affinity at submit
        #: time; rides the bucket key, so one flush never mixes sets):
        #: None = unpinned — such flushes shard SPMD across ALL lanes
        self.affinity = affinity
        #: device-lane discipline (ISSUE 13): STREAM_INTERACTIVE buckets
        #: belong to the dedicated latency dispatcher (bounded batches,
        #: deadline sizing, on_ready completion); STREAM_BULK buckets
        #: keep the coalescing loop. Rides the bucket key.
        self.stream = stream
        self.items: list[_Pending] = []
        #: set while the loop holds this bucket for coalescing (device
        #: pipeline saturated); cleared at flush — feeds hold telemetry
        self.held_since: float | None = None


def _pad_batch(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return min(b, MAX_BATCH)


def _outputs_ready(out_dev) -> bool:
    """True when every device array of a flush's output has landed
    (``jax.Array.is_ready`` — the poll/on_ready form of awaiting a
    device future without ``__await__`` or a blocking readback).
    A raising poll counts as ready: the blocking readback that follows
    then raises (and salvages) truthfully, on the poller thread."""
    outs = out_dev if isinstance(out_dev, tuple) else (out_dev,)
    try:
        return all(a.is_ready() for a in outs)
    except Exception:  # noqa: BLE001 — unknown state: let readback raise
        return True


class _IAHandle:
    """One in-flight interactive-lane device flush awaiting readiness,
    carrying everything ``DispatchQueue._complete`` needs."""

    __slots__ = ("b", "out_dev", "items", "accounted", "qbytes",
                 "predicted_s", "t0", "span_done", "tl_done", "lane",
                 "tok")

    def __init__(self, b, out_dev, items, accounted, qbytes,
                 predicted_s, t0, span_done, tl_done, lane, tok=None):
        self.b = b
        self.out_dev = out_dev
        self.items = items
        self.accounted = accounted
        self.qbytes = qbytes
        self.predicted_s = predicted_s
        self.t0 = t0
        self.span_done = span_done
        self.tl_done = tl_done
        self.lane = lane
        self.tok = tok


class _AsyncCompleter(threading.Thread):
    """The interactive lane's on_ready completer (ISSUE 13): device
    flushes register here after launch, and ONE poller thread checks
    ``is_ready`` across all of them, running the host readback only for
    flushes whose transfer already landed. Two contracts:

    * **No parked threads.** The bulk lane's blocking completer model
      occupies one thread per in-flight readback; here a single thread
      serves any number of outstanding interactive flushes, so a burst
      of small heal flushes cannot exhaust the completer pool that the
      CPU route (and the spill path) depends on.
    * **Submission order per bucket.** Handles are kept in per-bucket
      FIFO queues and completed HEAD-FIRST: flush k+1's futures never
      resolve before flush k's, even if its (smaller) transfer lands
      earlier — consumers like the heal writer window rely on block
      order (tests/test_interactive_lane.py pins this).
    """

    def __init__(self, q: "DispatchQueue"):
        super().__init__(name="minio-tpu-ia-complete", daemon=True)
        self.q = q
        self._cv = threading.Condition()
        self._pending: dict[int, "deque[_IAHandle]"] = {}
        self._stopping = False

    def submit(self, h: _IAHandle) -> None:
        with self._cv:
            self._pending.setdefault(id(h.b), deque()).append(h)
            self._cv.notify()

    def stop(self) -> None:
        """Drain everything still pending (blocking readbacks are fine
        at shutdown) and join the poller."""
        with self._cv:
            self._stopping = True
            self._cv.notify()
        self.join(timeout=10)

    def run(self):
        while True:
            ready: list[_IAHandle] = []
            with self._cv:
                while not self._stopping and not self._pending:
                    self._cv.wait()
                if self._stopping and not self._pending:
                    return
                for key in list(self._pending):
                    dq = self._pending[key]
                    # head-first: completion order == submission order
                    # per bucket. At shutdown everything counts as
                    # ready (blocking readback on this thread).
                    while dq and (self._stopping or
                                  _outputs_ready(dq[0].out_dev)):
                        ready.append(dq.popleft())
                    if not dq:
                        del self._pending[key]
                poll = bool(self._pending) and not ready
            for h in ready:
                try:
                    self.q.ia_async_completions += 1
                    self.q._complete(h.b, h.out_dev, h.items,
                                     h.accounted, h.qbytes,
                                     h.predicted_s, h.t0, h.span_done,
                                     h.tl_done, h.lane, h.tok)
                except Exception as e:  # noqa: BLE001 — completion must
                    for p in h.items:   # never kill the poller; waiters
                        if not p.future.done():  # get the error
                            p.future.set_exception(e)
            if poll:
                # nothing landed yet: sleep one poll interval OUTSIDE
                # the lock, then re-check readiness
                time.sleep(interactive_poll_s())


class DispatchQueue:
    def __init__(self, max_batch: int = MAX_BATCH,
                 max_delay: float = MAX_DELAY_S,
                 completers: int = COMPLETERS):
        self.max_batch = max_batch
        self.max_delay = max_delay
        self.completer_count = completers
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        #: the interactive dispatcher's OWN wait channel, sharing the
        #: same lock (bucket state stays single-lock); a bulk submit
        #: wakes only the bulk loop and vice versa — with one shared cv
        #: every submit would wake both dispatcher threads
        self._ia_cv = threading.Condition(self._lock)
        self._buckets: dict[tuple, _Bucket] = {}
        self._completers = ThreadPoolExecutor(
            max_workers=completers, thread_name_prefix="minio-tpu-complete")
        # the interactive lane's OWN CPU executor: a spilled (or
        # CPU-routed) heal rebuild must not queue behind thousands of
        # bulk items in the shared pool's FIFO — measured 22 s heal
        # wall under bulk saturation with one shared pool, ~flush-time
        # with this split (tests/test_interactive_lane.py's gate)
        self._ia_completers = ThreadPoolExecutor(
            max_workers=max(2, min(4, completers)),
            thread_name_prefix="minio-tpu-ia-cpu")
        self._stop = False
        self._profile: LinkProfile | None = None
        self._profile_failed = False
        self._probe_failed_at = 0.0
        self._probe_running = False
        self._profile_lock = threading.Lock()
        # telemetry (route decisions surface in the dispatch metrics
        # group and in BENCH extras — regressions in the routing model
        # must be visible, not inferred)
        self.batches = 0
        self.items = 0
        self.cpu_batches = 0
        self.device_batches = 0
        self.cpu_items = 0
        self.device_items = 0
        self.hold_events = 0
        self.hold_seconds = 0.0
        #: CPU salvages of device work by reason (injected /
        #: device_flush_failed / readback_failed), items not flushes —
        #: the product keeps answering through them, so a smoke or a
        #: benchmark must be able to hold them to zero (_note_salvage)
        self.salvaged_items: dict[str, int] = {}
        #: (op, input shape) of device flushes that already failed once:
        #: the first failure of each logs at ERROR with the exception
        #: text (a compiler refusal is not a dead device, and would
        #: otherwise re-salvage on every flush while requests succeed)
        self._failed_flush_sigs: set[tuple] = set()
        self.probe_failures = 0
        # interactive device lane telemetry (ISSUE 13; GIL-atomic
        # counters, same rule as the route counters above) — the
        # minio_tpu_lane_* metric group and the bench extras read these
        self.ia_flushes = 0
        self.ia_items = 0
        self.ia_deadline_cuts = 0
        self.ia_async_completions = 0
        self.ia_max_batch = 0
        # bulk counted DIRECTLY at the same boundary (_flush entry),
        # not derived as batches - ia_flushes: the route counters move
        # later (and twice for a split flush), so subtraction could go
        # transiently negative or permanently drift on a scrape
        self.bulk_flushes = 0
        self.bulk_items = 0
        #: monotone flush sequence — the batch id every coalesced item's
        #: span records, so concurrent requests can prove they shared
        #: (or didn't share) a device launch
        self._batch_seq = 0
        #: deadline-aware scheduler: per-item device-vs-CPU routing with
        #: spill + per-route queued-bytes caps (minio_tpu.qos.scheduler)
        self.qos = _qos.QosScheduler()
        # predicted drain deadline for device flushes already dispatched
        # and their in-flight count (under _profile_lock); the estimate
        # self-corrects — when the last in-flight flush completes early
        # the deadline resets to now
        self._dev_busy_until = 0.0
        self._dev_inflight = 0
        #: on_ready async completer for the interactive lane (started
        #: lazily on its first device flush; None until then)
        self._ia_completer: _AsyncCompleter | None = None
        # every attribute the loop reads must exist before it starts
        self._thread = threading.Thread(
            target=self._loop, name="minio-tpu-dispatch", daemon=True)
        self._thread.start()
        # the interactive lane's DEDICATED submission stream: its own
        # dispatcher thread, so a small heal flush never queues behind
        # a bulk flush's stack/launch work on the loop above
        self._ia_thread = threading.Thread(
            target=self._ia_loop, name="minio-tpu-dispatch-ia",
            daemon=True)
        self._ia_thread.start()
        # warm the profile off the request path: in auto mode the first
        # flush would otherwise absorb the full probe cost (device
        # transfers + 8 CPU encodes) inside its latency. Forced-device
        # mode needs the profile too — the in-flight accounting behind
        # the hold/coalesce cap only runs when a profile exists.
        if dispatch_enabled() and os.environ.get(
                "MINIO_TPU_DISPATCH_MODE", "auto") in ("auto", "device"):
            self._kick_probe()

    # --- submission ---------------------------------------------------------

    def encode(self, codec, words: np.ndarray) -> Future:
        """words uint32 [k, W] -> Future[uint32 [m, W]] (parity)."""
        key = ("encode", codec.k, codec.m, words.shape[-1], id(codec.matrix))
        return self._submit(key, codec, "encode", words, None)

    @staticmethod
    def _item_bytes(b: "_Bucket", p: _Pending) -> tuple[int, int]:
        """(bytes up the link, bytes back) for ONE pending item — the
        unit the QoS scheduler costs per-item routing on."""
        if b.op == "select_scan":
            # row codes come back: 4 B per tracked row
            return p.words.nbytes, p.params[3] * 4
        if b.op == "sse_xor":
            # the whole payload rides back XORed, plus a 32 B Poly1305
            # key per 64 KiB-class package (negligible) and the per-
            # package nonce words up (ditto)
            npkgs = p.words.shape[0]
            return p.words.nbytes + npkgs * 12, p.words.nbytes + npkgs * 32
        bytes_in = p.words.nbytes
        out_rows = b.codec.m
        if p.masks is not None:
            bytes_in += p.masks.nbytes
            out_rows = p.masks.shape[1]
        bytes_out = out_rows * p.words.shape[-1] * 4
        if b.op == "encode_hashed":
            # the digests ride the downlink too: 32 B per chunk of all
            # k+m shards
            nc = p.words.shape[-1] * 4 // b.chunk_size
            bytes_out += (b.codec.k + b.codec.m) * nc * 32
        return bytes_in, bytes_out

    def masked(self, codec, words: np.ndarray, masks: np.ndarray) -> Future:
        """words uint32 [k, W] + masks uint32 [8, o, k] -> Future[[o, W]].

        Per-element masks let one batch mix arbitrary loss patterns — the
        same launch serves degraded reads and multi-object heal (BASELINE
        configs 3/5). Batches are keyed by o (= rows per element), so
        same-loss-count patterns share a compiled shape and no padded
        rows ride the link."""
        key = ("masked", codec.k, masks.shape[1], words.shape[-1])
        return self._submit(key, codec, "masked", words, masks)

    def encode_hashed(self, codec, words: np.ndarray, hash_key: bytes,
                      chunk_size: int, hash_algo: int = 0) -> Future:
        """Fused encode+hash (the PUT flush's device-side hash lane):
        words uint32 [k, W] -> Future[(parity uint32 [m, W], digests
        uint32 [k+m, nc*8])] — the per-``chunk_size``-chunk bitrot
        digests of every data AND parity shard come back with the
        parity, so the PUT path interleaves ready-made [digest][chunk]
        frames without hashing payload bytes on the host. Coalesces
        across concurrent PUTs exactly like 'encode' (same bucket
        mechanics, QoS class tagging included)."""
        key = ("encode_hashed", codec.k, codec.m, words.shape[-1],
               id(codec.matrix), hash_key, chunk_size, hash_algo)
        return self._submit(key, codec, "encode_hashed", words, None,
                            hash_key=hash_key, chunk_size=chunk_size,
                            hash_algo=hash_algo)

    def fused(self, codec, words: np.ndarray, masks: np.ndarray,
              digests: np.ndarray, hash_key: bytes,
              chunk_size: int, hash_algo: int = 0) -> Future:
        """Fused bitrot-verify + rebuild (BASELINE config 4): like masked()
        but the launch also hash-verifies each of the k source shards'
        ``chunk_size``-byte chunks against ``digests`` uint32 [k, nc*8]
        with the device kernel for ``hash_algo`` (native ALGO_* id).
        Future resolves to (out_words [o, W], valid bool [k])."""
        key = ("fused", codec.k, masks.shape[1], words.shape[-1], hash_key,
               chunk_size, hash_algo)
        return self._submit(key, codec, "fused", words, masks,
                            digests=digests, hash_key=hash_key,
                            chunk_size=chunk_size, hash_algo=hash_algo)

    def select_scan(self, words: np.ndarray, program: tuple, cols: tuple,
                    delim: int, max_rows: int) -> Future:
        """Batched S3 Select predicate scan (ops/scan_pallas): one CSV
        block as uint32 [1, L//4] -> Future[codes int32 [1, max_rows]].
        Blocks of one request (and concurrent requests running the same
        compiled program) bucket together into one device launch; the
        CPU route/salvage runs the bit-identical pure-Python reference."""
        key = ("select_scan", words.shape[-1], program, cols, delim,
               max_rows)
        return self._submit(key, None, "select_scan", words, None,
                            params=(program, cols, delim, max_rows))

    def sse_xor(self, words: np.ndarray, cipher_key: bytes,
                nonces: np.ndarray) -> Future:
        """SSE ChaCha20 package-crypto lane (ops/chacha_pallas): a whole
        PUT/GET block's packages uint32 [P, pkg//4] -> Future[(xored
        [P, pkg//4], poly_keys uint32 [P, 8])] under per-package nonces
        uint32 [P, 3]. Package keys are per object, so items carry them
        as params (one launch per item inside a shared flush); the CPU
        route runs the numpy ChaCha20 reference — bit-identical either
        way."""
        key = ("sse_xor", words.shape)
        return self._submit(key, None, "sse_xor", words, None,
                            params=(cipher_key, nonces))

    def _submit(self, key, codec, op, words, masks, digests=None,
                hash_key=None, chunk_size=0, hash_algo=0,
                params=None) -> Future:
        ctx = _sp.current()
        if ctx is not None and not ctx.sampled:
            ctx = None
        from ..obs import stages as _stages
        p = _Pending(words=words, masks=masks, digests=digests, ctx=ctx,
                     params=params, stc=_stages.active())
        # QoS class rides the bucket key: interactive PUT/GET work and
        # background heal/scanner work never share a flush, so the loop
        # can order and spill them independently. The erasure-set lane
        # affinity rides it too — folded to its flush-lane SLOT, so a
        # flush is one lane's traffic (sets sharing a lane coalesce)
        # and single-chip hosts keep coalescing across sets entirely.
        # The device-lane DISCIPLINE (ISSUE 13) rides it last: explicit
        # qos.device_stream overrides, else heal/reconstruct ops default
        # to the interactive lane, everything else to bulk.
        cls = _qos.current_class()
        affinity = self._affinity_slot(_qos.current_affinity())
        stream = _qos.current_stream()
        if stream is None:
            stream = _qos.STREAM_INTERACTIVE \
                if op in _INTERACTIVE_LANE_OPS else _qos.STREAM_BULK
        if stream == _qos.STREAM_INTERACTIVE and \
                not interactive_lane_enabled():
            # master switch: dispatch.interactive_lane=0 restores the
            # single coalescing lane even for explicit stream pins
            stream = _qos.STREAM_BULK
        key = key + (cls, affinity, stream)
        # per-item wall latency through the queue (what a caller sees:
        # queue wait + flush + readback) into the last-minute window
        # behind minio_tpu_kernel_op_latency_seconds — and the per-class
        # window behind minio_tpu_qos_class_latency_seconds
        op_name = _OP_NAME.get(op, op)
        nbytes = words.nbytes
        tid = ctx.trace_id if ctx is not None else ""

        def _record(_f, t=p.t, op_name=op_name, nbytes=nbytes, cls=cls,
                    tid=tid, stream=stream):
            try:
                wall = time.monotonic() - t
                if _f.exception() is not None:
                    # failed ops must not read as kernel throughput —
                    # same rule the heal_shard window applies — but a
                    # failed background item DOES burn that class's
                    # availability budget (the request plane feeds the
                    # interactive/control SLO classes in s3api)
                    if cls == _qos.CLASS_BACKGROUND:
                        _slo.record(cls, wall, error=True, trace_id=tid)
                    return
                if cls == _qos.CLASS_BACKGROUND:
                    _slo.record(cls, wall, trace_id=tid)
                _lat.observe("kernel", wall, nbytes, op=op_name,
                             trace_id=tid)
                _lat.observe("qos", wall, nbytes, trace_id=tid,
                             **{"class": cls})
                # per-STREAM wall window: the minio_tpu_lane_* family's
                # latency half (interactive vs bulk percentiles)
                _lat.observe("lane", wall, nbytes, trace_id=tid,
                             stream=stream)
                self.qos.note_deadline(cls, wall)
                # flight recorder: the completion callback closes the
                # item's enqueue→...→complete chain (sampled event type)
                _tl.record("complete", op=op_name, trace_id=tid,
                           wall=round(wall, 6), stream=stream,
                           **{"class": cls})
            except Exception:  # noqa: BLE001 — obs never breaks the path
                pass

        p.future.add_done_callback(_record)
        with self._cv:
            b = self._buckets.get(key)
            if b is None:
                b = self._buckets[key] = _Bucket(codec, op, hash_key,
                                                 chunk_size, hash_algo,
                                                 cls=cls,
                                                 affinity=affinity,
                                                 stream=stream)
            b.items.append(p)
            depth = len(b.items)
            # wake the dispatcher that owns this bucket's stream (the
            # two loops wait on separate conditions over one lock)
            if stream == _qos.STREAM_INTERACTIVE:
                self._ia_cv.notify()
            else:
                self._cv.notify()
        # flight recorder: item entered its bucket (sampled event type;
        # recorded OUTSIDE the dispatch cv lock)
        _tl.record("enqueue", op=op_name, trace_id=tid, bytes=nbytes,
                   bucket_depth=depth, stream=stream, **{"class": cls})
        return p.future

    # --- dispatcher ---------------------------------------------------------

    def _loop(self):
        while True:
            to_flush: list[tuple[tuple, _Bucket, list[_Pending]]] = []
            qdepth = -1
            with self._cv:
                while not self._stop:
                    now = time.monotonic()
                    deadline = None
                    saturated = self._device_saturated()
                    for key in list(self._buckets):
                        b = self._buckets[key]
                        if b.stream == _qos.STREAM_INTERACTIVE:
                            # the interactive dispatcher (_ia_loop)
                            # owns these buckets
                            continue
                        if not b.items:
                            # evict idle buckets so distinct tail-shard
                            # sizes don't accumulate entries forever
                            del self._buckets[key]
                            continue
                        age = now - b.items[0].t
                        if len(b.items) < self.max_batch and \
                                age >= self.max_delay and \
                                age < MAX_HOLD_S and saturated and \
                                self._device_bound(b):
                            # device pipeline full: HOLD this bucket so
                            # later arrivals coalesce into one big flush
                            # instead of queueing many tiny ones behind
                            # the link; completion notifies the cv
                            if b.held_since is None:
                                b.held_since = now
                                self.hold_events += 1
                            d = b.items[0].t + MAX_HOLD_S
                            deadline = d if deadline is None \
                                else min(deadline, d)
                            continue
                        if b.held_since is not None:
                            self.hold_seconds += now - b.held_since
                            b.held_since = None
                        if len(b.items) >= self.max_batch or \
                                age >= self.max_delay:
                            items, b.items = b.items[:self.max_batch], \
                                b.items[self.max_batch:]
                            to_flush.append((key, b, items))
                        else:
                            d = b.items[0].t + self.max_delay
                            deadline = d if deadline is None \
                                else min(deadline, d)
                    if to_flush:
                        # interactive flushes launch ahead of background
                        # ones collected in the same pass (QoS priority)
                        to_flush.sort(key=lambda e: _qos.CLASS_PRIORITY.get(
                            e[1].cls, 1))
                        # queue-depth sample per flush pass (items still
                        # waiting after this pass's extraction) for the
                        # minio_tpu_device_queue_depth distribution
                        qdepth = sum(len(bb.items)
                                     for bb in self._buckets.values())
                        break
                    timeout = None if deadline is None \
                        else max(0.0, deadline - time.monotonic())
                    self._cv.wait(timeout=timeout)
                stopping = self._stop
                if stopping:
                    # drain everything still queued so no waiter hangs
                    for key, b in self._buckets.items():
                        while b.items:
                            items, b.items = b.items[:self.max_batch], \
                                b.items[self.max_batch:]
                            to_flush.append((key, b, items))
                    self._buckets.clear()
            if qdepth >= 0:
                _tl.note_queue_depth(qdepth)
            for key, b, items in to_flush:
                try:
                    self._flush(b, items)
                except Exception as e:  # noqa: BLE001
                    for p in items:
                        if not p.future.done():
                            p.future.set_exception(e)
            if stopping:
                return

    # --- the interactive lane dispatcher ------------------------------------

    def _deadline_cut(self, b: _Bucket, cap: int) -> tuple[int, bool]:
        """Deadline-aware batch size for an interactive bucket:
        ``(take, cut)`` — the number of queued items that fit under the
        oldest item's remaining class budget (qos.deadline_batch over
        the link profile + the lane's own backlog), capped at
        ``dispatch.interactive_batch``; ``cut`` True when the DEADLINE
        limited the batch (waiting for more arrivals would be pointless
        — they wouldn't fit either). Called under the cv (reads
        b.items)."""
        n = min(cap, len(b.items))
        prof = self._profile
        if prof is None:
            return n, False
        sizes = [self._item_bytes(b, p) for p in b.items[:n]]
        oldest = time.monotonic() - b.items[0].t
        take, cut = self.qos.deadline_batch(
            prof, b.cls, sizes, self.qos.ia_backlog_s(), oldest)
        if cut:
            self.ia_deadline_cuts += 1
        return max(1, min(n, take)), cut

    def _ia_loop(self):
        """The interactive lane's dedicated submission stream: small
        bounded batches, flushed the moment the deadline-aware size is
        reached (or a ~200us coalescing window expires) — never held
        for pipeline saturation, never behind a bulk flush."""
        while True:
            to_flush: list[tuple[tuple, _Bucket, list[_Pending]]] = []
            # _ia_cv wraps the SAME lock as _cv — bucket state stays
            # single-lock; this loop just waits on its own channel
            with self._ia_cv:
                while not self._stop:
                    now = time.monotonic()
                    deadline = None
                    delay = interactive_delay_s()
                    for key in list(self._buckets):
                        b = self._buckets[key]
                        if b.stream != _qos.STREAM_INTERACTIVE:
                            continue
                        if not b.items:
                            del self._buckets[key]
                            continue
                        age = now - b.items[0].t
                        cap = interactive_batch()
                        take, cut = self._deadline_cut(b, cap)
                        # flush now when the batch cap is reached, the
                        # DEADLINE limited the batch (later arrivals
                        # wouldn't fit anyway), or the ~200us
                        # coalescing window expired; otherwise wait so
                        # a trickle of items still coalesces
                        if len(b.items) >= cap or cut or age >= delay:
                            items, b.items = \
                                b.items[:take], b.items[take:]
                            to_flush.append((key, b, items))
                        else:
                            d = b.items[0].t + delay
                            deadline = d if deadline is None \
                                else min(deadline, d)
                    if to_flush:
                        break
                    timeout = None if deadline is None \
                        else max(0.0, deadline - time.monotonic())
                    self._ia_cv.wait(timeout=timeout)
                if self._stop and not to_flush:
                    # the bulk loop's stop path drains every bucket,
                    # interactive ones included
                    return
            for key, b, items in to_flush:
                try:
                    self._flush(b, items)
                except Exception as e:  # noqa: BLE001
                    for p in items:
                        if not p.future.done():
                            p.future.set_exception(e)
            if self._stop:
                return

    def _async_completer(self) -> "_AsyncCompleter":
        """The interactive lane's on_ready poller, started on first use
        (the completer must not exist on CPU-route-only deployments)."""
        c = self._ia_completer
        if c is None:
            with self._profile_lock:
                c = self._ia_completer
                if c is None:
                    c = self._ia_completer = _AsyncCompleter(self)
                    c.start()
        return c

    # --- device-vs-CPU routing ----------------------------------------------

    def _kick_probe(self):
        """Run (or refresh) the link probe on a background thread; callers
        keep using the previous profile (or the static default route) until
        the new measurement lands."""
        with self._profile_lock:
            if self._probe_running:
                return
            self._probe_running = True

        def run():
            try:
                prof = LinkProfile.probe()
                with self._profile_lock:
                    self._profile = prof
                    self._profile_failed = False
            except Exception:  # noqa: BLE001 — no device: CPU-only
                # from here `auto` routes everything to the CPU until
                # the next probe: say so, with the cause
                log.error("dispatch link probe failed; the queue runs "
                          "CPU-only until a probe succeeds",
                          exc_info=True)
                with self._profile_lock:
                    self._profile_failed = True
                    self._probe_failed_at = time.monotonic()
                    self.probe_failures += 1
            finally:
                with self._profile_lock:
                    self._probe_running = False

        self._probe_thread = threading.Thread(
            target=run, name="minio-tpu-probe", daemon=True)
        self._probe_thread.start()

    def _get_profile(self) -> LinkProfile | None:
        """Current link profile; stale or missing profiles trigger a
        background re-probe without blocking the caller. Failed probes back
        off for a full TTL — without that, a device that dies after a good
        first probe would trigger back-to-back probe attempts (device
        transfers + CPU encodes each) on every flush, forever."""
        prof = self._profile
        backoff = self._profile_failed and \
            time.monotonic() - self._probe_failed_at < PROBE_TTL_S
        if prof is None:
            if not backoff:
                self._kick_probe()
        elif time.monotonic() - prof.measured_at > PROBE_TTL_S \
                and not backoff:
            self._kick_probe()
        return prof

    def _flush_bytes(self, b: _Bucket, items: list[_Pending]
                     ) -> tuple[int, int]:
        n = len(items)
        bytes_in, bytes_out = self._item_bytes(b, items[0])
        return n * bytes_in, n * bytes_out

    @staticmethod
    def _effective_lanes(names: tuple[str, ...]) -> int:
        """Lane count after the MINIO_TPU_DISPATCH_LANES cap."""
        n = len(names)
        if DISPATCH_LANES not in ("", "auto"):
            try:
                n = min(n, max(1, int(DISPATCH_LANES)))
            except ValueError:
                pass
        return n

    def _affinity_slot(self, affinity: int | None) -> int | None:
        """Fold a raw erasure-set affinity key into its flush-lane slot
        for bucket keying: None when per-lane placement is inactive
        (routing off, or a single-device host once the topology is
        known) — so those hosts keep coalescing ACROSS sets instead of
        splitting every flush per crc32 key for a lane decision that
        always lands on the same device. Before the first device flush
        resolves the topology the raw key passes through (a transient
        conservative split; submit must never be what initializes the
        backend) — except in forced-CPU mode, where no device flush
        will ever resolve it and lane placement can never apply."""
        if affinity is None or DISPATCH_LANES in ("0", "1") or \
                os.environ.get("MINIO_TPU_DISPATCH_MODE", "auto") == "cpu":
            return None
        names = getattr(self, "_lanes_cache", None)
        if names is None:
            return affinity
        n = self._effective_lanes(names)
        return affinity % n if n > 1 else None

    def _lane_for(self, b: _Bucket, record: bool = True) -> int | None:
        """The flush lane this bucket's device work occupies, or None
        for the SPMD all-lanes route (no affinity, lane routing off, or
        a single-device host). Consults the scheduler's pick_lane so a
        saturated preferred lane diverts to the least-loaded sibling —
        the device-lane → sibling-lane leg of the spill order."""
        if b.affinity is None or DISPATCH_LANES in ("0", "1"):
            return None
        n = self._effective_lanes(self._device_lanes())
        if n <= 1:
            return None
        self.qos.configure_lanes(n)
        return self.qos.pick_lane(b.affinity, record=record)

    def _backlog_s(self, lane: int | None) -> float:
        """Predicted drain seconds ahead of a new flush: the chosen
        lane's own busy-until when per-lane routed; for SPMD all-lanes
        flushes the busiest single lane (an SPMD launch waits on every
        chip, and pinned flushes occupy lanes the global serial model
        knows nothing about) joined with the global model."""
        if lane is not None:
            return self.qos.lane_backlog_s(lane)
        with self._profile_lock:
            g = max(0.0, self._dev_busy_until - time.monotonic())
        return max(g, self.qos.max_lane_backlog_s())

    def _plan_flush(self, b: _Bucket, items: list[_Pending]
                    ) -> tuple[int, int | None]:
        """Per-item consultation of the QoS scheduler (replaces the old
        flush-granular device_wins coin flip): how many leading items of
        this flush take the device route — and WHICH flush lane they
        occupy — the rest SPILL to the CPU executor. Even in
        forced-device mode an item spills when its predicted device
        completion exceeds ~N x its CPU estimate, its class budget, or
        the device/lane queued-bytes caps; a saturated lane first
        diverts to a sibling lane (pick_lane) and only then to CPU."""
        mode = os.environ.get("MINIO_TPU_DISPATCH_MODE", "auto")
        lane = None
        if mode == "cpu":
            n_dev = 0
        else:
            prof = self._get_profile()
            if b.stream == _qos.STREAM_INTERACTIVE:
                # the interactive lane rides its dedicated submission
                # stream: no per-lane pinning, and the backlog feeding
                # the deadline math is the lane's OWN in-flight work —
                # a coalescing bulk queue must not spill a 2-item heal
                # flush that will launch immediately
                backlog = self.qos.ia_backlog_s()
            else:
                lane = self._lane_for(b)
                backlog = self._backlog_s(lane)
            sizes = [self._item_bytes(b, p) for p in items]
            n_dev = self.qos.plan(mode, prof, b.cls, sizes, backlog,
                                  self.completer_count,
                                  cpu_scale=_CPU_ROUTE_SCALE.get(b.op,
                                                                 1.0),
                                  lane=lane)
        # flight recorder: the routing decision for this flush (always
        # recorded — a timeline without its plans is not a timeline;
        # spill REASONS ride the scheduler's own "spill" events)
        _tl.record("plan", op=_OP_NAME.get(b.op, b.op), n=len(items),
                   device=n_dev, spilled=len(items) - n_dev,
                   stream=b.stream, **{"class": b.cls})
        return n_dev, lane

    @staticmethod
    def _rows_from_masks(masks: np.ndarray) -> np.ndarray:
        """Invert coeff_masks: uint32 [8, o, k] bit-plane masks -> uint8
        [o, k] coefficient matrix (masks[b] is all-ones iff bit b set)."""
        return ((masks & 1).astype(np.uint8)
                << np.arange(8, dtype=np.uint8)[:, None, None]).sum(
                    axis=0, dtype=np.uint8)

    def _flush_cpu(self, b: _Bucket, items: list[_Pending]):
        """Run a flush on the native AVX2 kernel (per item, on completer
        threads) — the adaptive fallback when the device link would cost
        more than the math (reference behavior: SIMD per request)."""
        from .. import native
        self.batches += 1
        self.cpu_batches += 1
        self.items += len(items)
        self.cpu_items += len(items)
        trace_done = self._flush_trace_cb(b, items, "cpu")
        span_done = self._flush_span_cb(b, items, "cpu")
        tl_done = self._tl_flush_cb(b, items, "cpu", ("cpu",))
        # observed CPU flush wall corrects the route cost EWMA (only
        # meaningful once a link profile provides the base estimate)
        prof = self._profile
        cost_done = None
        if prof is not None:
            bytes_in, bytes_out = self._flush_bytes(b, items)
            predicted = self.qos.cost.cpu_s(
                prof, bytes_in + bytes_out,
                min(len(items), self.completer_count)) * \
                _CPU_ROUTE_SCALE.get(b.op, 1.0)
            t0 = time.monotonic()
            left = [len(items)]
            llock = threading.Lock()

            def cost_done(_f, predicted=predicted, t0=t0):  # noqa: F811
                with llock:
                    left[0] -= 1
                    if left[0]:
                        return
                self.qos.cost.observe("cpu", predicted,
                                      time.monotonic() - t0)

        def one(p: _Pending):
            try:
                if b.op == "select_scan":
                    # bit-identical pure-Python twin of the scan kernel
                    from ..ops.scan_pallas import scan_blocks_reference
                    program, cols, delim, max_rows = p.params
                    blocks = np.ascontiguousarray(p.words).view(np.uint8)
                    p.future.set_result(scan_blocks_reference(
                        blocks, program, cols, delim, max_rows)[0])
                    return
                if b.op == "sse_xor":
                    # numpy ChaCha20 reference — same bytes the kernel
                    # produces (pinned), so a salvage changes nothing
                    from ..crypto.chacha20poly1305 import keystream_xor
                    cipher_key, nonces = p.params
                    data = np.ascontiguousarray(p.words).view(np.uint8)
                    out, pk = keystream_xor(cipher_key, nonces, data)
                    p.future.set_result(
                        (out.view("<u4"), pk.view("<u4")))
                    return
                u8 = np.ascontiguousarray(p.words).view(np.uint8)
                if b.op in ("encode", "encode_hashed"):
                    rows = b.codec.parity_rows
                else:
                    rows = self._rows_from_masks(p.masks)
                out = native.cpu_encode(rows, u8, rows.shape[0])
                out_words = np.ascontiguousarray(out).view(np.uint32)
                if b.op == "encode_hashed":
                    # digest data + parity shards with the native batch
                    # hasher — bit-identical to the device hash lane
                    from ..erasure.bitrot import native_batch_hasher
                    batch_hash = native_batch_hasher(b.hash_algo)
                    both = np.concatenate([u8, out], axis=0)
                    digs = batch_hash(
                        b.hash_key, both.reshape(-1, b.chunk_size))
                    n_sh = both.shape[0]
                    p.future.set_result(
                        (out_words,
                         digs.reshape(n_sh, -1).view(np.uint32)))
                elif b.op == "fused":
                    from ..erasure.bitrot import native_batch_hasher
                    batch_hash = native_batch_hasher(b.hash_algo)
                    k = u8.shape[0]
                    chunks = u8.reshape(k, -1, b.chunk_size)
                    digs = batch_hash(
                        b.hash_key, chunks.reshape(-1, b.chunk_size))
                    want = np.ascontiguousarray(p.digests).view(np.uint8)
                    valid = np.array([
                        digs[i * chunks.shape[1]:(i + 1) * chunks.shape[1]]
                        .tobytes() == want[i].tobytes() for i in range(k)])
                    p.future.set_result((out_words, valid))
                else:
                    p.future.set_result(out_words)
            except Exception as e:  # noqa: BLE001
                if not p.future.done():
                    p.future.set_exception(e)

        # interactive-lane CPU work rides its own small executor: the
        # shared pool's FIFO can hold thousands of queued bulk items,
        # and a latency-tier rebuild parked behind them defeats the
        # whole lane (ISSUE 13)
        pool = self._ia_completers \
            if b.stream == _qos.STREAM_INTERACTIVE else self._completers
        for p in items:
            if trace_done is not None:
                p.future.add_done_callback(trace_done)
            if span_done is not None:
                p.future.add_done_callback(span_done)
            if cost_done is not None:
                p.future.add_done_callback(cost_done)
            if tl_done is not None:
                p.future.add_done_callback(tl_done)
            # pure kernel compute — span context rides the attached
            # future callbacks, not the executing thread
            pool.submit(one, p)  # graftlint: disable=GL005

    def _flush_trace_cb(self, b: _Bucket, items: list[_Pending],
                        route: str):
        """Future-done callback publishing ONE kernel-type trace per
        flush (op, route, batch size, queue wait, wall duration) once
        the flush's last item resolves; None when nobody subscribes to
        the trace plane (zero hot-path cost while unobserved)."""
        if not _trc.subscribed():
            return None
        t0 = time.monotonic()
        qwait = t0 - min(p.t for p in items)
        bytes_in, bytes_out = self._flush_bytes(b, items)
        remaining = [len(items)]
        rlock = threading.Lock()

        def done(_f):
            with rlock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            _trc.publish_kernel(
                op=_OP_NAME.get(b.op, b.op), route=route,
                batch=len(items), queue_wait_s=qwait,
                duration_s=time.monotonic() - t0,
                input_bytes=bytes_in, output_bytes=bytes_out)

        return done

    def _flush_span_cb(self, b: _Bucket, items: list[_Pending],
                       route: str):
        """Future-done callback recording the flush's KERNEL SPAN into
        every traced item's span tree once the last item resolves. One
        flush serves items from many requests, so ONE shared span_id is
        recorded ONCE per involved trace (a pipelined request may
        contribute several items to the same flush — those collapse
        into its single record), carrying span links to every coalesced
        context plus that trace's oldest queue wait, its item count and
        the flush's batch id — per-request trees stay truthful under
        batching. None when no item is traced (zero hot-path cost)."""
        traced = [p for p in items if p.ctx is not None]
        if not traced or not _sp.enabled():
            return None
        t0 = time.monotonic()
        wall0 = time.time()
        span_id = _sp.new_span_id()
        with self._cv:
            self._batch_seq += 1
            batch_id = self._batch_seq
        groups: dict[str, list[_Pending]] = {}
        for p in traced:
            groups.setdefault(p.ctx.trace_id, []).append(p)
        qwait = {tid: t0 - min(p.t for p in ps)
                 for tid, ps in groups.items()}
        links = []
        seen: set[tuple[str, str]] = set()
        for p in traced:
            key = (p.ctx.trace_id, p.ctx.span_id)
            if key not in seen:
                seen.add(key)
                links.append({"trace_id": p.ctx.trace_id,
                              "span_id": p.ctx.span_id})
        op_name = _OP_NAME.get(b.op, b.op)
        remaining = [len(items)]
        rlock = threading.Lock()
        cancelled = [False]

        def done(_f):
            with rlock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            if cancelled[0]:
                # device readback salvaged on CPU: the CPU re-flush
                # records its own truthful span; a route="device" span
                # spanning the whole salvage would be a phantom launch
                return
            dur = round(time.monotonic() - t0, 6)
            for tid, ps in groups.items():
                exc = None
                for p in ps:
                    try:
                        exc = p.future.exception()
                    except BaseException:  # noqa: BLE001 — cancelled
                        exc = None  # futures raise CancelledError,
                        # which is NOT an Exception since Python 3.8
                    if exc is not None:
                        break
                _sp.record({
                    "name": f"kernel.{op_name}",
                    "trace_id": tid, "span_id": span_id,
                    "parent_span_id": ps[0].ctx.span_id, "time": wall0,
                    "duration_s": dur,
                    "error": f"{type(exc).__name__}: {exc}" if exc
                             else "",
                    "links": links,
                    "attrs": {"route": route, "batch": len(items),
                              "batch_id": batch_id,
                              "items": len(ps),
                              "queue_wait_s": round(qwait[tid], 6)}})

        done.cancel = lambda: cancelled.__setitem__(0, True)
        return done

    def _device_lanes(self) -> tuple[str, ...]:
        """Lane names a device flush occupies: one ``dev<i>`` per mesh
        device (an SPMD launch runs on every chip at once), or the
        default device's lane for single-chip launches. Cached — the
        device topology cannot change within a process."""
        lanes = getattr(self, "_lanes_cache", None)
        if lanes is not None:
            return lanes
        try:
            from .mesh import object_mesh
            mesh = object_mesh()
            if mesh is not None:
                lanes = tuple(f"dev{d.id}"
                              for d in mesh.devices.flatten())
            else:
                import jax
                lanes = (f"dev{jax.devices()[0].id}",)
        except Exception:  # noqa: BLE001 — no backend: nominal lane
            lanes = ("dev0",)
        self._lanes_cache = lanes
        return lanes

    def _tl_flush_cb(self, b: _Bucket, items: list[_Pending], route: str,
                     lanes: tuple[str, ...] = ("cpu",)):
        """Paired flight-recorder flush events (graftlint GL011: every
        CPU/device flush route emits these): ``flush_start`` now,
        ``flush_end`` once the flush's last item resolves — the end
        event also feeds the per-lane utilization accounting (busy
        ratio, batch occupancy). Returns the future-done callback (with
        a ``.cancel`` hook for the readback-salvage path, whose CPU
        re-flush records its own truthful pair), or None while the
        recorder is off — zero hot-path cost."""
        if not _tl.enabled():
            return None
        bytes_in, bytes_out = self._flush_bytes(b, items)
        fid = _tl.next_flush_id()
        op_name = _OP_NAME.get(b.op, b.op)
        cap = interactive_batch() \
            if b.stream == _qos.STREAM_INTERACTIVE else self.max_batch
        _tl.record("flush_start", op=op_name, lane=lanes, flush_id=fid,
                   batch=len(items), capacity=cap,
                   bytes=bytes_in + bytes_out, route=route,
                   stream=b.stream, **{"class": b.cls})
        t0 = time.monotonic()
        remaining = [len(items)]
        rlock = threading.Lock()
        cancelled = [False]

        def done(_f):
            with rlock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            if cancelled[0]:
                return
            _tl.record("flush_end", op=op_name, lane=lanes, flush_id=fid,
                       batch=len(items), capacity=cap,
                       bytes=bytes_in + bytes_out, route=route,
                       stream=b.stream,
                       dur=round(time.monotonic() - t0, 6))

        done.cancel = lambda: cancelled.__setitem__(0, True)
        return done

    def _device_saturated(self) -> bool:
        with self._profile_lock:
            return self._dev_inflight >= DEVICE_PIPELINE

    def _device_bound(self, b: _Bucket) -> bool:
        """Would any of this bucket's flush take the device route? Pure
        probe of the QoS scheduler (record=False: hold checks must not
        charge spill counters). Work the scheduler would spill entirely
        to CPU is NOT held — holding it up to MAX_HOLD_S would blow its
        latency budget for a device launch that will never happen."""
        mode = os.environ.get("MINIO_TPU_DISPATCH_MODE", "auto")
        if mode == "cpu":
            return False
        prof = self._profile
        if mode != "device" and prof is None:
            return False
        lane = self._lane_for(b, record=False)
        backlog = self._backlog_s(lane)
        sizes = [self._item_bytes(b, p) for p in b.items]
        return self.qos.plan(mode, prof, b.cls, sizes, backlog,
                             self.completer_count, record=False,
                             cpu_scale=_CPU_ROUTE_SCALE.get(b.op, 1.0),
                             lane=lane) > 0

    def _flush(self, b: _Bucket, items: list[_Pending]):
        # per-thread QoS tag (obs/profiler.py): the sampling profiler
        # joins this dispatcher thread's samples to the batch's class
        # and op for the duration of the flush
        from ..obs import profiler as _prof
        _prof.set_task_tag(b.cls, _OP_NAME.get(b.op, b.op))
        try:
            self._flush_tagged(b, items)
        finally:
            _prof.clear_task_tag()

    def _flush_tagged(self, b: _Bucket, items: list[_Pending]):
        from .. import fault as _fault
        self.qos.note_items(b.cls, len(items))
        if b.stream == _qos.STREAM_INTERACTIVE:
            self.ia_flushes += 1
            self.ia_items += len(items)
            if len(items) > self.ia_max_batch:
                self.ia_max_batch = len(items)
        else:
            self.bulk_flushes += 1
            self.bulk_items += len(items)
        # standing attribution (satellite of ISSUE 13): each item's
        # time from submit to flush extraction is its queue_wait —
        # the stage the 20 s heal-p99 lived in at conc 128
        now = time.monotonic()
        for p in items:
            if p.stc is not None:
                p.stc.add("queue_wait", now - p.t)
        if _fault.armed("kernel"):
            # per-flush injection point (chaos harness): an injected
            # device error exercises the CPU-salvage path — the whole
            # flush re-routes to the CPU executor, results stay correct
            try:
                _fault.inject("kernel", "device", b.op)
            except Exception:  # noqa: BLE001 — injected device failure
                self._note_salvage(b, "injected", len(items))
                self._flush_cpu(b, items)
                return
        n_dev, lane = self._plan_flush(b, items)
        dev_items, cpu_items = items[:n_dev], items[n_dev:]
        if dev_items:
            try:
                self._flush_device(b, dev_items, lane)
            except Exception as e:  # noqa: BLE001 — dead/hung device
                # (or a program the chip's compiler refuses): degrade
                sig = (b.op, dev_items[0].words.shape,
                       _pad_batch(len(dev_items)))
                with self._profile_lock:
                    first = sig not in self._failed_flush_sigs
                    self._failed_flush_sigs.add(sig)
                log.log(logging.ERROR if first else logging.WARNING,
                        "device flush failed (op=%s item shape=%s "
                        "batch=%d): %s: %s; falling back to CPU route",
                        b.op, sig[1], sig[2], type(e).__name__, e,
                        exc_info=first)
                self._mark_device_failed()
                self.batches -= 1  # _flush_cpu re-counts this flush
                self.items -= len(dev_items)
                self.device_batches -= 1  # the flush never completed
                self.device_items -= len(dev_items)
                self._note_salvage(b, "device_flush_failed",
                                   len(dev_items))
                self._flush_cpu(b, dev_items)
        if cpu_items:
            self._flush_cpu(b, cpu_items)

    def _note_salvage(self, b: _Bucket, reason: str, n: int) -> None:
        """One CPU salvage of ``n`` device-bound items: the flight
        recorder's ``salvage`` event plus the by-reason item count that
        stats() carries."""
        _tl.record("salvage", op=_OP_NAME.get(b.op, b.op),
                   lane=("cpu",), reason=reason, batch=n)
        with self._profile_lock:
            self.salvaged_items[reason] = \
                self.salvaged_items.get(reason, 0) + n

    def _mark_device_failed(self):
        with self._profile_lock:
            self._profile = None
            self._profile_failed = True
            self._probe_failed_at = time.monotonic()

    def _flush_device(self, b: _Bucket, items: list[_Pending],
                      lane: int | None = None):
        # a lock held across an XLA launch is a convoy generator even
        # when it never deadlocks — lockrank reports the holder's stack
        _lr.note_blocking(f"device_flush:{b.op}")
        t_flush0 = time.monotonic()
        compiles0 = _dev.compiles_total()
        import jax
        import jax.numpy as jnp
        from .mesh import (mesh_device, object_mesh, replicated_for,
                           sharded_batched)
        n = len(items)
        bsz = _pad_batch(n)
        # multi-chip routing, per-lane first: an affinity-pinned flush
        # occupies ONE device lane (its erasure set's — jax.device_put
        # commits the inputs there, siblings stay free for other sets);
        # unpinned flushes shard the batch (objects) axis across the
        # whole mesh via shard_map — EC math has no cross-object
        # reduction, so that is one SPMD launch with zero collectives,
        # each chip taking bsz/n_dev blocks (and pallas kernels run
        # per-device, which bare sharded inputs could not express)
        mesh = object_mesh()
        pin = mesh_device(lane) if lane is not None else None
        use_mesh = mesh is not None and pin is None
        if use_mesh and bsz % mesh.devices.size:
            bsz += -bsz % mesh.devices.size
        # the flight recorder gets the lane(s) the flush ACTUALLY
        # occupies: the pinned device lane, every mesh lane for an SPMD
        # launch, the default device otherwise
        if pin is not None:
            lanes = (f"dev{pin.id}",)
        else:
            lanes = self._device_lanes()
        trace_done = self._flush_trace_cb(b, items, "device")
        span_done = self._flush_span_cb(b, items, "device")
        tl_done = self._tl_flush_cb(b, items, "device", lanes)

        def dev(arr):
            """Input placement for this flush's route: committed to the
            pinned lane device, default placement otherwise."""
            return jax.device_put(arr, pin) if pin is not None \
                else jnp.asarray(arr)

        # count first so the fallback's decrement is always balanced
        self.batches += 1
        self.items += n
        self.device_batches += 1
        self.device_items += n
        if b.op == "sse_xor":
            # per-object package keys ride per-LANE kernel inputs now:
            # the whole flush — many objects, each with its own key —
            # is ONE padded multi-package launch (multi_fn_for) instead
            # of a Python loop of per-item launches, and the item axis
            # shards over the mesh like every other op
            from ..ops.chacha_pallas import multi_fn_for, multi_jitted
            pkgs, words = items[0].words.shape
            for p in items:
                nc = p.params[1]
                if not (len(nc) == pkgs and np.all(nc[:, 0] == nc[0, 0])
                        and np.all(nc[:, 1] == nc[0, 1])):
                    raise ValueError(
                        "packages of one item share nonce words 0/1 "
                        "(base_iv[:8]); only word 2 varies per package")
            keys = np.stack(
                [np.frombuffer(p.params[0], "<u4") for p in items] +
                [np.frombuffer(items[0].params[0], "<u4")] * (bsz - n))
            nonces = np.stack(
                [p.params[1].astype(np.uint32) for p in items] +
                [items[0].params[1].astype(np.uint32)] * (bsz - n))
            data = np.stack([p.words for p in items] +
                            [items[0].words] * (bsz - n))
            if use_mesh:
                fn = sharded_batched(multi_fn_for(pkgs, words), mesh,
                                     (True, True, True), out_batch=2)
                out_dev = fn(keys, nonces, data)
            else:
                out_dev = multi_jitted(pkgs, words)(
                    dev(keys), dev(nonces), dev(data))
            if bsz != n:  # drop pad lanes ON DEVICE, not over the link
                out_dev = (out_dev[0][:n], out_dev[1][:n])
            self._account_and_complete(b, out_dev, items, span_done,
                                       trace_done, tl_done, lane=lane,
                                       t_flush0=t_flush0,
                                       compiles0=compiles0)
            return
        stack = np.stack([p.words for p in items] +
                         [items[0].words] * (bsz - n))
        if b.op == "select_scan":
            # every item of a select_scan bucket shares (program, cols,
            # delim, max_rows) — they ride the bucket key; the block
            # (batch) axis shards over the mesh exactly like the
            # erasure ops' routes
            from ..ops.scan_pallas import scan_fn_for
            program, cols, delim, max_rows = items[0].params
            fn = scan_fn_for(program, cols, delim,
                             stack.shape[-1] * 4, max_rows)
            blocks = stack[:, 0, :]
            if use_mesh:
                out_dev = sharded_batched(fn, mesh, (True,))(blocks)
            else:
                out_dev = fn(dev(blocks))
        elif b.op == "encode":
            if use_mesh:
                fn = sharded_batched(b.codec._mm_batch, mesh, (False, True))
                out_dev = fn(replicated_for(
                    b.codec, "_mesh_enc_masks", b.codec._enc_masks, mesh),
                    stack)
            else:
                out_dev = b.codec.encode_words_batch(dev(stack))
        elif b.op == "encode_hashed":
            from ..obs import metrics as _mx
            from ..ops.fused import encode_hashed_fn_for
            inner = encode_hashed_fn_for(b.hash_key, stack.shape[-1] * 4,
                                         b.codec.encode_words_batch,
                                         b.chunk_size, b.hash_algo)
            _mx.inc("minio_tpu_pipeline_fused_hash_flushes_total",
                    op="encode_hashed")
            if use_mesh:
                fn = sharded_batched(inner, mesh, (True,), out_batch=2)
                out_dev = fn(stack)
            else:
                out_dev = inner(dev(stack))
        elif b.op == "masked":
            masks = np.stack([p.masks for p in items] +
                             [items[0].masks] * (bsz - n))
            if use_mesh:
                fn = sharded_batched(b.codec._mm_batch_per, mesh,
                                     (True, True))
                out_dev = fn(masks, stack)
            elif b.stream == _qos.STREAM_INTERACTIVE and \
                    _donate_active():
                # interactive lane on a TPU backend: the rebuild's
                # shard-words input buffer is DONATED to the launch
                # (jax donate_argnums), so the small latency-tuned HBM
                # round trips don't double-allocate; the fresh
                # per-flush stack is never touched again host-side
                out_dev = b.codec.batch_per_donated()(
                    dev(masks), dev(stack))
            else:
                out_dev = b.codec._mm_batch_per(dev(masks), dev(stack))
        else:  # 'fused': verify source digests + rebuild in one launch
            from ..obs import metrics as _mx
            from ..ops.fused import fused_fn_for
            _mx.inc("minio_tpu_pipeline_fused_hash_flushes_total",
                    op="fused")
            masks = np.stack([p.masks for p in items] +
                             [items[0].masks] * (bsz - n))
            digs = np.stack([p.digests for p in items] +
                            [items[0].digests] * (bsz - n))
            inner = fused_fn_for(b.hash_key, stack.shape[-1] * 4,
                                 b.codec._mm_batch_per, b.chunk_size,
                                 b.hash_algo)
            if use_mesh:
                fn = sharded_batched(inner, mesh, (True, True, True),
                                     out_batch=2)
                out_dev = fn(masks, stack, digs)
            else:
                out_dev = inner(dev(masks), dev(stack), dev(digs))
        if bsz != n:
            # slice the padded batch tail to n ON DEVICE before the
            # host readback: the completer used to down-link up to
            # (mesh multiple - 1) copies of items[0] per flush and
            # discard them on unpack — pad bytes never ride the link
            # and never count in _flush_bytes' QoS accounting
            out_dev = tuple(o[:n] for o in out_dev) \
                if isinstance(out_dev, tuple) else out_dev[:n]
        self._account_and_complete(b, out_dev, items, span_done,
                                   trace_done, tl_done, lane=lane,
                                   t_flush0=t_flush0, compiles0=compiles0)

    def _account_and_complete(self, b: _Bucket, out_dev,
                              items: list[_Pending], span_done,
                              trace_done, tl_done=None,
                              lane: int | None = None,
                              t_flush0: float = 0.0,
                              compiles0: int | None = None):
        """Post-launch tail shared by every device flush: extend the
        queue model (the chosen LANE's busy-until for pinned flushes,
        every lane's for SPMD; the interactive lane's OWN model for its
        stream), account queued bytes, attach trace/span callbacks and
        hand host readback off — to a blocking completer thread on the
        bulk lane, to the on_ready POLLER on the interactive lane (the
        async-completion half of ISSUE 13: the flush loop never stalls
        on readback, and no thread parks inside a device wait)."""
        interactive = b.stream == _qos.STREAM_INTERACTIVE
        # queue model: extend the predicted drain deadline by this
        # flush's link+kernel estimate so the scheduler sees the backlog
        prof = self._profile
        accounted = prof is not None
        bytes_in, bytes_out = self._flush_bytes(b, items)
        predicted_s = 0.0
        flush_s = 0.0
        if accounted:
            # a flush whose launch compiled a program is set-up, not link
            # cost: its wall must not teach the route-cost EWMA (seen on
            # the v5e, PR 21: eight 26 s first calls pinned the device
            # correction at its 10x cap and every PUT block of a
            # forced-device run then spilled for `budget`)
            if compiles0 is None or _dev.compiles_total() == compiles0:
                predicted_s = self.qos.cost.device_s(prof, bytes_in,
                                                     bytes_out)
            flush_s = prof.device_flush_s(bytes_in, bytes_out)
            now = time.monotonic()
            with self._profile_lock:
                self._dev_inflight += 1
                if lane is None and not interactive:
                    # only bulk SPMD flushes extend the global serial
                    # model: a pinned flush occupies ONE lane (its wall
                    # lives in the scheduler's per-lane busy-until) and
                    # an interactive flush lives in the ia model —
                    # summing parallel walls into one serial deadline
                    # read as a phantom backlog and spilled idle work
                    self._dev_busy_until = \
                        max(self._dev_busy_until, now) + flush_s
        # per-route queued-bytes accounting feeds the scheduler's caps
        # (global + this flush's lane + the interactive lane's model)
        self.qos.device_dispatched(bytes_in + bytes_out, lane=lane,
                                   flush_s=0.0 if interactive
                                   else flush_s)
        if interactive:
            self.qos.ia_dispatched(bytes_in + bytes_out, flush_s=flush_s)
        # standing attribution: host-side launch cost of this flush
        # (stack/upload/dispatch) — the "flush" stage between
        # queue_wait and readback
        if t_flush0 > 0.0:
            dt = time.monotonic() - t_flush0
            for p in items:
                if p.stc is not None:
                    p.stc.add("dev_flush", dt)
        for p in items:
            if trace_done is not None:
                p.future.add_done_callback(trace_done)
            if span_done is not None:
                p.future.add_done_callback(span_done)
            if tl_done is not None:
                p.future.add_done_callback(tl_done)
        # device-plane HBM ledger (obs/device.py): this flush's live
        # device buffers, charged to its lane until the readback lands
        # (donated rebuilds alias input into output — flagged, and the
        # release in _complete's finally covers the salvage path too)
        names = getattr(self, "_lanes_cache", None)
        ledger_lane = "interactive" if interactive else \
            ("mesh" if lane is None and names and len(names) > 1
             else "bulk")
        tok = _dev.ledger_acquire(
            ledger_lane, bytes_in + bytes_out,
            donated=interactive and b.op == "masked"
            and _donate_active())
        try:
            if interactive:
                # async completion: the poller polls device readiness
                # (is_ready — the __await__-free on_ready form) and
                # completes in submission order per bucket
                self._async_completer().submit(_IAHandle(
                    b, out_dev, items, accounted,
                    bytes_in + bytes_out, predicted_s,
                    time.monotonic(), span_done, tl_done, lane, tok))
            else:
                # hand host readback to a completer so the next batch
                # launches while this one's transfer is in flight
                self._completers.submit(self._complete, b, out_dev,
                                        items, accounted,
                                        bytes_in + bytes_out,
                                        predicted_s, time.monotonic(),
                                        span_done, tl_done, lane, tok)
        except BaseException:  # submit refused (shutdown): the paired
            self.qos.device_completed(bytes_in + bytes_out, lane=lane)
            if interactive:
                self.qos.ia_completed(bytes_in + bytes_out)
            if accounted:  # the pipeline slot must not stay occupied
                with self._profile_lock:
                    self._dev_inflight = max(0, self._dev_inflight - 1)
            _dev.ledger_release(tok)
            raise  # must not leak into the queued-bytes cap

    def _complete(self, b: _Bucket, out_dev, items: list[_Pending],
                  accounted: bool = True, qbytes: int = 0,
                  predicted_s: float = 0.0, t0: float = 0.0,
                  span_done=None, tl_done=None, lane: int | None = None,
                  tok=None):
        try:
            self._finish_readback(b, out_dev, items, span_done, tl_done)
        finally:
            # device-plane estimator + ledger release (obs/device.py):
            # submit -> readback-ready is the cheap per-op device-time
            # estimate feeding the roofline ratios; the ledger release
            # runs in the SAME finally, so the CPU-salvage path inside
            # _finish_readback still balances the lane
            if t0 > 0.0:
                _dev.note_device_time(_OP_NAME.get(b.op, b.op),
                                      time.monotonic() - t0, qbytes)
            _dev.ledger_release(tok)
            self.qos.device_completed(qbytes, lane=lane)
            if b.stream == _qos.STREAM_INTERACTIVE:
                self.qos.ia_completed(qbytes)
            if predicted_s > 0.0 and t0 > 0.0:
                # observed flush wall corrects the route cost EWMA
                self.qos.cost.observe("device", predicted_s,
                                      time.monotonic() - t0)
            if accounted:  # pairs with _flush_device's increment
                with self._profile_lock:
                    self._dev_inflight = max(0, self._dev_inflight - 1)
                    if self._dev_inflight == 0:
                        # drained ahead of (or behind) the model: resync
                        self._dev_busy_until = time.monotonic()
                # a pipeline slot freed: wake the bulk loop so held
                # buckets flush their coalesced batch now (the
                # interactive loop never holds, so it has no interest
                # in pipeline slots)
                with self._cv:
                    self._cv.notify()

    def _finish_readback(self, b: _Bucket, out_dev,
                         items: list[_Pending], span_done=None,
                         tl_done=None):
        t_rb = time.monotonic()

        def _charge_readback():
            # standing attribution: device wait + host copy for this
            # flush's results (the stage after queue_wait/dev_flush)
            dt = time.monotonic() - t_rb
            for p in items:
                if p.stc is not None:
                    p.stc.add("readback", dt)

        try:
            if b.op == "sse_xor":
                # one batched (ct, poly_keys) pair for the whole flush.
                # Each item gets a COPY, not a view: sse results are
                # full payload bytes, and a view would pin the entire
                # flush's batched array for as long as ANY consumer
                # (e.g. one slow streaming writer) holds its slice
                ct = np.asarray(out_dev[0])
                pk = np.asarray(out_dev[1])
                _charge_readback()
                for i, p in enumerate(items):
                    p.future.set_result((ct[i].copy(), pk[i].copy()))
            elif b.op in ("fused", "encode_hashed"):
                out = np.asarray(out_dev[0])
                extra = np.asarray(out_dev[1])  # valid mask / digests
                _charge_readback()
                for i, p in enumerate(items):
                    p.future.set_result((out[i], extra[i]))
            else:
                out = np.asarray(out_dev)
                _charge_readback()
                for i, p in enumerate(items):
                    p.future.set_result(out[i])
        except Exception:  # noqa: BLE001 — readback died: CPU salvages
            log.warning("device readback failed; salvaging flush on CPU",
                        exc_info=True)
            self._mark_device_failed()
            if span_done is not None:
                # the device launch delivered nothing — the CPU
                # re-flush below records the truthful kernel span
                span_done.cancel()
            if tl_done is not None:
                # ditto for the flight recorder: the CPU re-flush emits
                # its own truthful flush pair; a device flush_end here
                # would integrate salvage time into device busy-ratio
                tl_done.cancel()
            pending = [p for p in items if not p.future.done()]
            if pending:
                self.batches -= 1
                self.items -= len(pending)
                self.device_batches -= 1  # readback never delivered
                self.device_items -= len(pending)
                self._note_salvage(b, "readback_failed", len(pending))
                self._flush_cpu(b, pending)

    def stop(self):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
            self._ia_cv.notify_all()
        # the interactive dispatcher first (it defers its leftovers to
        # the bulk loop's drain), then the bulk loop's drain, then the
        # async completer (which must still accept the drain's flushes)
        self._ia_thread.join(timeout=5)
        self._thread.join(timeout=5)
        if self._ia_completer is not None:
            self._ia_completer.stop()
        # a probe mid-device-transfer at interpreter exit can abort the
        # teardown; wait it out before the caller tears the process down
        t = getattr(self, "_probe_thread", None)
        if t is not None and t.is_alive():
            t.join(timeout=10)
        self._completers.shutdown(wait=True)
        self._ia_completers.shutdown(wait=True)

    def lane_queued_bytes(self) -> dict:
        """Per-lane queued bytes {lane_name: bytes} for the metrics
        plane. Empty until a device flush resolved the lane topology —
        a metrics scrape must never be what initializes the backend."""
        names = getattr(self, "_lanes_cache", None)
        if not names or len(names) <= 1:
            return {}
        queued = self.qos.lane_queued_bytes()
        return {names[i]: (queued[i] if i < len(queued) else 0)
                for i in range(len(names))}

    def _probe_state(self) -> dict:
        """Link-probe state for stats(): ``ok`` (a profile is installed),
        ``failed`` (the last probe raised, or a device failure dropped
        the profile), else ``pending``; plus the profile's numbers."""
        with self._profile_lock:
            prof, failed = self._profile, self._profile_failed
            out = {"state": "ok" if prof is not None else
                   ("failed" if failed else "pending"),
                   "failures": self.probe_failures}
        if prof is not None:
            out.update(rt_s=prof.rt_s, up_gibs=prof.up_gibs,
                       down_gibs=prof.down_gibs, cpu_gibs=prof.cpu_gibs)
        return out

    def stats(self) -> dict:
        with self._cv:
            qdepth = sum(len(b.items) for b in self._buckets.values())
        return {"batches": self.batches, "items": self.items,
                "cpu_batches": self.cpu_batches,
                "device_batches": self.device_batches,
                "cpu_items": self.cpu_items,
                "device_items": self.device_items,
                "hold_events": self.hold_events,
                "hold_seconds": round(self.hold_seconds, 3),
                "spilled_items": self.qos.spilled_items,
                "spilled_batches": self.qos.spilled_batches,
                "spill_reasons": dict(self.qos.spill_reasons),
                "salvaged_items": dict(self.salvaged_items),
                "probe": self._probe_state(),
                "class_items": dict(self.qos.class_items),
                "deadline_misses": dict(self.qos.deadline_misses),
                "queue_depth": qdepth,
                "device_queued_bytes": self.qos.device_queued_bytes(),
                "lane_diverts": self.qos.lane_diverts,
                "lane_queued_bytes": self.lane_queued_bytes(),
                "bulk_flushes": self.bulk_flushes,
                "bulk_items": self.bulk_items,
                "interactive_lane": {
                    "enabled": interactive_lane_enabled(),
                    "flushes": self.ia_flushes,
                    "items": self.ia_items,
                    "deadline_cuts": self.ia_deadline_cuts,
                    "async_completions": self.ia_async_completions,
                    "max_batch": self.ia_max_batch,
                    "batch_cap": interactive_batch(),
                    "queued_bytes": self.qos.ia_queued_bytes(),
                    "backlog_s": round(self.qos.ia_backlog_s(), 6),
                },
                "avg_batch": self.items / self.batches if self.batches else 0}


_global: DispatchQueue | None = None
_global_lock = threading.Lock()


def global_queue() -> DispatchQueue:
    global _global
    if _global is None:
        with _global_lock:
            if _global is None:
                _global = DispatchQueue()
    return _global


def shutdown_global() -> None:
    """Stop the global queue (drains pending work, joins the dispatcher,
    shuts the completer pool down) and forget it; the next global_queue()
    call builds a fresh one. Part of minio_tpu.shutdown()."""
    global _global
    with _global_lock:
        q, _global = _global, None
    if q is not None:
        q.stop()
