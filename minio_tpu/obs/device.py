"""Device-plane observability — the device-side sibling of the PR 14
host profiler (ISSUE 16). Four pillars:

* **HBM accounting.** Per-device memory snapshots
  (``jax.Device.memory_stats()`` where the backend exposes them) plus a
  dispatch-integrated **live-buffer ledger**: every array a flush path
  holds on device (bulk lane, interactive lane, donated buffers,
  mesh-pinned inputs) is acquired against a per-lane ledger at launch
  and released when the readback lands (or the salvage path unwinds).
  The ledger is the authoritative per-lane
  ``minio_tpu_device_hbm_{used,peak,live_buffers}`` source — it works on
  every backend, including CPU where ``memory_stats()`` is absent — and
  doubles as a **leak gate**: after a pipeline drain every lane must be
  back to zero live buffers (``ledger_balanced()``).
* **Compile observability.** :func:`tracked_jit` wraps ``jax.jit`` so
  every compile site in ``ops/*.py``, ``runtime/dispatch.py`` and
  ``runtime/mesh.py`` (enforced by graftlint GL017) counts and times
  compilations per (op, shape-signature). Each first-seen signature
  emits a ``compile`` event into the flight recorder (PR 9 timeline), a
  ``compile`` stage charge into the armed attribution collector (PR 9
  stages/attribution) — a recompile-induced e2e spike is pinned to the
  request AND the shape that caused it — and feeds a **compile-storm
  detector**: more than ``storm_threshold`` compiles inside
  ``storm_window_s`` kicks a breach-style burst capture through the
  PR 14 cooldown machinery (``profiler.note_breach("compile_storm")``).
* **Per-kernel device timing.** An always-on cheap estimator — device
  time ≈ readback-ready minus dispatch, charged by ``_complete`` on both
  lanes — rolled into per-op device-seconds, plus on-demand
  ``jax.profiler`` trace sessions behind the admin plane
  (``GET /minio/admin/v3/device?trace=<seconds>``).
* **Roofline attribution.** Per-op achieved GiB/s (bytes moved over
  estimated device-seconds) vs. the calibrated kernel-plane ceiling
  (179 GiB/s encode / 183 GiB/s reconstruct: round-5 record, a set-up
  that is gone, git history; not measured on the current chip) as
  ``minio_tpu_kernel_roofline_ratio{op}`` — "the mesh scaled 6×"
  becomes a per-kernel measured claim.

Served at ``GET /minio/admin/v3/device`` (``?peers=1`` fans out over the
dist plane like ``obs/health.py``), ``madmin.device_status()``, the
``minio_tpu_device_obs_*`` metric family, and the dynamic ``device_obs``
config KVS subsystem (docs/config.md).

Everything here is import-light: ``jax`` is only imported lazily on the
first tracked call / explicit snapshot, so pulling in the obs package
never initializes a backend.
"""
from __future__ import annotations

import collections
import threading
import time

#: compile-storm defaults (overridable via the ``device_obs`` KVS)
DEFAULT_STORM_THRESHOLD = 8.0
DEFAULT_STORM_WINDOW_S = 30.0
#: roofline ceilings, GiB/s (encode 179, reconstruct 183: round-5
#: record, a set-up that is gone, git history; operators re-pin via
#: config after measuring their own part)
DEFAULT_ROOFLINE_ENCODE_GIBS = 179.0
DEFAULT_ROOFLINE_RECONSTRUCT_GIBS = 183.0
#: cap on distinct (op, shape-signature) compile rows — signatures are
#: as static as the workload's shape discipline; this only bounds a
#: pathological shape-shifting client (overflow folds into "<other>")
MAX_COMPILE_ROWS = 512
#: bound on the jax.profiler trace session an operator can request
MAX_TRACE_S = 30.0

_GIB = float(1 << 30)

_lock = threading.Lock()

# -- config ------------------------------------------------------------------

_apply_registered = False


def _register_apply() -> None:
    """Invalidate the shared ~5s config cache on dynamic ``device_obs``
    changes (same pattern as obs/profiler.py). Idempotent, best
    effort."""
    global _apply_registered
    if _apply_registered:
        return
    try:
        from ..config import get_config_sys

        def _invalidate(_cfg) -> None:
            from ..qos.budget import _cfg_cache
            for key in [k for k in list(_cfg_cache)
                        if k[0] == "device_obs"]:
                _cfg_cache.pop(key, None)

        get_config_sys().on_apply("device_obs", _invalidate)
        _apply_registered = True
    except Exception:  # noqa: BLE001 — config plane absent
        pass


def _cfg(key: str, env: str, default: float) -> float:
    """device_obs.<key> through the dynamic config KVS (env > stored >
    default), on the same short-TTL registry cache the QoS budgets
    use — the tracked-jit fast path reads ``enable`` per call."""
    from ..qos.budget import _config_float
    _register_apply()
    return _config_float("device_obs", key, env, default)


def enabled() -> bool:
    return _cfg("enable", "MINIO_TPU_DEVICE_OBS", 1.0) != 0.0


def storm_threshold() -> int:
    return max(2, int(_cfg("storm_threshold",
                           "MINIO_TPU_DEVICE_OBS_STORM_THRESHOLD",
                           DEFAULT_STORM_THRESHOLD)))


def storm_window_s() -> float:
    return max(1.0, _cfg("storm_window_s",
                         "MINIO_TPU_DEVICE_OBS_STORM_WINDOW_S",
                         DEFAULT_STORM_WINDOW_S))


def roofline_gibs(op: str) -> float:
    """Calibrated ceiling for ``op``: encode-shaped ops ride the encode
    ceiling, reconstruct-shaped ops (masked rebuild, fused
    reconstruct+hash) the reconstruct one; everything else defaults to
    the encode figure (both kernels are XOR-reduction bound — the two
    ceilings differ by ~2%)."""
    if op in ("masked", "reconstruct", "fused"):
        return max(1.0, _cfg("roofline_reconstruct_gibs",
                             "MINIO_TPU_DEVICE_OBS_ROOFLINE_RECONSTRUCT",
                             DEFAULT_ROOFLINE_RECONSTRUCT_GIBS))
    return max(1.0, _cfg("roofline_encode_gibs",
                         "MINIO_TPU_DEVICE_OBS_ROOFLINE_ENCODE",
                         DEFAULT_ROOFLINE_ENCODE_GIBS))


# -- pillar 2: compile observability -----------------------------------------

#: (op, signature) -> {"count": int, "seconds": float, "last_at": float}
_compiles: dict[tuple[str, str], dict] = {}
_compiles_total = 0
_compile_seconds_total = 0.0
#: monotonic timestamps of recent compiles (storm detector window)
_storm_times: collections.deque = collections.deque(maxlen=4096)
_storms_total = 0
_last_storm_mono = 0.0


def _leaf_sig(x) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        dims = ",".join(str(d) for d in tuple(shape))
        return f"{dtype}[{dims}]"
    if isinstance(x, (bool, int, float, str, bytes, type(None))):
        return repr(x)
    return type(x).__name__


def _signature(args: tuple, kwargs: dict) -> str:
    """Compact abstract signature of a call: per-leaf shape/dtype for
    arrays, repr for static scalars — the same equivalence jax's jit
    cache keys on (up to weak types), rendered human-readable for the
    compile table."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = ";".join(_leaf_sig(x) for x in leaves)
    return sig if sig else f"<{treedef}>"


class _TrackedJit:
    """A ``jax.jit``-compiled callable that counts and times first-call-
    per-signature compilations. Builds the underlying jit lazily (no jax
    import at module import), passes tracer calls straight through (a
    tracked fn called inside another traced fn inlines — jax does not
    recompile it separately), and tolerates ``setattr`` so
    ``runtime/mesh.py``'s per-fn shard cache keeps working."""

    def __init__(self, fn, op: str, jit_kwargs: dict):
        self._fn = fn
        self.op = op
        self._jit_kwargs = jit_kwargs
        self._jitted = None
        self._seen: set[str] = set()
        self._seen_lock = threading.Lock()
        self.__name__ = getattr(fn, "__name__", "fn")
        self.__doc__ = getattr(fn, "__doc__", None)
        self.__wrapped__ = fn

    def _build(self):
        jitted = self._jitted
        if jitted is None:
            import jax
            # the ONE sanctioned jax.jit construction site (GL017
            # exempts this module): every other site routes through
            # tracked_jit so compile counting cannot lose coverage
            jitted = jax.jit(self._fn, **self._jit_kwargs)
            self._jitted = jitted
        return jitted

    def lower(self, *args, **kwargs):
        return self._build().lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        jitted = self._build()
        if not enabled():
            return jitted(*args, **kwargs)
        import jax
        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return jitted(*args, **kwargs)
        sig = _signature(args, kwargs)
        with self._seen_lock:
            first = sig not in self._seen
            if first:
                self._seen.add(sig)
        if not first:
            return jitted(*args, **kwargs)
        t0 = time.monotonic()
        try:
            out = jitted(*args, **kwargs)
        except BaseException:
            with self._seen_lock:
                self._seen.discard(sig)
            raise
        note_compile(self.op, sig, time.monotonic() - t0)
        return out


def tracked_jit(fn=None, *, op: str | None = None, **jit_kwargs):
    """``jax.jit`` with compile tracking. Drop-in at every compile site
    (GL017): plain call ``tracked_jit(f)``, decorator ``@tracked_jit``,
    or configured ``@functools.partial(tracked_jit, op="encode",
    static_argnames=...)`` — all jit kwargs (``donate_argnums``,
    ``static_argnames``, ...) pass through. ``op`` labels the compile
    table row; defaults to the function's ``__name__``."""
    if fn is None:
        def deco(f):
            return tracked_jit(f, op=op, **jit_kwargs)
        return deco
    return _TrackedJit(fn, op or getattr(fn, "__name__", "fn"),
                       jit_kwargs)


def note_compile(op: str, sig: str, dt: float) -> None:
    """Record one compilation: table row, totals, timeline ``compile``
    event, ``compile`` attribution stage, storm detector."""
    global _compiles_total, _compile_seconds_total
    now = time.monotonic()
    window = storm_window_s()
    threshold = storm_threshold()
    storm = False
    with _lock:
        _compiles_total += 1
        _compile_seconds_total += dt
        key = (op, sig)
        if key not in _compiles and len(_compiles) >= MAX_COMPILE_ROWS:
            key = (op, "<other>")
        row = _compiles.get(key)
        if row is None:
            row = _compiles[key] = {"count": 0, "seconds": 0.0,
                                    "last_at": 0.0}
        row["count"] += 1
        row["seconds"] += dt
        row["last_at"] = time.time()
        _storm_times.append(now)
        while _storm_times and now - _storm_times[0] > window:
            _storm_times.popleft()
        if (len(_storm_times) >= threshold
                and now - _last_storm_mono >= window):
            storm = True
    from . import timeline as _tl
    _tl.record("compile", op=op, sig=sig, seconds=round(dt, 6))
    from . import stages as _stages
    stc = _stages.active()
    if stc is not None:
        stc.add("compile", dt)
    if storm:
        _note_storm(now)


def _note_storm(now: float) -> None:
    """Storm transition: count it, kick a breach-style burst capture
    through the host profiler's cooldown machinery (so the capture shows
    WHAT was recompiling), bump the metric counter."""
    global _storms_total, _last_storm_mono
    with _lock:
        _storms_total += 1
        _last_storm_mono = now
    from . import profiler as _prof
    _prof.note_breach("compile_storm")
    from . import metrics as mx
    mx.inc("minio_tpu_device_obs_compile_storms_total")


def compiles_total() -> int:
    with _lock:
        return _compiles_total


def compile_snapshot() -> dict:
    """The compile plane: totals plus the per-(op, signature) table,
    rows sorted by cumulative seconds descending."""
    with _lock:
        rows = [{"op": op, "signature": sig, "count": r["count"],
                 "seconds": round(r["seconds"], 6),
                 "last_at": r["last_at"]}
                for (op, sig), r in _compiles.items()]
        total, secs, storms = (_compiles_total, _compile_seconds_total,
                               _storms_total)
    rows.sort(key=lambda r: -r["seconds"])
    return {"compiles_total": total,
            "compile_seconds_total": round(secs, 6),
            "storms_total": storms,
            "storm_threshold": storm_threshold(),
            "storm_window_s": storm_window_s(),
            "table": rows}


# -- pillar 1: HBM live-buffer ledger ----------------------------------------


class _LaneLedger:
    """Per-lane live device-buffer accounting. ``bytes`` are the flush
    path's own estimate (payload in + out) — a lower bound on what the
    backend actually reserved, but it moves 1:1 with the arrays the
    dispatch pipeline holds, which is exactly what the leak gate and
    per-lane gauges need."""

    __slots__ = ("live_buffers", "live_bytes", "peak_bytes",
                 "peak_buffers", "acquired_total", "released_total",
                 "donated_total")

    def __init__(self):
        self.live_buffers = 0
        self.live_bytes = 0
        self.peak_bytes = 0
        self.peak_buffers = 0
        self.acquired_total = 0
        self.released_total = 0
        self.donated_total = 0

    def snapshot(self) -> dict:
        return {"live_buffers": self.live_buffers,
                "live_bytes": self.live_bytes,
                "peak_bytes": self.peak_bytes,
                "peak_buffers": self.peak_buffers,
                "acquired_total": self.acquired_total,
                "released_total": self.released_total,
                "donated_total": self.donated_total}


_LANES = ("bulk", "interactive", "mesh")
_ledgers: dict[str, _LaneLedger] = {ln: _LaneLedger() for ln in _LANES}


class _LedgerToken:
    """Release handle for one ledger acquisition; release is idempotent
    (the dispatch unwind paths can race the completer's finally)."""

    __slots__ = ("lane", "nbytes", "released")

    def __init__(self, lane: str, nbytes: int):
        self.lane = lane
        self.nbytes = nbytes
        self.released = False


def ledger_acquire(lane: str, nbytes: int,
                   donated: bool = False) -> _LedgerToken | None:
    """Charge ``nbytes`` of live device buffers to ``lane`` (one of
    bulk/interactive/mesh); returns the token to ``ledger_release`` when
    the readback lands. None when the plane is disabled (callers pass
    None through unconditionally)."""
    if not enabled():
        return None
    led = _ledgers.get(lane) or _ledgers["bulk"]
    nbytes = int(nbytes)
    with _lock:
        led.live_buffers += 1
        led.live_bytes += nbytes
        led.acquired_total += 1
        if donated:
            led.donated_total += 1
        if led.live_bytes > led.peak_bytes:
            led.peak_bytes = led.live_bytes
        if led.live_buffers > led.peak_buffers:
            led.peak_buffers = led.live_buffers
    return _LedgerToken(lane, nbytes)


def ledger_release(tok: _LedgerToken | None) -> None:
    if tok is None:
        return
    with _lock:
        if tok.released:
            return
        tok.released = True
        led = _ledgers.get(tok.lane) or _ledgers["bulk"]
        led.live_buffers -= 1
        led.live_bytes -= tok.nbytes
        led.released_total += 1


def ledger_snapshot() -> dict:
    with _lock:
        return {ln: led.snapshot() for ln, led in _ledgers.items()}


def ledger_balanced() -> bool:
    """The leak gate: after a pipeline drain every lane's live count and
    byte balance must be back to zero."""
    with _lock:
        return all(led.live_buffers == 0 and led.live_bytes == 0
                   for led in _ledgers.values())


# -- host buffer-pool counters (bufpool hook) --------------------------------

_host_buf = {"acquired_total": 0, "released_total": 0, "live": 0,
             "live_bytes": 0, "peak_bytes": 0}


def note_host_buf(nbytes: int, acquired: bool) -> None:
    """Host-side staging-buffer traffic from ``runtime/bufpool.py`` —
    the host mirror of the device ledger (pinned-host staging feeds
    every device transfer, so its high-water tracks transfer
    pressure)."""
    if not enabled():
        return
    with _lock:
        if acquired:
            _host_buf["acquired_total"] += 1
            _host_buf["live"] += 1
            _host_buf["live_bytes"] += nbytes
            if _host_buf["live_bytes"] > _host_buf["peak_bytes"]:
                _host_buf["peak_bytes"] = _host_buf["live_bytes"]
        else:
            _host_buf["released_total"] += 1
            _host_buf["live"] = max(0, _host_buf["live"] - 1)
            _host_buf["live_bytes"] = max(
                0, _host_buf["live_bytes"] - nbytes)


# -- device memory_stats snapshots -------------------------------------------


def _backend_live() -> bool:
    """True when jax has already initialized a backend — a metrics
    scrape must never be what spins one up."""
    import sys
    jm = sys.modules.get("jax")
    if jm is None:
        return False
    try:
        backends = jm._src.xla_bridge._backends  # noqa: SLF001
        return bool(backends)
    except Exception:  # noqa: BLE001 — internals moved: be conservative
        return False


def device_memory(touch: bool = False) -> list[dict]:
    """Per-device ``memory_stats()`` rows (empty on backends without
    them, e.g. CPU — the ledger is the fallback). With ``touch=False``
    (metrics scrapes) this returns [] unless a backend is already
    live; the admin endpoint passes ``touch=True`` (an explicit
    operator action may initialize)."""
    if not touch and not _backend_live():
        return []
    try:
        import jax
        devs = jax.local_devices()
    except Exception:  # noqa: BLE001 — no backend at all
        return []
    out = []
    for d in devs:
        row: dict = {"id": getattr(d, "id", -1),
                     "platform": getattr(d, "platform", "?")}
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without memory_stats
            stats = None
        if stats:
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                      "largest_free_block_bytes"):
                if k in stats:
                    row[k] = int(stats[k])
        out.append(row)
    return out


# -- pillar 3+4: device-seconds estimator + roofline -------------------------

#: op -> {"seconds": float, "bytes": int, "flushes": int}
_device_time: dict[str, dict] = {}


def note_device_time(op: str, seconds: float, nbytes: int) -> None:
    """Charge one flush's estimated device time (launch -> readback
    ready, measured by ``_complete`` on both lanes) and bytes moved to
    ``op``. The estimate includes queueing on the device stream —
    an upper bound on pure kernel time, so roofline ratios are
    conservative (never flattered)."""
    if not enabled() or seconds <= 0:
        return
    with _lock:
        row = _device_time.get(op)
        if row is None:
            row = _device_time[op] = {"seconds": 0.0, "bytes": 0,
                                      "flushes": 0}
        row["seconds"] += seconds
        row["bytes"] += int(nbytes)
        row["flushes"] += 1


def roofline_snapshot() -> dict:
    """Per-op achieved GiB/s and the ratio against the calibrated
    ceiling."""
    with _lock:
        rows = {op: dict(r) for op, r in _device_time.items()}
    out = {}
    for op, r in rows.items():
        secs = r["seconds"]
        achieved = (r["bytes"] / _GIB / secs) if secs > 0 else 0.0
        ceiling = roofline_gibs(op)
        out[op] = {"device_seconds": round(secs, 6),
                   "bytes": r["bytes"],
                   "flushes": r["flushes"],
                   "achieved_gibs": round(achieved, 6),
                   "ceiling_gibs": ceiling,
                   "roofline_ratio": round(achieved / ceiling, 8)}
    return out


# -- on-demand jax.profiler trace sessions -----------------------------------

_trace_busy = False


def capture_trace(seconds: float = 1.0) -> dict:
    """One on-demand ``jax.profiler`` trace session (admin plane:
    ``GET /minio/admin/v3/device?trace=<seconds>``). Writes the trace
    into a fresh tempdir and returns its path + files — the operator
    pulls the ``.trace``/``xplane.pb`` artifacts with their own
    tooling. One session at a time; bounded duration."""
    global _trace_busy
    if not enabled():
        return {"error": "device_obs disabled"}
    seconds = min(max(float(seconds), 0.05), MAX_TRACE_S)
    with _lock:
        if _trace_busy:
            return {"error": "a trace session is already running"}
        _trace_busy = True
    try:
        import os
        import tempfile
        import jax
        logdir = tempfile.mkdtemp(prefix="minio-tpu-devtrace-")
        t0 = time.monotonic()
        jax.profiler.start_trace(logdir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        files = []
        for root, _dirs, names in os.walk(logdir):
            files.extend(os.path.relpath(os.path.join(root, n), logdir)
                         for n in names)
        return {"logdir": logdir, "seconds": round(
            time.monotonic() - t0, 3), "files": sorted(files)}
    except Exception as e:  # noqa: BLE001 — backend may not support it
        return {"error": f"{type(e).__name__}: {e}"}
    finally:
        with _lock:
            _trace_busy = False


# -- status / reset ----------------------------------------------------------


def status(touch_backend: bool = False) -> dict:
    """The full device plane in one dict (admin endpoint / madmin /
    bench extra payload)."""
    with _lock:
        host = dict(_host_buf)
    return {
        "enabled": enabled(),
        "ledger": ledger_snapshot(),
        "ledger_balanced": ledger_balanced(),
        "host_bufpool": host,
        "compile": compile_snapshot(),
        "roofline": roofline_snapshot(),
        "device_memory": device_memory(touch=touch_backend),
    }


def reset() -> None:
    """Test hook: forget everything (per-wrapper ``_seen`` signature
    caches are deliberately kept — an already-compiled kernel will not
    recompile, so it must not recount)."""
    global _compiles_total, _compile_seconds_total, _storms_total, \
        _last_storm_mono
    with _lock:
        _compiles.clear()
        _compiles_total = 0
        _compile_seconds_total = 0.0
        _storm_times.clear()
        _storms_total = 0
        _last_storm_mono = 0.0
        for ln in _LANES:
            _ledgers[ln] = _LaneLedger()
        _device_time.clear()
        for k in _host_buf:
            _host_buf[k] = 0
