"""The stage boundary: where a request's time goes, charged to the
request's own collector on three clocks.

A ``StageTimes`` collector rides a contextvar for one unit of work: an
S3 request from the socket to the reply (armed by ``S3Handler._handle``
through ``obs/attribution.py``), an object operation or a heal that no
request is around (``attribution.observed``), or a test's own
(``collect``). ``timed(st, stage)`` / ``stage(name)`` is the ONE
boundary: a slotted context manager that, while a collector is armed,
charges to the named stage

* **wall seconds** (``time.monotonic``): how long the stage stood
  between the request and its reply;
* **thread CPU seconds** (``time.thread_time``): the interpreter time the
  stage took on this thread, plus what a native call burns with the
  interpreter lock released. Under one interpreter lock this, not the
  wall, is what a request takes away from every other request. Where a
  read of that clock is dear (``cpu_stride``) one unit of work in N reads
  it and the switches, and its record says so (``sampled``);
* a **count**, and around a pool task (and at a unit's two ends) the
  thread's **voluntary context switches**
  (``getrusage(RUSAGE_THREAD).ru_nvcsw``): every time the thread gave the
  processor up to wait for the interpreter lock, a file or a socket.
  ``(wall - cpu) / switches`` is the price of a turn where it is paid.

The same enter/exit puts the stage on the profiler's clock (a
``jax.profiler.TraceAnnotation("<api>/<stage>")`` while a profiler
session is up, one flag test while none is) and into ``_open``, the
per-thread registry the sampler (obs/profiler.py) reads to fold its
samples by (op, stage, function).

Nested boundaries on one thread charge SELF time: a stage's seconds are
its own less its children's, so the stages of a request's thread and
``other`` (the request less every stage) add up to the request's wall.
Charges from another thread than the collector's (pool tasks wrapped by
``spans.wrap_ctx`` as ``<stage>.pool``, a writer's ``shard_write``, the
dispatcher's ``queue_wait``) and direct ``add`` calls overlap the
request's thread and are kept aside of that sum.

Stage names. Front end (server/s3api.py): ``head``, ``admit``,
``route``, ``auth``, ``respond``, ``drain``, ``epilogue``. Object layer:
``bucket_check``, ``ns_lock``, ``meta_pass``, ``commit``, ``delete``.
Data path (erasure/streaming.py, crypto/sse.py): ``body_read``, ``etag``,
``encode_hash``, ``shard_write``, ``shard_read``, ``decode``,
``write_out``, ``rebuild``, ``sse_seal``, ``sse_open``. From the
dispatcher and the device plane, aside: ``queue_wait``, ``dev_flush``,
``readback``, ``compile``.

Off (``timeline.enable=0``): no collector is armed, and a boundary is one
module-level bool (``stage``) or one ``None`` test (``timed``).
"""
from __future__ import annotations

import contextlib
import contextvars
import resource
import threading
import time

from . import timeline as _tl

_current: contextvars.ContextVar = contextvars.ContextVar(
    "minio_tpu_stage_times", default=None)

#: thread ident -> innermost open boundary on that thread (nesting, the
#: name of a pool task, the sampler's stage tag)
_open: dict[int, "_Stage"] = {}

# the clocks, by module-level names so that a test can count their reads
_get_ident = threading.get_ident
_monotonic = time.monotonic
_thread_time = time.thread_time
_Annotation = None


#: does this host count context switches at all? (a sandboxed kernel may
#: answer 0 for ever: then the call is not made again). None = not asked
_counts_switches: bool | None = None
#: every how many units of work read the CPU clock; 0 = not calibrated
_stride = 0
#: a CPU-clock read that costs more than this is sampled, not read always
CPU_READ_BUDGET_NS = 400


def switches() -> int:
    """Voluntary context switches of the calling thread so far (0 on a
    host that does not count them)."""
    global _counts_switches
    if _counts_switches is None:
        # by now the process has waited for something, if only its imports
        _counts_switches = resource.getrusage(
            resource.RUSAGE_SELF).ru_nvcsw > 0
    if not _counts_switches:
        return 0
    return resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw


def cpu_stride() -> int:
    """Every how many units of work read the CPU clock and the switches,
    from what one read of ``time.thread_time`` costs HERE: a plain Linux
    host answers in ~0.3 us and every unit reads them; under gVisor (the
    chip's host: 5.9 us a read alone, 12-50 us beside other threads, the
    interpreter lock held meanwhile) one unit in ~15 does, which keeps
    the reads near CPU_READ_BUDGET_NS a boundary in the mean. The wall
    clock, the count and the record are taken for every unit."""
    global _stride
    if not _stride:
        costs = []
        for _ in range(5):
            t0 = time.perf_counter_ns()
            for _ in range(8):
                time.thread_time()
            costs.append((time.perf_counter_ns() - t0) / 8)
        _stride = max(1, min(64, round(sorted(costs)[2]
                                       / CPU_READ_BUDGET_NS)))
    return _stride


def _tracing() -> bool:
    """Is a profiler session up? Resolved at the first armed boundary
    (this module imports without jax), then ``TraceMe``'s own flag
    test."""
    global _tracing, _Annotation
    try:
        from jax.profiler import TraceAnnotation
        _Annotation, _tracing = TraceAnnotation, TraceAnnotation.is_enabled
    except Exception:  # noqa: BLE001 — no jax, no profiler's clock
        _tracing = bool
    return _tracing()


class StageTimes:
    """One unit of work's charges. ``own``: boundaries on the collector's
    own thread (self time; they and ``other`` add up to the wall).
    ``aside``: every other charge. Both map a stage to ``[wall_s, cpu_s,
    count, switches]``; a lost update under concurrent charges skews
    attribution, never correctness. ``parent`` chains collectors: the
    object layer's ``attribution.observed`` arms one INSIDE the
    request's, and every charge flows to both."""

    __slots__ = ("own", "aside", "parent", "tid", "api", "sampled",
                 "object_bytes")

    def __init__(self, parent: "StageTimes | None" = None, api: str = "",
                 sampled: bool = True):
        self.own: dict[str, list] = {}
        self.aside: dict[str, list] = {}
        self.parent = parent
        self.tid = _get_ident()
        self.api = api or (parent.api if parent is not None else "")
        #: do this unit's boundaries read the CPU clock and the switches?
        #: (``cpu_stride``; a chained collector does as its parent does)
        self.sampled = sampled if parent is None else parent.sampled
        #: size of the object the unit read, wrote, statted or removed
        #: (``touched``); -1 while nobody has said
        self.object_bytes = -1

    def _charge(self, stage: str, wall: float, cpu: float, sw: int,
                tid: int) -> None:
        st = self
        while st is not None:
            d = st.own if tid == st.tid else st.aside
            e = d.get(stage)
            if e is None:
                d[stage] = [wall, cpu, 1, sw]
            else:
                e[0] += wall
                e[1] += cpu
                e[2] += 1
                e[3] += sw
            st = st.parent

    def add(self, stage: str, dt: float, cpu: float = 0.0) -> None:
        """A charge no boundary measured (the native call's own split of
        its time, the dispatcher's wait for a flush): kept aside."""
        self._charge(stage, dt, cpu, 0, 0)

    def _merged(self, i: int) -> dict:
        out = {k: v[i] for k, v in dict(self.aside).items()}
        for k, v in dict(self.own).items():
            out[k] = out.get(k, 0) + v[i]
        return out

    @property
    def seconds(self) -> dict[str, float]:
        """Wall seconds a stage, every charge."""
        return self._merged(0)

    @property
    def counts(self) -> dict[str, int]:
        return self._merged(2)

    def snapshot(self) -> dict[str, float]:
        return {k: round(v, 6) for k, v in sorted(self.seconds.items())}


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def split(self, stage: str, share: float) -> None:
        pass


_NOOP = _Noop()


class _Stage:
    """The boundary (see the module's docstring). One use, one thread."""

    __slots__ = ("st", "name", "sw", "up", "ann", "t0", "c0", "s0",
                 "kid_wall", "kid_cpu", "part")

    def __init__(self, st: StageTimes, name: str, sw: bool = False):
        self.st, self.name, self.sw, self.part = st, name, sw, None

    def __enter__(self):
        tid = _get_ident()
        self.up = _open.get(tid)
        _open[tid] = self
        self.kid_wall = self.kid_cpu = 0.0
        if _tracing():
            self.ann = _Annotation(self.st.api + "/" + self.name)
            self.ann.__enter__()
        else:
            self.ann = None
        if self.st.sampled:
            self.s0 = switches() if self.sw else 0
            self.c0 = _thread_time()
        self.t0 = _monotonic()
        return self

    def split(self, stage: str, share: float) -> None:
        """Hand ``share`` of this boundary's time to ``stage`` when it
        closes (one native call that says how its time was spent)."""
        self.part = (stage, min(1.0, max(0.0, share)))

    def __exit__(self, *exc):
        wall = _monotonic() - self.t0
        if self.st.sampled:
            cpu = _thread_time() - self.c0
            sw = switches() - self.s0 if self.sw else 0
        else:
            cpu, sw = 0.0, 0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        tid = _get_ident()
        up = self.up
        if up is None:
            _open.pop(tid, None)
        else:
            _open[tid] = up
            up.kid_wall += wall
            up.kid_cpu += cpu
        wall -= self.kid_wall
        cpu -= self.kid_cpu
        if self.part is not None:
            other, share = self.part
            self.st._charge(other, wall * share, cpu * share, 0, tid)
            wall, cpu = wall * (1.0 - share), cpu * (1.0 - share)
        self.st._charge(self.name, wall, cpu, sw, tid)
        return False


def active() -> StageTimes | None:
    """The armed collector, or None (the common, zero-cost case)."""
    return _current.get()


def touched(nbytes: int) -> None:
    """The object layer says how large the object is that the armed unit
    of work reads, writes, stats or removes (a part PUT: the part): kept
    on the unit's collector and on those it chains into, so that a
    request's record can be told apart by the size of its object, where
    its ``bytes`` (consumed + sent) are ~0 for a STAT and a DELETE."""
    if not _tl._enabled:
        return
    st = _current.get()
    while st is not None:
        st.object_bytes = nbytes
        st = st.parent


def open_stage(tid: int) -> tuple[str, str] | None:
    """(api, stage) of the innermost boundary open on thread ``tid``:
    the sampler's view, cross-thread."""
    s = _open.get(tid)
    return None if s is None else (s.st.api, s.name)


@contextlib.contextmanager
def collect(st: StageTimes | None = None):
    """Arm ``st`` (or a fresh collector) for the with-body; yields it."""
    st = st or StageTimes()
    tok = _current.set(st)
    try:
        yield st
    finally:
        _current.reset(tok)


def timed(st: StageTimes | None, stage: str):
    """The boundary over ``st`` as the caller holds it (a hot loop reads
    ``active()`` once); free when it holds None."""
    return _NOOP if st is None else _Stage(st, stage)


def stage(name: str):
    """The boundary over the armed collector, looked up here."""
    if not _tl._enabled:
        return _NOOP
    st = _current.get()
    return _NOOP if st is None else _Stage(st, name)


def pool_task(name: str = ""):
    """The boundary around a task handed to a pool while a collector is
    armed (``spans.wrap_ctx`` calls this on the submitting thread and
    enters it on the worker): ``<name, or the stage open at the
    submit>.pool``, with the worker's switches. None when nothing is
    armed."""
    if not _tl._enabled:
        return None
    st = _current.get()
    if st is None:
        return None
    if not name:
        up = _open.get(_get_ident())
        name = "task" if up is None else up.name
    return _Stage(st, name if name.endswith(".pool") else name + ".pool",
                  True)


def pooled(fn, name: str):
    """``fn`` as a pool task of stage ``name`` (``<name>.pool``) for a
    submit that needs no context on the worker; ``fn`` itself when
    nothing is armed."""
    task = pool_task(name)
    if task is None:
        return fn

    def run(*a, **kw):
        with task:
            return fn(*a, **kw)
    return run
