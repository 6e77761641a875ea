"""The request's record: where every finished unit of work spent its
time, kept in memory and read when a run ends.

``obs/stages.py`` is the boundary; this module arms a collector a unit of
work and keeps what it collected:

* an S3 request, from the socket to the reply: ``begin`` / ``finish``
  (``S3Handler._handle``), API name as ``_api_name()`` gives it
  (``getobject``, ``headobject``, ``putobjectpart``...), id = the
  ``x-amz-request-id``;
* an object operation or a heal: ``observed("put" | "get" | "heal.object")``
  (objectlayer/). Inside a request it chains into the request's collector
  (``StageTimes.parent``) and its record is marked ``nested``; with no
  request around (the MRF healer, a heal sequence, library use) it is a
  record of its own.

One record a finished unit: end time on ``time.monotonic``, id, API,
status, bytes, wall and thread CPU seconds and voluntary switches of its
own thread, ``object_bytes`` (the size of the object it read, wrote,
statted or removed, as the object layer told ``stages.touched``; -1 where
there was none), and a stage its ``[wall_s, cpu_s, count, switches]``, the
stages of its own thread (``stages``, self times, ``other`` = the rest)
apart from what ran beside it (``pool``); ``sampled`` says whether the
unit read the CPU clock and the switches at all (``stages.cpu_stride``:
every unit where the read is cheap, one in N where it is dear). Records live in a ring of
``RING`` (the oldest overwritten and counted); ``between(t0, t1)`` hands
back the top-level records that ended in a span of monotonic time, which
is how the benchmark reads a window with no snapshot at its edges.

The standing report (``report()``, ``?attribution=1`` on the metrics and
admin timeline endpoints) comes from the same records: cumulative sums a
(API, stage) since the start, percentiles over the records of the last
minute. Rides the flight recorder's switch (``timeline.enable``).
"""
from __future__ import annotations

import contextlib
import threading
import time
from array import array

from . import stages as _stages
from . import timeline as _tl

#: records kept; a benchmark window is 5,000-7,500 requests and the
#: checks after it a few thousand more
RING = 65536
#: a report's percentiles look this far back
WINDOW_S = 60.0

_lock = threading.Lock()
_ring: list = [None] * RING
_n = 0                       # records ever written (slot = _n % RING)
_units = 0                   # units ever begun (which of them read the CPU clock)
#: (api, stage) -> [wall_s, cpu_s, count, switches] since the start;
#: stage "" is the unit itself (its wall, its own thread's CPU, count)
_cum: dict[tuple[str, str], list] = {}
#: interned (stage names of the own thread, stage names aside)
_names: dict[tuple, tuple] = {}


def enabled() -> bool:
    """Attribution rides the flight recorder's enable switch — one
    subsystem (`timeline`) turns the whole observability tentpole on or
    off."""
    return _tl.enabled()


def overwritten() -> int:
    """Records the ring has dropped to make room."""
    return max(0, _n - RING)


def _keep(api: str, rid: str, status: int, nbytes: int, nested: bool,
          t_end: float, wall: float, cpu: float, sw: int,
          st: _stages.StageTimes) -> None:
    """One finished unit into the ring and the cumulative sums. A unit
    that read the CPU clock stands for ``cpu_stride()`` units in the
    sums' CPU seconds and switches (the others read neither), so the
    totals stay estimates of the whole; the ring keeps what was read."""
    global _n
    own, aside = dict(st.own), dict(st.aside)
    names = (tuple(own), tuple(aside))
    vals = array("d")
    for d in (own, aside):
        for v in d.values():
            vals.extend(v)
    k = _stages.cpu_stride() if st.sampled else 0
    with _lock:
        names = _names.setdefault(names, names)
        _ring[_n % RING] = (t_end, rid, api, status, nbytes, nested, wall,
                            cpu, sw, names, vals, st.sampled,
                            st.object_bytes)
        _n += 1
        for stage, v in (("", (wall, cpu, 1, sw)), *own.items(),
                         *aside.items()):
            e = _cum.get((api, stage))
            if e is None:
                e = _cum[(api, stage)] = [0.0, 0.0, 0, 0]
            e[0] += v[0]
            e[1] += v[1] * k
            e[2] += v[2]
            e[3] += v[3] * k


def _expand(rec: tuple) -> dict:
    (t_end, rid, api, status, nbytes, nested, wall, cpu, sw,
     (own, aside), vals, sampled, object_bytes) = rec
    rows = [[vals[i], vals[i + 1], int(vals[i + 2]), int(vals[i + 3])]
            for i in range(0, len(vals), 4)]
    return {"t_end": t_end, "id": rid, "api": api, "status": status,
            "bytes": nbytes, "object_bytes": object_bytes,
            "nested": nested, "wall_s": wall,
            "cpu_s": cpu, "switches": sw, "sampled": sampled,
            "stages": dict(zip(own, rows)),
            "pool": dict(zip(aside, rows[len(own):]))}


def _records() -> list[tuple]:
    with _lock:
        if _n <= RING:
            return _ring[:_n]
        i = _n % RING
        return _ring[i:] + _ring[:i]


def between(t0: float, t1: float) -> list[dict] | None:
    """The top-level records that ended in [t0, t1) of ``time.monotonic``,
    oldest first; None when the ring has turned over past ``t0`` (a part
    of a span is never handed back). A record's ``stages`` are those of
    its own thread, ``other`` among them; ``pool`` is what ran beside."""
    recs = _records()
    if overwritten() and recs and recs[0][0] >= t0:
        return None
    out = []
    for rec in recs:
        if t0 <= rec[0] < t1 and not rec[5]:
            r = _expand(rec)
            spent = [sum(v[i] for v in r["stages"].values())
                     for i in (0, 1)]
            r["stages"]["other"] = [r["wall_s"] - spent[0],
                                    r["cpu_s"] - spent[1], 1, 0]
            out.append(r)
    return out


class _Unit:
    """A unit of work being collected (``begin`` .. ``finish``)."""

    __slots__ = ("rid", "api", "st", "tok", "t0", "c0", "s0", "nested")

    def __init__(self, rid: str, api: str, start: tuple | None = None):
        outer = _stages.active()
        self.rid, self.api, self.nested = rid, api, outer is not None
        # inside a request a unit reads the clocks its request reads
        head, start = start is not None, start or mark(
            None if outer is None else outer.sampled)
        # inside a request the stages go by the request's API name on
        # the profiler's clock and in the sampler's tag
        self.st = st = _stages.StageTimes(
            parent=outer, api=outer.api or api if self.nested else api,
            sampled=start[3])
        self.tok = _stages._current.set(st)
        if head:
            st._charge("head", _stages._monotonic() - start[0],
                       _stages._thread_time() - start[1] if st.sampled else 0.0,
                       0, st.tid)
        self.t0, self.c0, self.s0 = start[:3]


def mark(sampled: bool | None = None) -> tuple:
    """A unit's first end, read on its thread: (monotonic, thread_time,
    switches, does this unit read the CPU clock: one in
    ``stages.cpu_stride`` does, unless ``sampled`` says)."""
    global _units
    if sampled is None:
        _units += 1
        sampled = not _units % _stages.cpu_stride()
    if not sampled:
        return (_stages._monotonic(), 0.0, 0, False)
    return (_stages._monotonic(), _stages._thread_time(),
            _stages.switches(), True)


def begin(rid: str, api: str, start: tuple | None = None) -> _Unit | None:
    """Arm the collector of one unit of work on this thread; None while
    the switch is off. ``start`` is a ``mark()`` taken earlier on this
    thread, when the unit began before this call (a request's head is
    parsed before its handler runs): the time since is its stage
    ``head``."""
    if not enabled():
        return None
    return _Unit(rid, api, start)


def finish(u: _Unit | None, api: str = "", status: int = 0,
           nbytes: int = 0) -> None:
    """Disarm and keep the record. ``api`` renames the unit (a request
    knows its API only once it is parsed)."""
    if u is None:
        return
    t1 = _stages._monotonic()
    cpu, sw = (_stages._thread_time() - u.c0,
               _stages.switches() - u.s0) if u.st.sampled else (0.0, 0)
    _stages._current.reset(u.tok)
    try:
        _keep(api or u.api, u.rid, status, nbytes, u.nested, t1,
              t1 - u.t0, cpu, sw, u.st)
    except Exception:  # noqa: BLE001 — obs never fails the work
        pass


def record(op: str, st: _stages.StageTimes, wall_s: float) -> None:
    """Keep a collector someone else armed as one finished unit of
    ``wall_s`` seconds (tests, a caller with its own clock)."""
    _keep(op, "", 0, 0, False, _stages._monotonic(), wall_s, 0.0, 0, st)


@contextlib.contextmanager
def observed(op: str):
    """Arm a collector for the with-body and keep its record. A
    collector already armed (the request's) keeps receiving every charge
    via ``StageTimes`` chaining — arming here never starves it."""
    u = begin("", op)
    try:
        yield None if u is None else u.st
    finally:
        finish(u)


def _percentile(values: list[float], q: float) -> float:
    if not values:
        return 0.0
    values = sorted(values)
    return values[min(len(values) - 1, int(q * len(values)))]


def report() -> dict:
    """The standing report: per API (and per object operation ``put`` /
    ``get`` / ``heal.object``) count, wall and CPU seconds and switches in
    total and wall p50 / p99 over the last minute; per stage {p50_s,
    p99_s (last minute), seconds_total, cpu_seconds_total, count,
    switches_total, share_of_wall (cumulative)}."""
    with _lock:
        cum = {k: list(v) for k, v in _cum.items()}
    recent: dict[tuple[str, str], list[float]] = {}
    horizon = time.monotonic() - WINDOW_S
    for rec in _records():
        if rec[0] < horizon:
            continue
        api, names, vals = rec[2], rec[9], rec[10]
        recent.setdefault((api, ""), []).append(rec[6])
        for j, stage in enumerate(names[0] + names[1]):
            recent.setdefault((api, stage), []).append(vals[4 * j])
    out: dict = {}
    for (api, stage), (wall, cpu, n, sw) in sorted(cum.items()):
        vals = recent.get((api, stage), [])
        p50, p99 = _percentile(vals, 0.5), _percentile(vals, 0.99)
        if not stage:
            out[api] = {"count": n, "wall_seconds_total": round(wall, 6),
                        "cpu_seconds_total": round(cpu, 6),
                        "switches_total": sw,
                        "wall_p50_s": round(p50, 6),
                        "wall_p99_s": round(p99, 6), "stages": {}}
            continue
        total = cum[(api, "")][0]
        out[api]["stages"][stage] = {
            "p50_s": round(p50, 6), "p99_s": round(p99, 6),
            "seconds_total": round(wall, 6),
            "cpu_seconds_total": round(cpu, 6),
            "count": n, "switches_total": sw,
            "share_of_wall": round(wall / total, 4) if total else 0.0}
    return out


def reset() -> None:
    """Forget every record and sum (tests, bench isolation)."""
    global _n, _units
    with _lock:
        _ring[:] = [None] * RING
        _n = _units = 0
        _cum.clear()
        _names.clear()
