"""What the readers of the program's own request records share.

The program keeps one record a finished request (and a finished heal or
object operation that no request was around) in memory, with the wall and
thread CPU seconds, the count and the voluntary switches of each of its
stages (``minio_tpu.obs.attribution``). The server runs in this process and
the window's ``t_start`` / ``t_end`` are on the records' clock
(``time.monotonic``), so a window is read after the run with
``attribution.between``: no snapshot at its edges. A program without such
records, or a ring that has turned over past the window's start, gives
nothing to read (and the reason is said, once).

A record: ``api``, ``id`` (the request id; '' for a unit that is no
request), ``sampled`` (did it read the CPU clock: see ``sampled`` below),
``wall_s``, ``cpu_s`` and ``switches`` of its own thread,
``stages`` {stage: [wall_s, cpu_s, count, switches]} of that thread (self
times; ``other`` is the rest of the request) and ``pool`` (the same for
what ran beside it: pool tasks, the dispatcher's waits).
"""
from __future__ import annotations

import glob
import os
import tempfile

from served import say

#: stages of a request that are the front end's: the head, admission,
#: routing, SigV4 and policy, the reply, the unread body, the request's own
#: observability, and what no stage of the layers below covers
FRONTEND = ("head", "admit", "route", "auth", "respond", "drain",
            "epilogue", "other")
#: records of the server's other planes, which no client of a cell drives
NOT_S3 = ("admin", "internal")

_cache: dict = {}


def records(run: dict) -> list[dict] | None:
    """Every top-level record that ended in the window, oldest first."""
    w = run["window"]
    key = (w["t_start"], w["t_end"])
    if key not in _cache:
        _cache.clear()
        _cache[key] = _read(*key)
    return _cache[key]


def _read(t_start: float, t_end: float) -> list[dict] | None:
    try:
        from minio_tpu.obs import attribution
        between = attribution.between
    except (ImportError, AttributeError):
        say("request_stages: the program keeps no request records")
        return None
    recs = between(t_start, t_end)
    if recs is None:
        say("request_stages: the ring of records has turned over past the "
            f"window's start ({attribution.overwritten()} overwritten); "
            "nothing to read")
    elif not recs:
        say("request_stages: no record ended in the window")
        return None
    return recs


def s3(run: dict) -> list[dict] | None:
    """The window's records of S3 requests."""
    recs = records(run)
    if recs is None:
        return None
    return [r for r in recs if r["id"] and r["api"] not in NOT_S3] or None


def sampled(recs: list[dict]) -> list[dict]:
    """The records that read the CPU clock and the switches: all of them
    where a read is cheap, one in N where it is dear (the program's
    ``stages.cpu_stride``; the chip's host: one in ~15). The others carry
    wall seconds and counts alone."""
    return [r for r in recs if r.get("sampled", True)]


def cpu_s(rec: dict) -> float:
    """CPU seconds of a record: its own thread's and its pool tasks'."""
    return rec["cpu_s"] + sum(v[1] for v in rec["pool"].values())


def cpu_total_s(recs: list[dict]) -> dict[str, float]:
    """API -> CPU seconds of ALL its records, estimated as the mean of its
    sampled records times its records."""
    out = {}
    for api in {r["api"] for r in recs}:
        mine = [r for r in recs if r["api"] == api]
        read = sampled(mine)
        if read:
            out[api] = sum(cpu_s(r) for r in read) / len(read) * len(mine)
    return out


def turns(rec: dict) -> int:
    """Voluntary switches of a record's thread and of its pool tasks."""
    return rec["switches"] + sum(v[3] for v in rec["pool"].values())


def stage_cpu_s(recs: list[dict], names: tuple[str, ...]) -> float:
    return sum(r["stages"].get(n, (0.0, 0.0))[1] for r in recs
               for n in names)


def table(recs: list[dict]) -> dict:
    """API -> {'n', 'n_cpu', 'wall_ms', 'cpu_ms', 'turns', stage -> [wall
    ms, CPU ms, count, switches] an operation}: the breakdown PERF.md
    prints. Wall and counts are means over the API's ``n`` records, CPU and
    switches over the ``n_cpu`` of them that read those clocks."""
    out: dict = {}
    for r in recs:
        t = out.setdefault(r["api"], {"n": 0, "n_cpu": 0, "wall_ms": 0.0,
                                      "cpu_ms": 0.0, "turns": 0,
                                      "stages": {}})
        read = r.get("sampled", True)
        t["n"] += 1
        t["n_cpu"] += read
        t["wall_ms"] += r["wall_s"] * 1e3
        t["cpu_ms"] += cpu_s(r) * 1e3
        t["turns"] += turns(r)
        for name, v in (*r["stages"].items(), *r["pool"].items()):
            e = t["stages"].setdefault(name, [0.0, 0.0, 0, 0])
            e[0] += v[0] * 1e3
            e[1] += v[1] * 1e3
            e[2] += v[2]
            e[3] += v[3]
    for t in out.values():
        n, n_cpu = t["n"], max(1, t["n_cpu"])
        t["wall_ms"] = round(t["wall_ms"] / n, 3)
        t["cpu_ms"] = round(t["cpu_ms"] / n_cpu, 3)
        t["turns"] = round(t["turns"] / n_cpu, 3)
        t["stages"] = {k: [round(v[0] / n, 3), round(v[1] / n_cpu, 3),
                           round(v[2] / n, 3), round(v[3] / n_cpu, 3)]
                       for k, v in sorted(t["stages"].items(),
                                          key=lambda kv: -kv[1][1])}
    return out


def trace_file() -> str | None:
    """The run's one ``.xplane.pb``: it lies under the run's scratch root
    (``served.scratch_root``) until the readers are done. None, with the
    reason said, when there is none or more than one."""
    base = os.environ.get("MINIO_TPU_BENCH_DIR") or tempfile.gettempdir()
    files = glob.glob(os.path.join(base, "bench-drives-*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        say(f"request_stages: expected one .xplane.pb under {base}/"
            f"bench-drives-*/trace, found {len(files)}")
        return None
    return files[0]
