"""Share of the traced sub-window's idle-gap seconds that a host stage of
the program covers in the same ``.xplane.pb``, in %: the stage boundary
(``minio_tpu/obs/stages.py``) opens a ``TraceAnnotation("<api>/<stage>")``
while the profiler is up, so a stage lies on the host plane on the clock of
the device's operations. A gap is named by the stage that covers most of it
(the union of that stage's events over every thread); the ``GAPS`` line lists
the ten longest gaps with their three longest covers. ``breakdown.idle_gaps`` of
the result line is ``lib/xplane.py``'s and keeps its label."""
import re

import request_stages
import xplane
from served import say

#: '<api>/<stage>' as the boundary names it: 'getobject/meta_pass',
#: 'heal.object/shard_read', 'putobject/commit.pool'
STAGE = re.compile(r"[a-z0-9.]+/[a-z_]+(\.pool)?")


def host_stages(path: str) -> list[tuple[str, float, float]]:
    """(name, start_s, end_s) of every '<api>/<stage>' event of the host
    planes, on the trace's clock."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if STAGE.fullmatch(ev.name):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def idle_gaps(path: str) -> list[tuple[float, float]]:
    """The device planes' idle gaps between the two markers, as
    ``xplane.reduce_file`` cuts them."""
    gaps = []
    for p in xplane.device_planes(path):
        marks = sorted((a, b) for name, a, b in p["modules"]
                       if xplane.MARKER in name)
        if len(marks) < 2:
            continue
        lo, hi = marks[0][0], marks[-1][1]
        end = lo
        for a, b in sorted((max(a, lo), min(b, hi))
                           for _, a, b in (p["ops"] or p["modules"])
                           if b > lo and a < hi):
            if a > end:
                gaps.append((end, a))
            end = max(end, b)
    return gaps


def name_gaps(gaps, stages) -> list[tuple[float, list, float]]:
    """Per gap (seconds, [[stage, seconds it covers], ...] longest first,
    seconds some stage covers). Threads run side by side, so a stage's
    cover is the union of its events, not their sum; the first names the
    gap ('unattributed' when none covers any of it)."""
    stages = sorted(stages, key=lambda s: s[1])
    out = []
    for lo, hi in gaps:
        by_name: dict[str, list] = {}
        for name, a, b in stages:
            if a >= hi:
                break
            if b > lo:
                by_name.setdefault(name, []).append((max(a, lo), min(b, hi)))
        covers = sorted(([n, xplane._union(v)] for n, v in by_name.items()),
                        key=lambda nv: -nv[1])
        out.append((hi - lo, covers or [["unattributed", 0.0]],
                    xplane._union([iv for v in by_name.values()
                                   for iv in v])))
    return out


def read(run):
    if run["trace"] is None:
        return None
    path = request_stages.trace_file()
    if path is None:
        return None
    stages = host_stages(path)
    if not stages:
        say("device.idle_named_share: no stage of the program on the host "
            "planes of the trace; nothing to read")
        return None
    named = name_gaps(idle_gaps(path), stages)
    idle = sum(g for g, _, _ in named)
    if not idle:
        return None
    covered = sum(c for _, _, c in named)
    say("GAPS (seconds, the three longest covers) " + str(
        [[round(g, 4), [[n, round(c, 4)] for n, c in covers[:3]]]
         for g, covers, _ in sorted(named, key=lambda t: -t[0])[:10]]))
    say(f"device.idle_named_share: {covered:.3f} s of {idle:.3f} idle-gap "
        f"seconds under {len(stages)} host stage events")
    return 100.0 * covered / idle
