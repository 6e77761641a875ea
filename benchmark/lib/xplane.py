"""The profiler's trace of a steady sub-window, and its reduction to
numbers. The trace is read with ``jax.profiler.ProfileData`` only.

The sub-window is bounded on the trace's own clock by two marker launches
of this module (``bench_trace_marker``, one small jitted add each): the
first right after the profiler starts, the second right before it stops.
They also prove that the device plane was being recorded: a trace without
both markers is an error, never an idle device."""
from __future__ import annotations

import glob
import os
import re
import time

MARKER = "bench_trace_marker"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


class Tracer:
    def __init__(self, logdir: str, seconds: float, snapshot):
        """``snapshot()`` is called as the sub-window opens and as it
        closes; the reduction carries both results (``at0``, ``at1``)."""
        import jax
        import jax.numpy as jnp
        self.logdir, self.seconds, self.snapshot = logdir, seconds, snapshot
        self.at0 = self.at1 = None

        def bench_trace_marker(x):
            return x + 1
        self._marker = jax.jit(bench_trace_marker)
        self._x = jnp.zeros((8, 128), jnp.int32)
        self._marker(self._x).block_until_ready()   # compiled in set-up
        self.t0 = self.t1 = 0.0

    def during(self, t_start: float, t_end: float) -> None:
        """Called by the window's sleeping thread: hold the profiler over
        the window's first ``seconds``, from just before ``t_start`` (the
        router sends most of what it sends to the chip right after the
        quiet before the window, while its link profile is an idle one),
        and stop two seconds before ``t_end`` at the latest."""
        import jax
        span = max(0.1, min(self.seconds, (t_end - t_start) - 2.0))
        time.sleep(max(0.0, t_start - 0.8 - time.monotonic()))
        # device and host planes only: the Python tracer would slow the
        # server it looks at
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self._marker(self._x).block_until_ready()
        self.t0, self.at0 = time.monotonic(), self.snapshot()
        time.sleep(span)
        self.t1 = time.monotonic()
        self._marker(self._x).block_until_ready()
        jax.profiler.stop_trace()
        self.at1 = self.snapshot()

    def reduce(self, keep: str = "") -> dict:
        files = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb under "
                               f"{self.logdir}, found {files}")
        if keep:
            import shutil
            os.makedirs(keep, exist_ok=True)
            shutil.copy(files[0], keep)
        out = reduce_file(files[0])
        out.update(t0=self.t0, t1=self.t1, at0=self.at0, at1=self.at1)
        return out


def _union(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def device_planes(path: str) -> list[dict]:
    """Per device plane: {'name', 'ops': [(name, start_s, end_s)],
    'modules': [...]}, times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events]
        planes.append({"name": plane.name,
                       "ops": lines.get(OPS_LINE, []),
                       "modules": lines.get(MODULES_LINE, []),
                       "lines": {k: len(v) for k, v in lines.items()}})
    return planes


def short_op(name: str) -> str:
    """'%while.7 = (s32[]...) while(...' -> '%while.7 while': the trace
    names an operation by its whole HLO line."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"[\]})]\s([a-z][\w\-]*)\(", rest)
    return (head + (" " + m.group(1) if m else ""))[:80]


def reduce_file(path: str) -> dict:
    """busy_s and window_s (averaged over the device planes that ran
    anything), seconds per program (XLA module) and per operation, and the
    longest idle gaps, all inside the two markers."""
    planes = [p for p in device_planes(path) if p["modules"] or p["ops"]]
    if not planes:
        raise RuntimeError(f"{path}: no device plane with events; the "
                           "profiler recorded nothing of the chip")
    busy, span, program_s, op_s, gaps = [], [], {}, {}, []
    for p in planes:
        marks = sorted((a, b) for name, a, b in p["modules"]
                       if MARKER in name)
        if len(marks) < 2:
            raise RuntimeError(f"{path}: plane {p['name']} holds "
                               f"{len(marks)} of the 2 trace markers")
        lo, hi = marks[0][0], marks[-1][1]
        inside = lambda evs: [(n, max(a, lo), min(b, hi))  # noqa: E731
                              for n, a, b in evs if b > lo and a < hi]
        ops = inside(p["ops"] or p["modules"])
        busy.append(_union([(a, b) for _, a, b in ops]))
        span.append(hi - lo)
        for n, a, b in inside(p["modules"]):
            key = n.split("(")[0]
            program_s[key] = program_s.get(key, 0.0) + (b - a)
        for n, a, b in ops:
            n = short_op(n)
            op_s[n] = op_s.get(n, 0.0) + (b - a)
        edges = sorted((a, b) for _, a, b in ops)
        end = lo
        for a, b in edges:
            if a > end:
                gaps.append(a - end)
            end = max(end, b)
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy), "window_s": sum(span) / len(span),
        "program_s": program_s,
        "breakdown": {
            "device_ops": top(op_s),
            # the host side of a gap is not attributed yet: no host span of
            # the program is on the profiler's clock
            "idle_gaps": [["unattributed", g]
                          for g in sorted(gaps, reverse=True)[:10]]},
        "planes": [{"name": p["name"], "lines": p["lines"]}
                   for p in planes]}
