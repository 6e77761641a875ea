#!/usr/bin/env python
"""Drive one run of the benchmark on the CPU backend: everything of run.py
but its look for a chip. For the rehearsal tests only; its numbers are the
CPU's and are never reported.

    JAX_PLATFORMS=cpu python cpu_run.py <checkout> <run.py's arguments>
                                        [--break get-byte|heal-noop|fence]

``--break`` breaks the timed path underneath the harness before the run, to
show that ``correct`` comes out false; ``fence`` breaks nothing of the program
(a host's standstill, stood in for) and has to leave it true."""
from __future__ import annotations

import os
import sys
import time


def break_get_byte() -> None:
    """Every 4th GET the object layer serves has the first byte of its body
    altered where it is produced."""
    from minio_tpu.objectlayer.erasure_objects import ErasureObjects
    orig, calls = ErasureObjects.get_object, [0]

    class Flip:
        def __init__(self, w):
            self.w, self.done = w, False

        def write(self, b):
            if not self.done and len(b):
                b = bytes([b[0] ^ 1]) + bytes(b[1:])
                self.done = True
            return self.w.write(b)

        def flush(self):
            return self.w.flush()

    def get_object(self, bucket, key, writer, *a, **kw):
        calls[0] += 1
        if calls[0] % 4 == 0:
            writer = Flip(writer)
        return orig(self, bucket, key, writer, *a, **kw)
    ErasureObjects.get_object = get_object


def break_heal_noop() -> None:
    """heal_object returns 'all ok' and leaves its state unchanged."""
    import types
    from minio_tpu.objectlayer.erasure_objects import ErasureObjects

    def heal_object(self, bucket, key, *a, **kw):
        ok = ["ok"] * len(self.disks)
        return types.SimpleNamespace(before_state=ok, after_state=ok)
    ErasureObjects.heal_object = heal_object


def fence_two_drives() -> None:
    """No break of the program: what a host that stands still does to it.
    Half-way through the window two drives' health trackers are given four
    timeouts in a row: they are fenced for their cool-down and PUTs
    meanwhile are acknowledged on the drives that are left. The run has to
    come out correct all the same (``served.whole``)."""
    import threading

    import run
    orig = run.Ctx.timed

    def timed(self, make_plans, seconds):
        def trip():
            for d in self.served.obj.disks[:2]:
                for _ in range(4):
                    d._record(False, 3.0, True)
        threading.Timer(self.mix.get("lead_s", 0.5) + 0.5 * seconds,
                        trip).start()
        return orig(self, make_plans, seconds)
    run.Ctx.timed = timed


BREAKS = {"get-byte": break_get_byte, "heal-noop": break_heal_noop,
          "fence": fence_two_drives}


class FakeTracer:
    """A CPU run has no device plane to trace: the per-layer readers get a
    canned reduction, so that their plumbing is rehearsed and nothing
    else."""

    def __init__(self, logdir, seconds, snapshot):
        self.t0 = self.t1 = 0.0
        self.snap = snapshot

    def during(self, t_start, t_end):
        self.t0, self.t1 = t_start, t_end

    def reduce(self, keep=""):
        return {"busy_s": 0.001, "window_s": 1.0, "program_s": {},
                "t0": self.t0, "t1": self.t1,
                "at0": self.snap(), "at1": self.snap(),
                "breakdown": {"device_ops": [], "idle_gaps": []}}


def main() -> int:
    root, argv = os.path.abspath(sys.argv[1]), sys.argv[2:]
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import run
    import xplane
    xplane.Tracer = FakeTracer
    if "--break" in argv:
        i = argv.index("--break")
        BREAKS[argv[i + 1]]()
        del argv[i: i + 2]
    args = run.arg_parser().parse_args(argv)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, cfg, mix = run.resolve(bench, args.workload)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run.run_cell(bench, cell, cfg, mix, args, device,
                        time.monotonic())


if __name__ == "__main__":
    sys.exit(main())
