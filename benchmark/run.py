#!/usr/bin/env python
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one cell of BENCHMARK.json: this process holds the chip and is
the server (the CLI's own single-node launch, as shipped); client processes
(lib/client.py, no JAX, nothing of the program) send the traffic and hand
back one record per operation. Everything a cell is made of is found by
name: workloads[i] -> configs/<config>.json, traffic/<mix>.json, whose
``kind`` names traffic_kinds/<kind>.py; each metric of BENCHMARK.json ->
e2e_metrics/<name>.py or layer_metrics/<name>.py. This file holds no cell,
configuration or metric of its own.

Sequence: set-up (server, native library, bucket, seeded objects, warm-up,
quiet) -> window of --seconds -> checks outside the window -> one JSON line.
It refuses to run off a TPU.
"""
from __future__ import annotations

import argparse
import faulthandler
import importlib.util
import json
import os
import shutil
import sys
import time
import types

WATCHDOG_S = 200.0
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)    # the program, from this checkout
sys.path.insert(0, os.path.join(HERE, "lib"))
sys.path.insert(0, os.path.join(HERE, "kernels"))
sys.dont_write_bytecode = True

import procs  # noqa: E402
import refmodel  # noqa: E402
import served  # noqa: E402
import window  # noqa: E402
from served import say  # noqa: E402


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """benchmark/<directory>/<name>.py, found by the name alone ('.' and
    '-' in a metric's name are kept in the file's name)."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no cell {workload!r} in "
                         f"BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT, cfg_entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, cfg, mix


def metrics_of(bench: dict, group: str, directory: str, cell: str,
               run: dict) -> dict:
    """Every metric of ``group`` this cell reports, each from its own
    reader. A reader that finds nothing to read returns None and the metric
    is left out of the line."""
    out = {}
    for m in bench[group]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = load_module(directory, m["name"]).read(run)
        if value is None:
            say(f"METRIC {m['name']} absent: its reader found nothing to "
                "read")
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Ctx(types.SimpleNamespace):
    """What a traffic kind gets: cfg, mix, seed, control, served (the
    server), pool (the client processes), model (the reference model), and
    the two ways to run plans: ``ctx.pool.run`` at once, ``ctx.timed`` as
    the measured window."""

    edge_reader = None    # a kind's own counters, read at the window's edges

    def timed(self, make_plans, seconds: float) -> list[list[dict]]:
        """The measured window. ``make_plans(t_start, t_end)`` gives one
        plan per client thread, its times counted from the window's start
        (it is called with 0.0 and ``seconds``): the client processes make
        and hash their bodies and say that they are ready, and only then is
        the start fixed, ``lead_s`` from that moment, and sent to them; no
        request goes out between. A kind that set ``ctx.edge_reader`` finds
        what it returned at the window's two edges in ``ctx.edges``. This
        thread only sleeps (and, in a traced run, holds the profiler over a
        steady sub-window)."""
        self.pool.prepare(make_plans(0.0, seconds))
        read = self.edge_reader or (lambda: None)
        self.before, edge0 = served.observe(), read()
        cpu0 = time.process_time()
        t_start = time.monotonic() + self.mix.get("lead_s", 0.5)
        t_end = t_start + seconds
        self.pool.go(t_start)
        if self.tracer is not None:
            self.tracer.during(t_start, t_end)
        time.sleep(max(0.0, t_end - time.monotonic()))
        self.after, self.edges = served.observe(), (edge0, read())
        # the server's own CPU seconds (all threads) over the window: a
        # window that got less work done on the same CPU waited elsewhere
        self.server_cpu_s = time.process_time() - cpu0
        threads = self.pool.finish()
        self.window = {"t_start": t_start, "t_end": t_end,
                       "seconds": seconds, "threads": threads}
        return threads

    @staticmethod
    def first_calls() -> int:
        """First calls (compilations or cache loads) the program has
        counted so far."""
        return served.observe()["compile"]["compiles_total"]

    def quiet(self, still_s: float = 1.0, timeout_s: float = 60.0,
              heals: bool = False) -> None:
        """Wait until the dispatch queue has been idle for ``still_s``:
        warm-up's background heals must not run into the window. With
        ``heals`` also until heal-on-read has worked off its queue (a heal
        parked for a later retry is not waited for)."""
        from minio_tpu.runtime.dispatch import global_queue
        mrf = getattr(self.served.srv, "mrf", None) if heals else None
        last, since = None, time.monotonic()
        deadline = since + timeout_s
        while time.monotonic() < deadline:
            st = global_queue().stats()
            hs = mrf.stats() if mrf is not None else {}
            now = (st["items"], hs.get("healed"), hs.get("failed"))
            waiting = hs.get("queued", 0) - hs.get("retry_pending", 0)
            if now != last or st["queue_depth"] or waiting:
                last, since = now, time.monotonic()
            elif time.monotonic() - since >= still_s:
                return
            time.sleep(0.1)
        say(f"quiet: the dispatch queue{' or heal-on-read' * heals} was "
            f"still busy after {timeout_s}s")

    def settle(self) -> None:
        """After the checks, before anything is torn down: heal-on-read
        works off what the checks' degraded GETs queued and the dispatch
        queue comes to rest, so that no thread of the program is inside a
        device call (a first call takes 3-30 s) when the server stops."""
        self.quiet(2.0, 90.0, heals=True)


def bounded(what, seconds: float = 30.0) -> None:
    """Call ``what()`` and wait for it ``seconds`` at the most: stopping
    the program's threads may not hold the run's end."""
    import threading
    t = threading.Thread(target=what, daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        print(f"benchmark: {what.__qualname__} had not returned after "
              f"{seconds}s; going on", file=sys.stderr, flush=True)


def arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="break one stated guarantee on purpose (the runs "
                         "the comparison has to fail; see PERF.md)")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's .xplane.pb into this "
                         "directory")
    return ap


def main(argv=None) -> int:
    args = arg_parser().parse_args(argv)
    t_begin = time.monotonic()
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, cfg, mix = resolve(bench, args.workload)
    for name in served.ROUTE_ENV:
        if name in os.environ:
            raise SystemExit(f"benchmark: {name} is set; the server runs as "
                             "shipped, with no routing environment")
    os.environ.update(cfg.get("env", {}))
    device = served.require_tpu(cell["chips"])
    if device is None:
        return 2
    return run_cell(bench, cell, cfg, mix, args, device, t_begin)


def run_cell(bench, cell, cfg, mix, args, device, t_begin) -> int:
    """Everything after the look for a chip (the tests enter here)."""
    import minio_tpu
    from minio_tpu import ops
    say(f"device: {json.dumps(device)}; compile cache: "
        f"{ops.COMPILE_CACHE_DIR}")
    served.require_native()
    init_s = time.monotonic() - t_begin    # imports, the chip, the native build
    kind = load_module("traffic_kinds", mix["kind"])
    peaks = load_json(HERE, "peaks.json")
    root = served.scratch_root()
    ctx = Ctx(cfg=cfg, mix=mix, seed=args.seed, control=args.control,
              model=refmodel.Model(), tracer=None,
              phases={"init_s": init_s})
    try:
        t = time.monotonic()
        ctx.served = served.Served(root, cfg["drives"], cfg["parity"])
        say(f"server: {ctx.served.banner}; {ctx.served.endpoint}; drives "
            f"under {root}; block {ctx.served.obj.block_size}; bitrot "
            f"{ctx.served.obj.bitrot_algo.value}")
        ctx.pool = procs.ClientPool(mix["client_processes"], {
            "endpoint": ctx.served.endpoint, "ak": served.AK,
            "sk": served.SK, "geometry": cfg["geometry"]})
        ctx.phases["server_start_s"] = time.monotonic() - t
        try:
            if args.trace:
                import xplane
                ctx.tracer = xplane.Tracer(
                    os.path.join(root, "trace"), mix.get("trace_s", 4.0),
                    served.queue_counters)
            for phase in ("setup", "warm"):
                t = time.monotonic()
                getattr(kind, phase)(ctx)
                ctx.phases[phase + "_s"] = time.monotonic() - t
            t = time.monotonic()
            ctx.quiet()
            ctx.phases["quiet_s"] = time.monotonic() - t
            setup_s = time.monotonic() - t_begin
            # a run that has not ended WATCHDOG_S after its window says
            # where every thread stands, and ends (exit code 1)
            faulthandler.dump_traceback_later(args.seconds + WATCHDOG_S,
                                              exit=True)
            kind.window(ctx, args.seconds)
            attempted = -ctx.model.counts["ops_attempted"]
            for recs in ctx.window["threads"]:
                ctx.model.replay(recs)
            attempted += ctx.model.counts["ops_attempted"]
            # the mix's ``verify_env`` holds for the checks and what they
            # set off (they are the benchmark's own reads, not the cell's
            # traffic), and is unset again
            os.environ.update(mix.get("verify_env", {}))
            try:
                t = time.monotonic()
                kind.verify(ctx)
                ctx.phases["verify_s"] = time.monotonic() - t
                t = time.monotonic()
                ctx.settle()
                ctx.phases["settle_s"] = time.monotonic() - t
            finally:
                for name in mix.get("verify_env", {}):
                    os.environ.pop(name, None)
        finally:
            ctx.pool.close()
            bounded(ctx.served.shutdown)
        delta = served.window_delta(ctx.before, ctx.after)
        delta["server_cpu_s"] = round(ctx.server_cpu_s, 3)
        ctx.model.counts["salvaged_items"] = \
            sum(delta["salvaged_items"].values()) + delta["salvage_events"]
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        run = {"cell": cell, "cfg": cfg, "mix": mix, "window": ctx.window,
               "before": ctx.before, "after": ctx.after, "delta": delta,
               "setup_s": setup_s, "peaks": peaks, "device": device,
               "trace": None}
        traced = {}
        if ctx.tracer is not None:
            run["trace"] = ctx.tracer.reduce(args.keep_trace)
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            traced["breakdown"] = run["trace"]["breakdown"]
            group, directory = "per_layer", "layer_metrics"
        else:
            group, directory = "end_to_end", "e2e_metrics"
        metrics = metrics_of(bench, group, directory, cell["name"], run)
        window.say_profile(run)
        say("PHASES " + json.dumps(
            {k: round(v, 3) for k, v in ctx.phases.items()}))
        say("WINDOW " + json.dumps(delta, sort_keys=True))
        from minio_tpu.obs import device as devobs
        say("COMPILES (op, signature, first calls, seconds; whole run) "
            + json.dumps([[r["op"], r["signature"][:60], r["count"],
                           r["seconds"]]
                          for r in devobs.compile_snapshot()["table"][:16]]))
        correct = ctx.model.verdict(say)
        line = result_line(correct, attempted, ctx.model.failed,
                           ctx.model.checks(), metrics, device, traced)
    finally:
        bounded(minio_tpu.shutdown)
        shutil.rmtree(root, ignore_errors=True)
    faulthandler.cancel_dump_traceback_later()
    # every number compared beside its limit: the last lines of standard
    # error, and the last key of the result line
    print(*refmodel.check_lines(line["checks"]), sep="\n", file=sys.stderr,
          flush=True)
    say(json.dumps(line))
    return 0


def result_line(correct: bool, attempted: int, failed: int, checks: dict,
                metrics: dict, device: dict, traced: dict) -> dict:
    """The run's last line. ``checks`` comes last; a run that is not correct
    names, before it, the checks that fell (``failed_checks``: name ->
    count), so that a refusal says which guarantee broke."""
    fell = {n: c["value"] for n, c in checks.items()
            if c["value"] > c["limit"]}
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **traced,
            **({} if correct else {"failed_checks": fell}),
            "checks": checks}


def leave(code: int) -> None:
    """End the process without the interpreter's own teardown. By now every
    client process has been waited for, the server and the program's
    threads have been stopped and the result line is out; a daemon thread
    of the program that is still inside a device call would otherwise be
    able to hold the exit for ever."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        if e.code is not None and not isinstance(e.code, int):
            print(e.code, file=sys.stderr)
        code = e.code if isinstance(e.code, int) else int(e.code is not None)
    except BaseException:  # noqa: BLE001 — reported, then the same way out
        import traceback
        traceback.print_exc()
        code = 1
    leave(code)
