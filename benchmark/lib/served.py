"""The system under test as one run holds it: the CLI's own single-node
launch served from a thread of the process that holds the chip, and the
program's counters read as one snapshot. Copied from chip_smoke.py (proved
on the chip in PR 21), which stays as it is."""
from __future__ import annotations

import os
import sys
import tempfile
import time

AK, SK = "benchadmin", "benchsecret1"
ROUTE_ENV = ("MINIO_TPU_PUT_PATH", "MINIO_TPU_GET_PATH",
             "MINIO_TPU_DISPATCH_MODE")


def say(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int) -> dict | None:
    """The device as JAX reports it, or None with the reason printed: no
    CPU route."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        say(f"benchmark: refusing to run: jax.devices()[0].platform is "
            f"{d0.platform!r}, not 'tpu' (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')}); there is no "
            "CPU route")
        return None
    if len(devs) < chips:
        say(f"benchmark: refusing to run: the cell asks for {chips} chip(s) "
            f"but JAX sees {len(devs)}")
        return None
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def require_native() -> None:
    from minio_tpu import native
    from minio_tpu.erasure.bitrot import (DEFAULT_BITROT_ALGO,
                                          BitrotAlgorithm)
    if not native.available():
        raise RuntimeError("native library failed to build or load (see "
                           "the ERROR log above)")
    if DEFAULT_BITROT_ALGO is not BitrotAlgorithm.HIGHWAYHASH256S:
        raise RuntimeError(f"default bitrot algorithm is "
                           f"{DEFAULT_BITROT_ALGO.value}, not highwayhash256S")


def scratch_root() -> str:
    """Drives sit on a real file system in a directory this run makes and
    removes: under MINIO_TPU_BENCH_DIR if set, else under TMPDIR (the driver
    gives each side its own), never at a fixed path."""
    base = os.environ.get("MINIO_TPU_BENCH_DIR")
    if base:
        os.makedirs(base, exist_ok=True)
    return tempfile.mkdtemp(prefix="bench-drives-", dir=base or None)


def whole(dirs: list[str], bucket: str, keys: list[str],
          seconds: float = 90.0) -> None:
    """Before a read-back that takes shards away: wait until every drive of
    ``dirs`` that the health tracker fenced is online again (its trip and
    re-online counters) and every key has its ``xl.meta`` on every one of
    them. A host that stands still for some seconds makes the operations in
    flight miss the tracker's deadline: drives are fenced for their
    cool-down, a PUT meanwhile is acknowledged on the drives that are left,
    and the program's own heal makes it whole once the drive is back (no
    heal is asked for here). Until then the object is short of shards by the
    host's doing, and a read-back less ``parity`` FURTHER drives asks for
    more than the set promises: that answer is late, not wrong. After
    ``seconds`` this goes on all the same, and the read-back says what it
    finds."""
    import counter_edges
    trips, back = "minio_tpu_disk_trips_total", "minio_tpu_disk_reonline_total"

    def fenced() -> list[str]:
        n = dict.fromkeys(dirs, 0.0)
        for k, v in counter_edges.snapshot((trips, back)).items():
            d = counter_edges.label(k, "disk")
            if d in n:
                n[d] += v if k.startswith(trips) else -v
        return [os.path.basename(d) for d, v in n.items() if v > 0]

    def short() -> list[str]:
        return [k for k in keys if not all(os.path.exists(os.path.join(
            d, bucket, k, "xl.meta")) for d in dirs)]

    t0 = time.monotonic()
    while (fenced() or short()) and time.monotonic() - t0 < seconds:
        time.sleep(0.2)
    if time.monotonic() - t0 >= 0.2:
        say(f"NOTE whole: waited {time.monotonic() - t0:.1f} s before the "
            f"read-back less shards; drives still fenced {fenced()}, keys "
            f"still short of a drive {short()}")


class Served:
    """server/__main__.build_server (expand_endpoints -> pick_set_layout ->
    XLStorage -> ErasureObjects -> S3Server, background services on),
    serving on a thread."""

    def __init__(self, root: str, drives: int, parity: int):
        from minio_tpu.server import __main__ as cli
        os.environ["MINIO_TPU_ROOT_USER"] = AK
        os.environ["MINIO_TPU_ROOT_PASSWORD"] = SK
        self.dirs = [os.path.join(root, f"d{i:02d}") for i in range(drives)]
        args = cli.arg_parser().parse_args(
            [*self.dirs, "--address", "127.0.0.1:0", "--parity", str(parity)])
        self.srv, self.banner = cli.build_server(args)
        self.srv.start_background()
        self.obj = self.srv.obj
        self.endpoint = self.srv.endpoint()

    def shutdown(self):
        self.srv.shutdown()


def flush_events() -> list[dict]:
    """The flight recorder's flush and salvage events still in its ring."""
    from minio_tpu.obs import timeline as tl
    return [e for e in tl.snapshot()
            if e["type"] in ("flush_end", "salvage")]


def queue_counters() -> dict:
    """What the traced sub-window is bracketed with: the dispatch queue's
    counters (exact) and the flush events (a ring that turns over)."""
    from minio_tpu.runtime.dispatch import global_queue
    return {"stats": global_queue().stats(), "events": flush_events()}


def observe() -> dict:
    """Everything the per-layer readers may read of the program, at one
    moment: the dispatch queue's stats(), the compile table, the host hash
    fallback counters, and the flight recorder's flush and salvage events."""
    from minio_tpu.obs import device as devobs
    from minio_tpu.obs import metrics as mx
    from minio_tpu.obs import timeline as tl
    from minio_tpu.runtime.dispatch import global_queue
    st = global_queue().stats()
    comp = devobs.compile_snapshot()
    fb = {}
    for key, v in mx.counters_snapshot().items():
        if key.startswith("minio_tpu_pipeline_host_fallback_total"):
            fb[key.split('reason="')[1].split('"')[0]] = int(v)
    return {"t": time.monotonic(), "stats": st, "compile": comp,
            "host_fallback": fb, "timeline_dropped": tl.dropped_total(),
            "events": flush_events()}


def window_delta(before: dict, after: dict) -> dict:
    """What moved between two ``observe()`` snapshots, as plain numbers."""
    sb, sa = before["stats"], after["stats"]
    out = {k: sa[k] - sb[k] for k in
           ("items", "device_items", "cpu_items", "device_batches",
            "spilled_items")}
    out["interactive_items"] = sa["interactive_lane"]["items"] \
        - sb["interactive_lane"]["items"]
    for name in ("spill_reasons", "salvaged_items"):
        a, b = sb[name], sa[name]
        out[name] = {k: b.get(k, 0) - a.get(k, 0) for k in sorted(
            set(a) | set(b)) if b.get(k, 0) != a.get(k, 0)}
    out["salvage_events"] = sum(
        1 for e in after["events"]
        if e["type"] == "salvage" and e["ts"] > before["t"])
    out["compiles"] = after["compile"]["compiles_total"] \
        - before["compile"]["compiles_total"]
    out["compile_s"] = round(after["compile"]["compile_seconds_total"]
                             - before["compile"]["compile_seconds_total"], 3)
    out["probe"] = sa["probe"]
    out["timeline_dropped"] = after["timeline_dropped"] \
        - before["timeline_dropped"]
    return out
