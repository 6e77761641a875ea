"""Traffic kind ``mixed_1down``: kind ``mixed`` (``mixed.py``, loaded from
beside this file and used as it is) over a set ONE DRIVE of which is dead
from before the window until after the dead-state checks. The drive named
by ``[seed, 5]`` dies after set-up, from this process and with no hook in
the program: its directory is renamed aside and a regular file left at its
path, so every call on it fails with ENOTDIR as on an unmounted disk.

``warm`` repeats ``mixed``'s warm-up until the health tracker has fenced
that drive, so the window starts in the steady state of an outage. The
window is ``mixed``'s; the program's counters are read at its two edges
(``ctx.window["counters"]``, for the per-layer readers) and said on a
``COUNTERS`` line. ``verify``, with the drive still dead, is ``mixed``'s
over the drives that are left (read-back with ``parity - offline_drives``
FURTHER drives' shards removed: exactly k remain), once more for pool keys;
then the return leg: ``return_objects`` objects PUT into a bucket of their
own, the drive remounted with what it held when it died, and, once the
tracker has it online and with no heal asked for here, those objects are on
it and read back with ``parity`` other drives' shards removed, and pool keys
deleted in the window are 404 and gone from it.

Mix parameters: ``mixed``'s, and return_objects, trip_wait_s,
reonline_wait_s, restore_wait_s, gone_wait_s (seconds the tracker, its probe
and the healers are given). Configuration: ``offline_drives`` (1).

``--control``: ``lost-write`` as in ``mixed``."""
from __future__ import annotations

import importlib.util
import os
import time
import types

import numpy as np

import counter_edges
import refmodel
import served
from served import say

_spec = importlib.util.spec_from_file_location(
    "bench_traffic_kinds_mixed",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mixed.py"))
mixed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(mixed)

BUCKET, BACK_BUCKET = mixed.BUCKET, "bench-back"
ROUTES = "minio_tpu_pipeline_get_blocks_total"
TRIPS = "minio_tpu_disk_trips_total"
REONLINE = "minio_tpu_disk_reonline_total"
FAMILIES = (ROUTES, "minio_tpu_heal_objects_total", "minio_tpu_mrf_",
            TRIPS, REONLINE)


def _by_drive(ctx, family: str) -> dict[int, float]:
    """A per-drive counter by the drive's number."""
    at = {d: i for i, d in enumerate(ctx.served.dirs)}
    return {at[counter_edges.label(k, "disk")]: v
            for k, v in counter_edges.snapshot((family,)).items()
            if counter_edges.label(k, "disk") in at}


def _wait(cond, seconds: float) -> bool:
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.1)
    return cond()


def setup(ctx) -> None:
    if ctx.cfg["offline_drives"] != 1:
        raise SystemExit("benchmark: kind mixed_1down takes one drive "
                         f"away, not {ctx.cfg['offline_drives']}")
    mixed.setup(ctx)
    # with all twelve up, so that the bucket is on the drive that returns
    ctx.pool.run(mixed._ops([[{"op": "MKBUCKET", "bucket": BACK_BUCKET}]]))
    ctx.dead = int(np.random.default_rng([ctx.seed, 5]).integers(
        len(ctx.served.dirs)))


def warm(ctx) -> None:
    path = ctx.served.dirs[ctx.dead]
    os.rename(path, path + ".aside")
    with open(path, "w"):
        pass
    say(f"DRIVE {ctx.dead} ({os.path.basename(path)}) is dead: a regular "
        "file stands at its path")
    deadline = time.monotonic() + ctx.mix["trip_wait_s"]
    rounds = 0
    while True:
        mixed.warm(ctx)
        rounds += 1
        if _by_drive(ctx, TRIPS).get(ctx.dead) or \
                time.monotonic() >= deadline:
            break
    if not _by_drive(ctx, TRIPS).get(ctx.dead):
        raise SystemExit(f"benchmark: drive {ctx.dead} was not fenced by "
                         f"the health tracker in {rounds} warm-up rounds")
    say(f"warm: drive {ctx.dead} fenced after {rounds} round(s)")


def window(ctx, seconds: float) -> None:
    """``mixed``'s window, with the program's counters read at its two
    edges (``ctx.edge_reader``: once the clients are ready, no request
    sent yet, and at ``t_end``)."""
    ctx.edge_reader = lambda: counter_edges.snapshot(FAMILIES)
    mixed.window(ctx, seconds)
    c0, c1 = ctx.edges
    ctx.window["counters"] = (c0, c1)
    delta = {k.removeprefix("minio_tpu_"): round(v - c0.get(k, 0.0), 3)
             for k, v in sorted(c1.items()) if v != c0.get(k, 0.0)}
    blocks = {counter_edges.label(k, "route"): v
              for k, v in delta.items()
              if k.startswith(ROUTES.removeprefix("minio_tpu_"))}
    share = 100.0 * blocks.get("native_degraded", 0.0) \
        / max(1.0, sum(blocks.values()))
    mrf = getattr(ctx.served.srv, "mrf", None)
    say(f"COUNTERS moved in the window: {delta}; native_degraded "
        f"{share:.1f} % of {int(sum(blocks.values()))} GET blocks; heal "
        f"queue at the end {mrf.stats() if mrf is not None else None}")


def _readback(ctx, bucket: str, keys: list[str], drives) -> None:
    """``keys`` GET after their shards were removed from ``drives``, once
    they are whole on every drive that is mounted."""
    served.whole([d for d in ctx.served.dirs if os.path.isdir(d)], bucket,
                 keys)
    ops = [{"op": "EMPTY", "paths": [
        os.path.join(ctx.served.dirs[d], bucket, k)
        for d in drives for k in keys]}]
    ops += [{"op": "GET", "bucket": bucket, "key": k} for k in keys]
    for recs in ctx.pool.run(mixed._ops([ops])):
        ctx.model.replay(recs)


def verify(ctx) -> None:
    mix, parity = ctx.mix, ctx.cfg["parity"]
    dead, path = ctx.dead, ctx.served.dirs[ctx.dead]
    online = [i for i in range(len(ctx.served.dirs)) if i != dead]
    rng = np.random.default_rng([ctx.seed, 6])
    pick = lambda keys, n: [str(k) for k in  # noqa: E731
                            rng.permutation(sorted(keys))[:n]]
    # the drive still dead: mixed's checks over the drives that are left,
    # taking parity - 1 of them away (exactly k shards remain) ...
    mixed.verify(types.SimpleNamespace(**{
        **vars(ctx), "cfg": {**ctx.cfg, "parity": parity - 1},
        "served": types.SimpleNamespace(
            dirs=[ctx.served.dirs[i] for i in online])}))
    # ... and the same for pool keys, whose shard on the dead drive was
    # written before it died
    _readback(ctx, BUCKET,
              pick([k for k in ctx.model.live if k.startswith("obj-")],
                   mix["readback_sample"]),
              [int(d) for d in rng.permutation(online)[: parity - 1]])
    tripped = sorted(i for i, v in _by_drive(ctx, TRIPS).items()
                     if v and i != dead)
    say(f"NOTE other_drives_tripped {tripped}")
    # the return leg
    back = [f"back-{i:03d}" for i in range(mix["return_objects"])]
    puts = ctx.pool.run(mixed._ops([[
        {"op": "PUT", "bucket": BACK_BUCKET, "key": k,
         "size": mix["object_bytes"], "body": [ctx.seed, 7, i]}
        for i, k in enumerate(back)]]))
    written = refmodel.Model()
    for recs in puts:
        ctx.model.replay(recs)
        written.replay(recs)
    stale = pick([k for k in ctx.model.deleted if k.startswith("obj-")],
                 mix["readback_sample"])
    meta = lambda bucket, key: os.path.join(  # noqa: E731
        path, bucket, key, "xl.meta")
    reonline = _by_drive(ctx, REONLINE).get(dead, 0)
    t0 = time.monotonic()
    os.remove(path)
    os.rename(path + ".aside", path)
    say(f"DRIVE {dead} is remounted with what it held: "
        f"{sum(os.path.exists(meta(BUCKET, k)) for k in stale)} of "
        f"{len(stale)} sampled deleted keys are still on it")
    if not _wait(lambda: _by_drive(ctx, REONLINE).get(dead, 0) > reonline,
                 mix["reonline_wait_s"]):
        ctx.model.fault("ops_errored", {"op": "REONLINE", "key": f"drive-"
                        f"{dead}", "status": 0}, "the tracker's probe had "
                        f"not re-onlined it after {mix['reonline_wait_s']}"
                        " s")
    t1 = time.monotonic()
    _wait(lambda: all(os.path.exists(meta(BACK_BUCKET, k)) for k in back),
          mix["restore_wait_s"])
    t2 = time.monotonic()
    written.shards_present([path], BACK_BUCKET)
    ctx.model.counts["shards_missing_after_heal"] += \
        written.counts["shards_missing_after_heal"]
    ctx.model.faults += written.faults
    _readback(ctx, BACK_BUCKET, back,
              [int(d) for d in rng.permutation(online)[:parity]])
    for recs in ctx.pool.run(mixed._ops([[
            {"op": "GET", "bucket": BUCKET, "key": k} for k in stale]])):
        ctx.model.replay(recs)
    _wait(lambda: not any(os.path.exists(meta(BUCKET, k)) for k in stale),
          mix["gone_wait_s"])
    for k in stale:
        if os.path.exists(meta(BUCKET, k)):
            ctx.model.fault("deleted_keys_served",
                            {"op": "GONE", "key": k, "status": 0},
                            "its xl.meta is still on the returned drive "
                            f"after {mix['gone_wait_s']} s")
    say(f"RETURN online again after {t1 - t0:.1f} s, the "
        f"{len(back)} objects written meanwhile on it {t2 - t1:.1f} s "
        f"later, the {len(stale)} deleted ones gone "
        f"{time.monotonic() - t2:.1f} s after that")
