"""Traffic kind ``heal_drive``: one drive of the set has been replaced
(every shard and ``xl.meta`` of the bucket is gone from it) and is rebuilt
by admin heal sequences while closed-loop clients GET uniformly random keys.
When a sequence ends inside the window the next drive is emptied and the
next sequence starts, so "one drive being rebuilt under reads" lasts the
whole window whatever the heal rate is.

Mix parameters: client_processes (the last one runs the heal driver),
get_threads_per_process, objects, object_bytes, warm_objects, warm_steps,
warm_s, warm_repeats, readback_sample, heal_wait_s.

``--control``: ``lost-write`` (an acknowledged object that is on no drive),
``unhealed-shard`` (after the heal, one healed object is removed from the
rebuilt drive), ``unhealed-part`` (one part file of it is removed, its
``xl.meta`` left)."""
from __future__ import annotations

import glob
import json
import os
import shutil
import time

import numpy as np

import served
from served import say

BUCKET, WARM_BUCKET = "bench", "bench-warm"


def _ops(plans_ops):
    return [{"type": "ops", "ops": ops} for ops in plans_ops]


def _get_procs(ctx) -> int:
    return ctx.mix["client_processes"] - 1


def _fill(ctx, bucket: str, keys: list[str], stream: int) -> None:
    n = _get_procs(ctx) * ctx.mix["get_threads_per_process"]
    plans = [[{"op": "PUT", "bucket": bucket, "key": k,
               "size": ctx.mix["object_bytes"],
               "body": [ctx.seed, stream, int(k[4:])]} for k in keys[t::n]]
             for t in range(n)]
    for recs in ctx.pool.run(_ops(plans)):
        ctx.model.replay(recs)


def _empty_drive(ctx, bucket: str, drive: int) -> None:
    ctx.pool.run(_ops([[{"op": "EMPTY", "paths": [
        os.path.join(ctx.served.dirs[drive], bucket) + "/*"]}]]))


def _plans(ctx, bucket, keys, t_start, t_end, first_drive):
    """GET loops on all processes but the last; the heal driver alone on
    the last."""
    per, procs = ctx.mix["get_threads_per_process"], _get_procs(ctx)
    plans = [{"type": "loop", "proc": t // per, "bucket": bucket,
              "keys": keys, "deck": ["GET"], "min_live": 0,
              "rng": [ctx.seed, 3, t], "t_start": t_start, "t_end": t_end}
             for t in range(procs * per)]
    plans.append({"type": "heal", "proc": procs, "bucket": bucket,
                  "drive_dirs": ctx.served.dirs, "first_drive": first_drive,
                  "t_start": t_start, "t_end": t_end, "poll_s": 0.25})
    return plans


def _finish_heal(ctx, bucket: str, threads: list[list[dict]]) -> int:
    """Let the sequence that was running at the end finish (bounded wait);
    returns the drive it was rebuilding: the last one emptied, whose record
    the heal driver always writes."""
    last = threads[-1][-1]
    if last["status"] == 0 and last["seq"]["status"] == "running":
        for recs in ctx.pool.run(_ops([[{
                "op": "HEALWAIT", "bucket": bucket,
                "token": last["seq"]["clientToken"],
                "timeout_s": ctx.mix["heal_wait_s"]}]])):
            ctx.model.replay(recs)
    return last.get("drive", 0)


def setup(ctx) -> None:
    ctx.pool.run(_ops([[{"op": "MKBUCKET", "bucket": b}
                        for b in (BUCKET, WARM_BUCKET)]]))
    ctx.keys = [f"obj-{i:05d}" for i in range(ctx.mix["objects"])]
    _fill(ctx, BUCKET, ctx.keys, 1)
    _empty_drive(ctx, BUCKET, 0)
    if ctx.control == "lost-write":
        ctx.pool.run(_ops([[{"op": "EMPTY", "paths": [
            os.path.join(d, BUCKET, ctx.keys[0])
            for d in ctx.served.dirs]}]]))


def warm(ctx) -> None:
    """GET loops on the warm-up bucket, one step of ``warm_s`` seconds for
    each entry of ``warm_steps`` ({clients, env, heal}): drive 0 emptied
    anew (heal-on-read makes a warm object whole after its first GET), the
    step's ``env`` set for its length and unset again. The mix's steps send
    every rebuild to the chip and cap the batch at 1, 2, 4 and 8 in turn,
    so that each padded batch size at each shard width is called once; the
    last is the window's own pattern, repeated while it still meets a first
    call (at most ``warm_repeats`` times)."""
    keys = [f"wrm-{i:05d}" for i in range(ctx.mix["warm_objects"])]
    _fill(ctx, WARM_BUCKET, keys, 9)
    steps = list(ctx.mix["warm_steps"])
    repeats = ctx.mix.get("warm_repeats", 0)
    i = 0
    while i < len(steps):
        step, seen = steps[i], ctx.first_calls()
        _empty_drive(ctx, WARM_BUCKET, 0)
        os.environ.update(step["env"])
        try:
            t0 = time.monotonic() + 0.2
            plans = _plans(ctx, WARM_BUCKET, keys, t0,
                           t0 + ctx.mix["warm_s"], 0)
            gets, heal = plans[:-1], plans[-1:]
            # nothing measures a warm-up: its loops start when they can,
            # not at t0 or never (a host that stalls 0.2 s is no fault)
            for plan in gets:
                del plan["t_start"]
            threads = ctx.pool.run(
                [dict(gets[j % len(gets)], rng=[ctx.seed, 8, i, j])
                 for j in range(step["clients"])]
                + (heal if step.get("heal") else []))
            if step.get("heal"):
                _finish_heal(ctx, WARM_BUCKET, threads)
        finally:
            for name in step["env"]:
                os.environ.pop(name, None)
        for recs in threads:
            ctx.model.replay(recs)
        i += 1
        if i == len(steps) and repeats and ctx.first_calls() > seen:
            steps.append(step)
            repeats -= 1
    for k in keys:
        ctx.model.live.pop(k, None)


def window(ctx, seconds: float) -> None:
    ctx.timed(lambda t0, t1: _plans(ctx, BUCKET, ctx.keys, t0, t1, 0),
              seconds)


def verify(ctx) -> None:
    """Outside the window: the running sequence ends ``done``; every object
    is whole on all drives (``xl.meta`` and part files: what a sequence
    reported failed is judged here, on the drives, and nowhere else); and a
    seeded sample reads back bit-exact with two other drives' shards gone,
    so that healed shards are among what is read."""
    w = ctx.window
    seqs = [(r, r.get("seq", {})) for r in w["threads"][-1]]
    say("NOTE heal_sequences " + json.dumps([
        {"drive": r["drive"], "from_s": round(r["t0"] - w["t_start"], 2),
         "to_s": round(r["t1"] - w["t_start"], 2),
         "status": seq.get("status", r.get("err")),
         "failed": seq.get("failed"), "restored": r.get("restored")}
        for r, seq in seqs]) + f" window_s {w['seconds']}")
    t0 = time.monotonic()
    healed = _finish_heal(ctx, BUCKET, w["threads"])
    # the controls damage the object that is looked at first: heal-on-read
    # is still working off its queue and mends what it comes by
    on_healed = os.path.join(ctx.served.dirs[healed], BUCKET, ctx.keys[0])
    if ctx.control == "unhealed-shard":
        shutil.rmtree(on_healed)
    elif ctx.control == "unhealed-part":
        os.remove(sorted(glob.glob(os.path.join(on_healed, "*", "part.*")))[0])
    t1 = time.monotonic()
    ctx.model.shards_present(ctx.served.dirs, BUCKET)
    say(f"NOTE after the window: {t1 - t0:.2f} s until the last sequence "
        f"had ended, {time.monotonic() - t1:.2f} s looking at the drives")
    rng = np.random.default_rng([ctx.seed, 4])
    sample = [str(k) for k in
              rng.permutation(ctx.keys)[: ctx.mix["readback_sample"]]]
    n = len(ctx.served.dirs)
    served.whole(ctx.served.dirs, BUCKET, sample)
    ops = [{"op": "EMPTY", "paths": [
        os.path.join(ctx.served.dirs[(healed + j) % n], BUCKET, k)
        for j in (1, 2) for k in sample]}]
    ops += [{"op": "GET", "bucket": BUCKET, "key": k} for k in sample]
    for recs in ctx.pool.run(_ops([ops])):
        ctx.model.replay(recs)
