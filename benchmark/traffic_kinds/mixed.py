"""Traffic kind ``mixed``: closed-loop clients over a prepared pool of
objects, each drawing its next operation from shuffled copies of the mix's
``deck`` (GET / STAT / PUT / DELETE by count), all drives healthy. Every
client thread owns a disjoint slice of the keys, so the reference model is
exact without locks between processes.

Mix parameters: client_processes, threads_per_process, pool_objects,
object_bytes, deck {op: count}, put_bodies (prepared bodies per thread),
readback_sample."""
from __future__ import annotations

import numpy as np

import served

BUCKET, WARM_BUCKET = "bench", "bench-warm"


def _threads(ctx) -> int:
    return ctx.mix["client_processes"] * ctx.mix["threads_per_process"]


def _ops(plans_ops):
    return [{"type": "ops", "ops": ops} for ops in plans_ops]


def setup(ctx) -> None:
    n, size = _threads(ctx), ctx.mix["object_bytes"]
    ctx.pool.run(_ops([[{"op": "MKBUCKET", "bucket": b}
                                for b in (BUCKET, WARM_BUCKET)]]))
    keys = [f"obj-{i:05d}" for i in range(ctx.mix["pool_objects"])]
    ctx.own = [keys[t::n] for t in range(n)]
    plans = [[{"op": "PUT", "bucket": BUCKET, "key": k, "size": size,
               "body": [ctx.seed, 1, int(k[4:])]} for k in ctx.own[t]]
             for t in range(n)]
    for recs in ctx.pool.run(_ops(plans)):
        ctx.model.replay(recs)


def warm(ctx) -> None:
    """One of each operation per thread on the warm-up bucket: connections,
    signing keys, the server's per-request path. No device program is
    needed by this kind."""
    size = ctx.mix["object_bytes"]
    plans = []
    for t in range(_threads(ctx)):
        key = f"warm-{t:03d}"
        plans.append([{"op": "PUT", "bucket": WARM_BUCKET, "key": key,
                       "size": size, "body": [ctx.seed, 9, t]}]
                     + [{"op": op, "bucket": WARM_BUCKET, "key": key}
                        for op in ("GET", "STAT", "DELETE", "GET")])
    for recs in ctx.pool.run(_ops(plans)):
        ctx.model.replay(recs)
    if ctx.control == "lost-write":
        _lose(ctx, ctx.own[0][0])


def _lose(ctx, key: str) -> None:
    """The control: an acknowledged PUT that is on no drive."""
    import os
    ctx.pool.run(_ops([[{"op": "EMPTY", "paths": [
        os.path.join(d, BUCKET, key) for d in ctx.served.dirs]}]]))


def window(ctx, seconds: float) -> None:
    mix, n = ctx.mix, _threads(ctx)
    deck = [op for op, c in mix["deck"].items() for _ in range(c)]

    def plans(t_start, t_end):
        return [{"type": "loop", "bucket": BUCKET, "keys": ctx.own[t],
                 "deck": deck, "size": mix["object_bytes"],
                 "bodies": [[ctx.seed, 2, t * mix["put_bodies"] + j]
                            for j in range(mix["put_bodies"])],
                 "new_prefix": f"new-{t:03d}", "rng": [ctx.seed, 3, t],
                 "t_start": t_start, "t_end": t_end} for t in range(n)]
    ctx.timed(plans, seconds)


def verify(ctx) -> None:
    """Outside the window: every key the model holds answers a STAT with
    its size and ETag (an acknowledged PUT that is not there shows here);
    a sample of deleted keys is 404; and a seeded sample of keys
    acknowledged in the window reads back bit-exact with ``parity``
    drives' worth of their shards gone."""
    import os
    n = _threads(ctx)
    rng = np.random.default_rng([ctx.seed, 4])
    live = sorted(ctx.model.live)
    plans = [[{"op": "STAT", "bucket": BUCKET, "key": k}
              for k in live[t::n]] for t in range(n)]
    gone = sorted(ctx.model.deleted)
    for k in rng.permutation(gone)[: ctx.mix["readback_sample"]]:
        plans[0].append({"op": "GET", "bucket": BUCKET, "key": str(k)})
    for recs in ctx.pool.run(_ops(plans)):
        ctx.model.replay(recs)
    new = [k for k in live if k.startswith("new-")] or live
    sample = [str(k) for k in
              rng.permutation(new)[: ctx.mix["readback_sample"]]]
    drives = rng.permutation(len(ctx.served.dirs))[: ctx.cfg["parity"]]
    served.whole(ctx.served.dirs, BUCKET, sample)
    ops = [{"op": "EMPTY", "paths": [
        os.path.join(ctx.served.dirs[d], BUCKET, k)
        for d in drives for k in sample]}]
    ops += [{"op": "GET", "bucket": BUCKET, "key": k} for k in sample]
    for recs in ctx.pool.run(_ops([ops])):
        ctx.model.replay(recs)
