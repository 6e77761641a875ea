"""Traffic kind ``mixed_sizes``: warp's mixed deck (kind ``mixed``) over a
bucket that holds every size, as ``warp mixed --obj.randsize`` fills one:
"objects will be distributed in equal number for each doubling of the
size". A size is ``size_min * 2 ** (d + u)`` bytes, ``d`` the doubling
(0 .. log2(size_max / size_min) - 1) and ``u`` in [0, 1) from the seed, so
inline versions (at or under the configuration's ``inline_max_bytes``),
one-part shard files and multipart uploads (from ``multipart_min_bytes``,
parts of ``multipart_part_bytes``, as the client library sends them) are
written, read, statted and removed side by side by one server process.
About half the objects are inline; half the bytes lie in the top doubling
(at ``put-sizes``'s ``size_max`` of 32 MiB, two-part uploads).

Closed-loop clients (``lib/sizes_client.py`` processes; the model is
``lib/sizes_ref.py``'s), each thread over its own disjoint slice of the
keys, each drawing from shuffled copies of ``deck`` and each key uniformly
from its own live keys, as kind ``mixed`` does. The pool is stratified by
doubling, not drawn: ``pool_objects / doublings`` objects a doubling, each
with a ``u`` of its own, dealt so that every thread's keys hold every
doubling at least once; it is PUT in set-up the way the window PUTs. A PUT
of the window writes a new key with one of the thread's ``put_bodies``
bodies (one a doubling, each with a ``u`` of its own; made, hashed and
referenced before the window), taken in an order the seed shuffles. A
body from ``multipart_min_bytes`` goes up as Create, parts, Complete and is
ONE operation of the deck: one record of op ``PUT`` from Create sent to
Complete's 200, its parts as sub-records ``PART``. Every record carries
the ``size`` of the object it touched.

The program's counters are read at the window's two edges
(``ctx.window["counters"]``) and said on a ``COUNTERS`` line; a traced run
also says the program's request records as a ``STAGES`` table by size
class (``inline`` / ``file`` / ``multipart``) and API.

``verify``, outside the window: first ``at_rest_sample`` live keys (two a
doubling: one of the pool, one PUT in the window) as their drives hold
them, by the plain reference ``lib/sizes_ref.py check``: the body rebuilt
from the k data shards is what was PUT (``at_rest_body_mismatch``), every
frame's digest is its chunk's (``at_rest_digest_bad``), the parity drives
hold the plain encode of the data shards (``at_rest_parity_mismatch``),
the layout and the parts are what the size names
(``at_rest_layout_wrong``) and the bytes at rest are within the stated
bound (``at_rest_bytes_over``); then kind ``mixed``'s checks: a STAT of
every live key (size and ETag by its class's rule), ``readback_sample``
deleted keys answer 404, and ``readback_sample`` keys (one a doubling)
read back bit-exact with ``parity`` drives' object directories gone. Every
limit is 0.

Mix parameters: client_processes, threads_per_process, deck {op: count},
size_min, size_max, pool_objects, put_bodies, at_rest_sample,
readback_sample; read by ``run.py``: trace_s, lead_s, verify_env. The
part size and the size from which a body goes up in parts are the
geometry's ``multipart_part_bytes`` and ``multipart_min_bytes``.

``--control``: ``lost-write`` as in ``mixed`` (an acknowledged PUT that is
on no drive)."""
from __future__ import annotations

import json
import os

import numpy as np

import counter_edges
import procs
import served
import sizes_ref
from served import say

BUCKET, WARM_BUCKET = "bench", "bench-warm"
CLIENT = os.path.join(os.path.dirname(os.path.abspath(procs.__file__)),
                      "sizes_client.py")
FAMILIES = ("minio_tpu_objectlayer_put_", "minio_tpu_objectlayer_inline_",
            "minio_tpu_s3_requests_total",
            "minio_tpu_pipeline_get_blocks_total",
            "minio_tpu_storage_commits_total",
            "minio_tpu_storage_part_commits_total",
            "minio_tpu_storage_staged_files_total")
#: requests of an upload, whatever the size their record names
UPLOAD_APIS = ("newmultipartupload", "putobjectpart",
               "completemultipartupload", "abortmultipartupload")


class Pool(procs.ClientPool):
    """``procs.ClientPool`` of ``lib/sizes_client.py`` processes."""

    def __init__(self, n_procs: int, cfg: dict):
        theirs, procs.CLIENT = procs.CLIENT, CLIENT
        try:
            super().__init__(n_procs, cfg)
        finally:
            procs.CLIENT = theirs


def _threads(ctx) -> int:
    return ctx.mix["client_processes"] * ctx.mix["threads_per_process"]


def _doublings(mix: dict) -> int:
    n = int(mix["size_max"] // mix["size_min"]).bit_length() - 1
    if mix["size_min"] << n != mix["size_max"]:
        raise SystemExit("benchmark: size_max is size_min times a power "
                         "of two")
    return n


def _size(mix: dict, d: int, u: float) -> int:
    return int(mix["size_min"] * 2.0 ** (d + u))


def _doubling(mix: dict, size: int) -> int:
    return (size // mix["size_min"]).bit_length() - 1


def _sized(plans_ops):
    return [{"type": "sized", "ops": ops} for ops in plans_ops]


def _sput(bucket: str, key: str, size: int, spec: list[int]) -> dict:
    return {"op": "SPUT", "bucket": bucket, "key": key, "size": size,
            "body": spec}


def _replay(ctx, threads) -> None:
    for recs in threads:
        ctx.model.replay(recs)


def setup(ctx) -> None:
    mix, n, doublings = ctx.mix, _threads(ctx), _doublings(ctx.mix)
    per = mix["pool_objects"] // doublings
    if per * doublings != mix["pool_objects"] or per < n or \
            mix["put_bodies"] != doublings:
        raise SystemExit(
            "benchmark: kind mixed_sizes wants pool_objects a multiple of "
            f"the {doublings} doublings, at least one a thread a doubling, "
            "and put_bodies one a doubling")
    ctx.pool.close()
    ctx.pool = Pool(mix["client_processes"], {
        "endpoint": ctx.served.endpoint, "ak": served.AK, "sk": served.SK,
        "geometry": ctx.cfg["geometry"]})
    ctx.model = sizes_ref.Model()
    ctx.pool.run(_sized([[{"op": "MKBUCKET", "bucket": b}
                          for b in (BUCKET, WARM_BUCKET)]]))
    # the pool: ``per`` objects a doubling; of each doubling the first n
    # go one a thread, the rest are dealt round
    rng = np.random.default_rng([ctx.seed, 1])
    ctx.own = [[] for _ in range(n)]
    rest, i = [], 0
    for d in range(doublings):
        for j, u in enumerate(rng.random(per)):
            obj = (f"obj-{i:05d}", _size(mix, d, float(u)), [ctx.seed, 1, i])
            (ctx.own[(j + d) % n] if j < n else rest).append(obj)
            i += 1
    for j, obj in enumerate(rest):
        ctx.own[j % n].append(obj)
    total = sum(o[1] for own in ctx.own for o in own)
    say(f"POOL {i} objects, {per} a doubling of {doublings}, {total} bytes "
        f"of bodies; a thread {sorted({len(o) for o in ctx.own})} keys")
    _replay(ctx, ctx.pool.run(_sized(
        [[_sput(BUCKET, *obj) for obj in own] for own in ctx.own])))
    ctx.own = [[[key, size] for key, size, _ in own] for own in ctx.own]


def warm(ctx) -> None:
    """Each route once by every thread on the warm-up bucket (an inline
    PUT, a one-part PUT over ``etag_min_bytes``, an upload of a whole part
    and a short one), then GET, STAT, DELETE and a GET of the deleted key:
    connections, signing keys, the server's per-request path. No device
    program is needed by this kind."""
    geom = ctx.cfg["geometry"]
    sizes = (geom["inline_max_bytes"] // 32, 2 * geom["etag_min_bytes"],
             geom["multipart_part_bytes"] + geom["inline_max_bytes"] // 2)
    plans = []
    for t in range(_threads(ctx)):
        ops = []
        for j, size in enumerate(sizes):
            key = f"warm-{t:03d}-{j}"
            ops.append(_sput(WARM_BUCKET, key, size + t,
                             [ctx.seed, 9, 3 * t + j]))
            ops += [{"op": op, "bucket": WARM_BUCKET, "key": key,
                     "size": size + t}
                    for op in ("GET", "STAT", "DELETE", "GET")]
        plans.append(ops)
    _replay(ctx, ctx.pool.run(_sized(plans)))
    if ctx.control == "lost-write":
        ctx.lost = ctx.own[0][0][0]
        ctx.pool.run(_sized([[{"op": "EMPTY", "paths": [
            os.path.join(d, BUCKET, ctx.lost) for d in ctx.served.dirs]}]]))


def window(ctx, seconds: float) -> None:
    mix, n, doublings = ctx.mix, _threads(ctx), _doublings(ctx.mix)
    deck = [op for op, c in mix["deck"].items() for _ in range(c)]
    rng = np.random.default_rng([ctx.seed, 2])
    us = rng.random((doublings, n))   # a thread's bodies: one a doubling
    ctx.edge_reader = lambda: counter_edges.snapshot(FAMILIES)

    def plans(t_start, t_end):
        return [{"type": "sizes_loop", "bucket": BUCKET, "keys": ctx.own[t],
                 "deck": deck,
                 "bodies": [{"body": [ctx.seed, 2, t * doublings + d],
                             "size": _size(mix, d, float(us[d, t]))}
                            for d in range(doublings)],
                 "new_prefix": f"new-{t:03d}", "rng": [ctx.seed, 3, t],
                 "t_start": t_start, "t_end": t_end} for t in range(n)]
    ctx.timed(plans, seconds)
    c0, c1 = ctx.edges
    ctx.window["counters"] = (c0, c1)
    say("COUNTERS moved in the window: " + str(
        {k.removeprefix("minio_tpu_"): round(v - c0.get(k, 0.0), 3)
         for k, v in sorted(c1.items()) if v != c0.get(k, 0.0)}))


def stages_by_class(ctx) -> None:
    """The window's request records (``lib/request_stages.py``) as one
    ``STAGES <class> <api>`` line a size class and API: a record's class
    is that of its ``object_bytes`` (the requests of an upload: multipart).
    A program whose records name no object size gives nothing to say."""
    import request_stages
    geom = ctx.cfg["geometry"]
    recs = request_stages.s3({"window": ctx.window}) or []
    by_class: dict[str, list[dict]] = {}
    for r in recs:
        if r["api"] in UPLOAD_APIS:
            by_class.setdefault("multipart", []).append(r)
        elif r.get("object_bytes", -1) >= 0:
            by_class.setdefault(sizes_ref.route_of(r["object_bytes"], geom),
                                []).append(r)
    for cls in ("inline", "file", "multipart"):
        for api, row in sorted(request_stages.table(
                by_class.get(cls, [])).items()):
            say(f"STAGES {cls} {api} {json.dumps(row)}")


def _one_a_doubling(ctx, rng, keys: list[str]) -> dict[int, str]:
    """doubling -> one seeded key of ``keys`` (live ones) in it."""
    out: dict[int, str] = {}
    for k in rng.permutation(sorted(keys)):
        out.setdefault(_doubling(ctx.mix, ctx.model.live[str(k)][0]), str(k))
    return out


def at_rest(ctx) -> None:
    """``at_rest_sample`` live keys as their drives hold them, by the
    plain reference: of every doubling one key of the pool and one PUT in
    the window (where the window left none, a second of the pool)."""
    geom, doublings = ctx.cfg["geometry"], _doublings(ctx.mix)
    rng = np.random.default_rng([ctx.seed, 8])
    live = [k for k in ctx.model.live if k != getattr(ctx, "lost", None)]
    pool = [k for k in live if k[:4] == "obj-"]
    new = _one_a_doubling(ctx, rng, [k for k in live if k[:4] == "new-"])
    old = _one_a_doubling(ctx, rng, pool)
    more = _one_a_doubling(ctx, rng,
                           [k for k in pool if k not in old.values()])
    per = ctx.mix["at_rest_sample"] // doublings
    sample = []
    for d in range(doublings):
        picks = [s[d] for s in (old, new, more) if d in s]
        sample += picks[:per]
    # what a host that stood still left short of a drive is made whole by
    # the program's own heal first (lib/served.py)
    served.whole(ctx.served.dirs, BUCKET, sample)
    layouts: dict[str, int] = {}
    tight = (0.0, "")
    for key in sample:
        size, sha, _etag = ctx.model.live[key]
        got = sizes_ref.check(
            [os.path.join(d, BUCKET, key) for d in ctx.served.dirs], geom,
            size, sha)
        name = f"{got['layout']}x{len(got['want'][1])}"
        layouts[name] = layouts.get(name, 0) + 1
        tight = max(tight, (got["bytes"] / got["limit"],
                            f"{got['bytes']} of {got['limit']} allowed "
                            f"({size} B)"))
        ctx.model.at_rest(key, got)
    say(f"ATREST {len(sample)} keys by the plain reference "
        f"({len([k for k in sample if k[:4] == 'new-'])} PUT in the "
        f"window): layouts x parts {dict(sorted(layouts.items()))}; "
        f"nearest its bound at rest: {tight[1]}")


def verify(ctx) -> None:
    if ctx.tracer is not None:
        stages_by_class(ctx)
    at_rest(ctx)
    mix, n = ctx.mix, _threads(ctx)
    rng = np.random.default_rng([ctx.seed, 4])
    live = sorted(ctx.model.live)
    plans = [[{"op": "STAT", "bucket": BUCKET, "key": k,
               "size": ctx.model.live[k][0]} for k in live[t::n]]
             for t in range(n)]
    gone = sorted(ctx.model.deleted - set(ctx.model.live))
    for k in rng.permutation(gone)[: mix["readback_sample"]]:
        plans[0].append({"op": "GET", "bucket": BUCKET, "key": str(k)})
    _replay(ctx, ctx.pool.run(_sized(plans)))
    # one a doubling, of the keys PUT in the window where it left one
    keep = [k for k in live if k != getattr(ctx, "lost", None)]
    picked = {**_one_a_doubling(ctx, rng, keep),
              **_one_a_doubling(ctx, rng,
                                [k for k in keep if k[:4] == "new-"])}
    sample = [picked[d] for d in sorted(picked)][: mix["readback_sample"]]
    drives = rng.permutation(len(ctx.served.dirs))[: ctx.cfg["parity"]]
    served.whole(ctx.served.dirs, BUCKET, sample)
    ops = [{"op": "EMPTY", "paths": [
        os.path.join(ctx.served.dirs[d], BUCKET, k)
        for d in drives for k in sample]}]
    ops += [{"op": "GET", "bucket": BUCKET, "key": k,
             "size": ctx.model.live[k][0]} for k in sample]
    _replay(ctx, ctx.pool.run(_sized([ops])))
