"""Traffic kind ``mixed_small``: kind ``mixed`` (``mixed.py``, loaded from
beside this file; its ``setup``, ``warm`` and ``window`` are used as they
are) over a bucket of SMALL objects, every one at or under the
configuration's ``inline_max_bytes``: what applications keeping thumbnails,
documents, log segments or training samples send. The program's counters
are read at the window's two edges (``ctx.window["counters"]``, for the
per-layer readers) and said on a ``COUNTERS`` line.

``verify`` first looks at the drives AS THEY LIE, through the plain
reference ``lib/inline_ref.py`` (which reads a shard from a drive's
``xl.meta`` or from its shard file, whichever is there), for
``at_rest_sample`` seeded keys, half PUT in the window and half of the
pool: the body rebuilt from the k data shards is what was PUT
(``at_rest_body_mismatch``), every frame's digest is its chunk's
(``at_rest_digest_bad``), the parity drives hold the plain encode of the
data shards (``at_rest_parity_mismatch``), and the object takes at most
(k+m)/k of its size plus 4 KiB a drive (``at_rest_bytes_over``); every
limit is 0. The look comes BEFORE ``mixed.verify`` takes object directories
away, so it never reads a drive that heal-on-read is rewriting; then
``mixed.verify`` as it is (STAT of every live key, deleted keys 404, a
sample read back with ``parity`` drives' object directories gone).
``correct`` holds the guarantees, not the mechanism: shard files pass it
too; that the inline path carried the window is read per layer.

Mix parameters: ``mixed``'s, and at_rest_sample.

``--control``: ``lost-write`` as in ``mixed``."""
from __future__ import annotations

import importlib.util
import os

import numpy as np

import counter_edges
import inline_ref
import served
from served import say

_spec = importlib.util.spec_from_file_location(
    "bench_traffic_kinds_mixed",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mixed.py"))
mixed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(mixed)

BUCKET = mixed.BUCKET
FAMILIES = ("minio_tpu_objectlayer_inline_", "minio_tpu_s3_requests_total",
            "minio_tpu_pipeline_get_blocks_total",
            "minio_tpu_storage_commits_total",
            "minio_tpu_storage_staged_files_total")
AT_REST = ("at_rest_body_mismatch", "at_rest_digest_bad",
           "at_rest_parity_mismatch", "at_rest_bytes_over")


def setup(ctx) -> None:
    if ctx.mix["object_bytes"] > ctx.cfg["geometry"]["inline_max_bytes"]:
        raise SystemExit("benchmark: kind mixed_small sends objects at or "
                         "under the configuration's inline_max_bytes")
    for name in AT_REST:    # reported by every run, sound or not
        ctx.model.counts[name] = 0
    mixed.setup(ctx)


warm = mixed.warm


def window(ctx, seconds: float) -> None:
    """``mixed``'s window, with the program's counters read at its two
    edges (``ctx.edge_reader``)."""
    ctx.edge_reader = lambda: counter_edges.snapshot(FAMILIES)
    mixed.window(ctx, seconds)
    c0, c1 = ctx.edges
    ctx.window["counters"] = (c0, c1)
    delta = {k.removeprefix("minio_tpu_"): round(v - c0.get(k, 0.0), 3)
             for k, v in sorted(c1.items()) if v != c0.get(k, 0.0)}
    say(f"COUNTERS moved in the window: {delta}")


def at_rest(ctx) -> None:
    """``at_rest_sample`` live keys as their drives hold them, by the
    plain reference."""
    geom, n = ctx.cfg["geometry"], ctx.mix["at_rest_sample"]
    rng = np.random.default_rng([ctx.seed, 8])
    live = sorted(ctx.model.live)
    new = [k for k in live if k.startswith("new-")]
    old = [k for k in live if not k.startswith("new-")]
    sample = [str(k) for k in rng.permutation(new)[: n // 2]]
    sample += [str(k) for k in rng.permutation(old)[: n - len(sample)]]
    # what a host that stood still left short of a drive is made whole by
    # the program's own heal first (lib/served.py)
    served.whole(ctx.served.dirs, BUCKET, sample)
    layouts: dict[str, int] = {}
    most = 0
    for key in sample:
        size, sha, _etag = ctx.model.live[key]
        got = inline_ref.check_object(
            [os.path.join(d, BUCKET, key) for d in ctx.served.dirs], geom,
            size, sha)
        layouts[got["layout"]] = layouts.get(got["layout"], 0) + 1
        most = max(most, got["bytes"])
        over = got["bytes"] > inline_ref.at_rest_limit(size, geom)
        rec = {"op": "ATREST", "key": key, "status": 0}
        for name, bad in (("at_rest_body_mismatch", got["body_mismatch"]),
                          ("at_rest_digest_bad", got["digest_bad"]),
                          ("at_rest_parity_mismatch",
                           got["parity_mismatch"]),
                          ("at_rest_bytes_over", int(over))):
            for _ in range(bad):
                ctx.model.fault(name, rec, "; ".join(got["why"][:3]) or
                                f"{got['bytes']} bytes at rest")
    say(f"ATREST {len(sample)} keys by the plain reference "
        f"({len([k for k in sample if k.startswith('new-')])} PUT in the "
        f"window): layouts {layouts}; the most bytes at rest {most} of "
        f"{inline_ref.at_rest_limit(ctx.mix['object_bytes'], geom)} "
        "allowed")


def verify(ctx) -> None:
    at_rest(ctx)
    mixed.verify(ctx)
