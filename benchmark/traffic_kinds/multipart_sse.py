"""Traffic kind ``multipart_sse``: encrypted large-object ingest and
read-back. Uploader threads write objects by multipart upload with
server-side encryption asked for on CreateMultipartUpload only (Create, the
parts one after another, Complete, then the next key of the thread's own
ring: an overwrite once the ring is full), while reader threads GET whole
objects of a pool that was uploaded the same way in set-up. All closed
loops; each part PUT is one record (op ``PUT``), each Complete one
(``COMPLETE``), each whole-object read one (``GET``).

The clients are ``lib/mp_client.py`` processes (``lib/client.py`` has no
multipart call and sends no header) and the model is ``lib/mp_model.py``;
``setup`` puts both in the place of the ones ``run.py`` made.

Mix parameters: client_processes, upload_threads, get_threads, parts,
part_bytes, pool_objects, ring, sse (the header's value), readback_sample,
range_gets, degraded_sample, at_rest_sample, big_parts,
big_put_live_bound_bytes, big_get_live_bound_bytes, warm_repeats; read by
``run.py``: trace_s, lead_s, verify_env.

``--control``: ``lost-write`` (a completed upload that is on no drive),
``wrong-master-key`` (the at-rest check unseals under another master key
than the deployment's: it has to fail)."""
from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

import procs
import served
from served import say

BUCKET, WARM_BUCKET = "bench", "bench-warm"
PKG = 64 << 10
MP_CLIENT = os.path.join(os.path.dirname(os.path.abspath(procs.__file__)),
                         "mp_client.py")


class Pool(procs.ClientPool):
    """``procs.ClientPool`` of ``lib/mp_client.py`` processes."""

    def __init__(self, n_procs: int, cfg: dict):
        env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1",
                   OMP_NUM_THREADS="1", OPENBLAS_NUM_THREADS="1")
        self.procs = [subprocess.Popen(
            [sys.executable, MP_CLIENT], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, env=env, text=True)
            for _ in range(n_procs)]
        for p in self.procs:
            self._send(p, {"cmd": "init", "cfg": cfg})
        for p in self.procs:
            self._recv(p)


def _mp(plans_ops):
    return [{"type": "mp", "ops": ops} for ops in plans_ops]


def _threads(ctx) -> int:
    return ctx.mix["upload_threads"] + ctx.mix["get_threads"]


def _specs(ctx, stream: int, index: int) -> list[list[int]]:
    """The part bodies of object ``index`` of ``stream``."""
    n = ctx.mix["parts"]
    return [[ctx.seed, stream, index * n + j] for j in range(n)]


def _upload(ctx, bucket: str, key: str, specs, **more) -> dict:
    return {"op": "UPLOAD", "bucket": bucket, "key": key, "parts": specs,
            "part_bytes": ctx.mix["part_bytes"], "sse": ctx.mix["sse"],
            **more}


def _replay(ctx, threads) -> None:
    for recs in threads:
        ctx.model.replay(recs)


def sse_counters() -> dict:
    """The program's SSE, multipart and GET-block-route counters at this
    moment (absent families are simply not there: a program without them
    gives {})."""
    from minio_tpu.obs import metrics as mx
    return {k: v for k, v in mx.counters_snapshot().items()
            if k.startswith(("minio_tpu_workloads_sse_",
                             "minio_tpu_multipart_",
                             "minio_tpu_pipeline_get_blocks_total"))}


def setup(ctx) -> None:
    import mp_model
    ctx.pool.close()
    ctx.pool = Pool(ctx.mix["client_processes"], {
        "endpoint": ctx.served.endpoint, "ak": served.AK, "sk": served.SK,
        "geometry": ctx.cfg["geometry"]})
    ctx.model = mp_model.Model(ctx.mix["sse"])
    say(f"server RSS {_rss()} B at set-up (runtime, native library, server)")
    ctx.pool.run(_mp([[{"op": "MKBUCKET", "bucket": b}
                       for b in (BUCKET, WARM_BUCKET)]]))
    n = _threads(ctx)
    ctx.pool_keys = [f"pool-{i:05d}" for i in range(ctx.mix["pool_objects"])]
    ctx.specs = {k: _specs(ctx, 1, i) for i, k in enumerate(ctx.pool_keys)}
    first = ctx.pool.run(_mp([[_upload(ctx, BUCKET, ctx.pool_keys[0],
                                       ctx.specs[ctx.pool_keys[0]])]]))
    if first[0][0]["status"] == 501:
        # a program that cannot take SSE on CreateMultipartUpload cannot
        # run this cell: say so and end, with no result line
        raise SystemExit("benchmark: CreateMultipartUpload under SSE "
                         f"answered 501 ({first[0][0].get('err', '')!r}): "
                         "this program cannot run the cell")
    _replay(ctx, first)
    _replay(ctx, ctx.pool.run(_mp(
        [[_upload(ctx, BUCKET, k, ctx.specs[k])
          for k in ctx.pool_keys[1:][t::n]] for t in range(n)])))
    if ctx.control == "lost-write":
        ctx.pool.run(_mp([[{"op": "EMPTY", "paths": [
            os.path.join(d, BUCKET, ctx.pool_keys[0])
            for d in ctx.served.dirs]}]]))


def warm(ctx) -> None:
    """A full upload and a whole GET by every thread on the warm-up bucket:
    connections, signing keys, the per-request path, and whatever device
    program the shipped cipher route calls (none on the host route).
    Repeated while a step still met a first call, ``warm_repeats`` times at
    the most."""
    for step in range(1 + ctx.mix["warm_repeats"]):
        seen = ctx.first_calls()
        plans = []
        for t in range(_threads(ctx)):
            key = f"warm-{t:03d}"
            plans.append([_upload(ctx, WARM_BUCKET, key,
                                  _specs(ctx, 9, t), reuse=True),
                          {"op": "GET", "bucket": WARM_BUCKET, "key": key},
                          {"op": "STAT", "bucket": WARM_BUCKET, "key": key}])
        _replay(ctx, ctx.pool.run(_mp(plans)))
        if ctx.first_calls() == seen:
            break
        say(f"warm: step {step} met {ctx.first_calls() - seen} first calls")


def window(ctx, seconds: float) -> None:
    mix = ctx.mix
    # the program's SSE counters at the window's two edges, for the
    # per-layer readers (run.py's own snapshots do not hold them)
    ctx.edge_reader = sse_counters

    def plans(t_start, t_end):
        ups = [{"type": "upload_loop", "bucket": BUCKET,
                "prefix": f"ring-{t:02d}", "ring": mix["ring"],
                "bodies": _specs(ctx, 2, t), "part_bytes": mix["part_bytes"],
                "sse": mix["sse"], "t_start": t_start, "t_end": t_end}
               for t in range(mix["upload_threads"])]
        gets = [{"type": "loop", "bucket": BUCKET, "keys": ctx.pool_keys,
                 "deck": ["GET"], "min_live": 0, "rng": [ctx.seed, 3, t],
                 "t_start": t_start, "t_end": t_end}
                for t in range(mix["get_threads"])]
        return ups + gets
    ctx.timed(plans, seconds)
    c0, c1 = ctx.edges
    ctx.window["sse_counters"] = (c0, c1)
    say("COUNTERS moved in the window: " + str(
        {k.removeprefix("minio_tpu_"): round(v - c0.get(k, 0.0), 3)
         for k, v in sorted(c1.items()) if v != c0.get(k, 0.0)}))
    for t in range(mix["upload_threads"]):
        ctx.specs.update({f"ring-{t:02d}-{i:03d}": _specs(ctx, 2, t)
                          for i in range(mix["ring"])})


def _ranges(ctx, rng, count: int) -> list[dict]:
    """Seeded ranges of pool objects: across one part edge, inside one
    package, across two part edges (three parts), on package edges."""
    size, parts = ctx.mix["part_bytes"], ctx.mix["parts"]
    out = []
    for i in range(count):
        key = ctx.pool_keys[int(rng.integers(len(ctx.pool_keys)))]
        edge = size * int(rng.integers(1, parts))
        a, b = (int(x) for x in rng.integers(1, 200_000, 2))
        if i % 4 == 0:
            lo, hi = edge - a, edge + b
        elif i % 4 == 1:
            lo = edge + PKG * int(rng.integers(0, size // PKG - 1)) + a % PKG
            hi = min(lo + b, lo - lo % PKG + PKG - 1)
        elif i % 4 == 2 and parts >= 3:
            edge = size * int(rng.integers(1, parts - 1))
            lo, hi = edge - a, edge + size + b
        else:
            lo = edge - PKG * (1 + a % 4)
            hi = edge + PKG * (1 + b % 4) - 1
        out.append({"op": "RGET", "bucket": BUCKET, "key": key, "lo": lo,
                    "hi": hi, "parts": ctx.specs[key], "part_bytes": size})
    return out


def _at_rest(ctx, keys: list[str]) -> None:
    """The reference (``lib/sse_ref.py``) reads each object's shard files
    from the drives, unseals its key under the deployment's master key and
    opens the first and last package of every part; no 64-byte run of a
    part's body may be in any of that part's shard files."""
    import mp_client
    import sse_ref
    master = bytes.fromhex(ctx.cfg["env"]["MINIO_TPU_KMS_MASTER_KEY"])
    if ctx.control == "wrong-master-key":
        master = hashlib.sha256(master).digest()
    size = ctx.mix["part_bytes"]
    opened = 0
    for key in keys:
        rec = {"op": "ATREST", "key": key, "status": 0}
        version, stored, files = sse_ref.stored_object(ctx.served.dirs,
                                                       BUCKET, key)
        meta = version["meta"]
        cipher = meta.get("x-minio-internal-sse-cipher", "")
        try:
            oek = sse_ref.unseal_oek(meta, BUCKET, key, master_key=master)
            streams = sse_ref.streams_of(meta, version["parts"], oek)
        except (sse_ref.BadTag, KeyError) as e:
            ctx.model.fault_n(
                "packages_unopened_at_rest", rec, "object key not unsealed: "
                f"{type(e).__name__}", 2 * len(version["parts"]))
            continue
        for i, (s, ct) in enumerate(zip(streams, stored)):
            body = mp_client.make_body(ctx.specs[key][i], size)
            last = (s.plain - 1) // PKG
            for seq in {0, last}:
                try:
                    ok = sse_ref.open_package(
                        cipher, s.key, s.iv, seq,
                        ct[seq * sse_ref.UNIT:(seq + 1) * sse_ref.UNIT]) \
                        == body[seq * PKG:(seq + 1) * PKG]
                except sse_ref.BadTag:
                    ok = False
                if ok:
                    opened += 1
                else:
                    ctx.model.fault("packages_unopened_at_rest", rec,
                                    f"part {i + 1} package {seq}")
            runs = mp_client.windows(body, 8)
            found = sum(w in blob for path, blob in files.items()
                        if path.endswith(f"part.{i + 1}") for w in runs)
            if found:
                ctx.model.fault_n("plaintext_runs_at_rest", rec, "64-byte "
                                  f"runs of part {i + 1}'s body", found)
    say(f"AT-REST {len(keys)} objects, {opened} packages opened by the "
        "reference under the configured master key")


def _rss() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def _big(ctx) -> None:
    """One object of ``big_parts`` parts is uploaded, then read back, while
    the bytes this process (the server) has allocated and not yet freed are
    traced (``tracemalloc``: Python objects, numpy arrays, raw buffers;
    exact, whatever the allocator keeps). Their peak may not reach
    ``big_put_live_bound_bytes`` during the upload (less than a part) nor
    ``big_get_live_bound_bytes`` during the read (a small share of the
    object). The growth of the resident size is said and not judged: a
    buffer the window's requests freed stays with the allocator and is
    handed out again without the resident size moving, so it cannot see a
    part that is kept (PERF.md section 6, PR 26)."""
    import tracemalloc
    mix = ctx.mix
    specs = [[ctx.seed, 5, j % mix["parts"]] for j in range(mix["big_parts"])]
    base, peak, done = _rss(), [0], threading.Event()

    def watch():
        while not done.wait(0.02):
            peak[0] = max(peak[0], _rss())
    t = threading.Thread(target=watch, daemon=True)
    t.start()
    live = {}
    tracemalloc.start(1)
    try:
        for phase, op in (
                ("put", _upload(ctx, BUCKET, "big", specs, reuse=True)),
                ("get", {"op": "GET", "bucket": BUCKET, "key": "big"})):
            tracemalloc.reset_peak()
            _replay(ctx, ctx.pool.run(_mp([[op]])))
            live[phase] = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
        done.set()
        t.join()
    say(f"BIG {mix['big_parts']} parts x {mix['part_bytes']} B: allocated "
        f"and not yet freed at the most {live['put']} B in the upload "
        f"(bound {mix['big_put_live_bound_bytes']} B), {live['get']} B in "
        f"the read (bound {mix['big_get_live_bound_bytes']} B); server RSS "
        f"{base} B before, grew by {max(0, peak[0] - base)} B at the most")
    for phase, got in live.items():
        if got >= mix[f"big_{phase}_live_bound_bytes"]:
            ctx.model.fault("memory_bound_exceeded",
                            {"op": "BIG", "key": "big", "status": 0},
                            f"{got} B live in the {phase}")


def verify(ctx) -> None:
    """Outside the window: every ring key's last completed upload STATs
    with its plaintext size and the folded ETag, a seeded sample of them
    and every pool key GET bit-exact, seeded ranges equal the bodies'
    slices, the reference opens what is at rest, a staged part is no
    plaintext, the big object passes under the memory bounds, and a sample
    reads back with ``parity`` drives' worth of its shards removed."""
    mix, n = ctx.mix, _threads(ctx)
    rng = np.random.default_rng([ctx.seed, 4])
    ring = sorted(k for k in ctx.model.live if k.startswith("ring-"))
    pick = lambda keys, count: [str(k) for k in  # noqa: E731
                                rng.permutation(keys)[:count]]
    plans = [[{"op": "STAT", "bucket": BUCKET, "key": k}
              for k in ring[t::n]] for t in range(n)]
    gets = pick(ring, mix["readback_sample"]) + ctx.pool_keys
    for t in range(n):
        plans[t] += [{"op": "GET", "bucket": BUCKET, "key": k}
                     for k in gets[t::n]]
    ranges = _ranges(ctx, rng, mix["range_gets"])
    for t in range(n):
        plans[t] += ranges[t::n]
    plans[0].append({"op": "STAGE", "bucket": BUCKET, "key": "staged",
                     "sse": mix["sse"], "part": [ctx.seed, 6, 0],
                     "part_bytes": mix["part_bytes"],
                     "drive_dirs": ctx.served.dirs})
    _replay(ctx, ctx.pool.run(_mp(plans)))
    _at_rest(ctx, pick(ring or ctx.pool_keys, mix["at_rest_sample"]))
    _big(ctx)
    sample = pick(ring or ctx.pool_keys, mix["degraded_sample"])
    drives = rng.permutation(len(ctx.served.dirs))[: ctx.cfg["parity"]]
    served.whole(ctx.served.dirs, BUCKET, sample)
    ops = [{"op": "EMPTY", "paths": [
        os.path.join(ctx.served.dirs[d], BUCKET, k)
        for d in drives for k in sample]}]
    ops += [{"op": "GET", "bucket": BUCKET, "key": k} for k in sample]
    _replay(ctx, ctx.pool.run(_mp([ops])))
