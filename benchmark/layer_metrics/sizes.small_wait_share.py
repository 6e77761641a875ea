"""Share of a SMALL request's life spent waiting: over the window's
request records (``minio_tpu.obs.attribution``) of GET, STAT, PUT and
DELETE whose ``object_bytes`` (the size of the object the request read,
wrote, statted or removed) is at or under the configuration's
``inline_max_bytes``, (wall seconds - thread CPU seconds of the request's
own thread) / wall seconds, summed, in %. Of the records that read the CPU
clock (``sampled``). What it waits for is a turn at the interpreter lock
beside large requests, a pool worker, a drive. A program whose records
name no object size gives nothing to read."""
import request_stages
from served import say

APIS = ("getobject", "headobject", "putobject", "deleteobject")


def read(run):
    small = run["cfg"]["geometry"]["inline_max_bytes"]
    recs = [r for r in request_stages.sampled(request_stages.s3(run) or [])
            if r["api"] in APIS and 0 <= r.get("object_bytes", -1) <= small]
    wall = sum(r["wall_s"] for r in recs)
    if not wall:
        return None
    cpu = sum(r["cpu_s"] for r in recs)
    say(f"sizes.small_wait_share: {len(recs)} records at or under {small} B "
        f"that read the CPU clock: wall {wall:.3f} s, own thread's CPU "
        f"{cpu:.3f} s; by API (n, wall ms, CPU ms) " + str({
            api: [t["n"], t["wall_ms"], t["cpu_ms"]]
            for api, t in request_stages.table(recs).items()}))
    return 100.0 * (wall - cpu) / wall
