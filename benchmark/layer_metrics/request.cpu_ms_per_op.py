"""Thread CPU time the server spent on one S3 request of the window, in ms:
the CPU seconds of every stage and pool task of the window's request records
(``minio_tpu.obs.attribution``: ``time.thread_time`` read at each stage's two
ends, on the request's thread and on its pool workers) over the records.
Under one interpreter lock this, not a count of calls and not a latency, is
what operations a second follow. Read from inside the program; the clients'
``server.stat_p50_ms`` and ``server.put_p95_ms`` time the same layers from
outside."""
import request_stages
from served import say


def read(run):
    recs = request_stages.sampled(request_stages.s3(run) or [])
    if not recs:
        return None
    cpu = sum(request_stages.cpu_s(r) for r in recs)
    say(f"request.cpu_ms_per_op: {cpu:.3f} CPU s over {len(recs)} request "
        f"records that read the CPU clock; an API: " + str({
            api: [t["n"], t["cpu_ms"]]
            for api, t in request_stages.table(recs).items()}))
    return 1e3 * cpu / len(recs)
