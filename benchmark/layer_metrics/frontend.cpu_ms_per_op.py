"""Thread CPU time one S3 request of the window spent OUTSIDE the object and
storage layers, in ms: the CPU seconds of the stages ``head``, ``admit``,
``route``, ``auth``, ``respond``, ``drain``, ``epilogue`` and ``other`` (the
request less every stage: the handler's own code) of the window's request
records over the records. ROADMAP Queue 1 item 1 (b) as a number."""
import request_stages
from served import say


def read(run):
    recs = request_stages.sampled(request_stages.s3(run) or [])
    if not recs:
        return None
    cpu = request_stages.stage_cpu_s(recs, request_stages.FRONTEND)
    by = {n: round(1e3 * request_stages.stage_cpu_s(recs, (n,)) / len(recs),
                   4) for n in request_stages.FRONTEND}
    say(f"frontend.cpu_ms_per_op: {cpu:.3f} CPU s of front-end stages over "
        f"{len(recs)} request records; ms a stage: {by}")
    return 1e3 * cpu / len(recs)
