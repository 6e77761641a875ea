"""What a request's own observability costs while it is on: the thread CPU
seconds of the stage ``epilogue`` (everything ``S3Handler._handle`` does
after the reply: counters, histograms, the latency window, bucket statistics,
the trace record, the audit entry, the SLO plane, the span tree) over the CPU
seconds of every stage and pool task of the window's request records, in %."""
import request_stages
from served import say


def read(run):
    recs = request_stages.sampled(request_stages.s3(run) or [])
    if not recs:
        return None
    cpu = sum(request_stages.cpu_s(r) for r in recs)
    if not cpu:
        return None
    epilogue = request_stages.stage_cpu_s(recs, ("epilogue",))
    say(f"obs.epilogue_cpu_share: {epilogue:.3f} CPU s in the epilogue over "
        f"{cpu:.3f} CPU s of {len(recs)} request records")
    return 100.0 * epilogue / cpu
