"""What the SSE readers share: the program's SSE counters at the window's
two edges (``traffic_kinds/multipart_sse.py`` leaves them in the window's
record) as deltas by label. A run whose kind left none, or a program without
the counter, gives nothing to read."""
from __future__ import annotations

P = "minio_tpu_workloads_sse_"


def delta(run: dict, family: str, **labels) -> float | None:
    """How much the ``family`` counters with these label values moved over
    the window, summed over their other labels; None when the window holds
    no such counter."""
    edges = run["window"].get("sse_counters")
    if not edges:
        return None
    want = [f'{k}="{v}"' for k, v in labels.items()]
    keys = [k for k in edges[1] if k.startswith(P + family)
            and all(w in k for w in want)]
    if not keys:
        return None
    return sum(edges[1][k] - edges[0].get(k, 0.0) for k in keys)


def ms_per_mib(run: dict, op: str) -> float | None:
    seconds = delta(run, "seconds_total", op=op)
    nbytes = delta(run, "bytes_total", op=op)
    if seconds is None or not nbytes:
        return None
    return 1e3 * seconds / (nbytes / (1 << 20))
