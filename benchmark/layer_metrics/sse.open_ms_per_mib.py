"""Milliseconds the package cipher spent opening one MiB in the window:
minio_tpu_workloads_sse_seconds_total{op="open"} over
minio_tpu_workloads_sse_bytes_total{op="open"}, deltas between the window's
edges, whatever cipher and route were shipped."""
import sse_counters
from served import say


def read(run):
    v = sse_counters.ms_per_mib(run, "open")
    say(f"sse.open_ms_per_mib: "
        f"{sse_counters.delta(run, 'seconds_total', op='open')} s over "
        f"{sse_counters.delta(run, 'bytes_total', op='open')} B opened")
    return v
