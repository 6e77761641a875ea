"""Milliseconds the package cipher spent sealing one MiB in the window:
minio_tpu_workloads_sse_seconds_total{op="seal"} over
minio_tpu_workloads_sse_bytes_total{op="seal"}, deltas between the window's
edges, whatever cipher and route were shipped."""
import sse_counters
from served import say


def read(run):
    v = sse_counters.ms_per_mib(run, "seal")
    say(f"sse.seal_ms_per_mib: "
        f"{sse_counters.delta(run, 'seconds_total', op='seal')} s over "
        f"{sse_counters.delta(run, 'bytes_total', op='seal')} B sealed")
    return v
