"""Share of the window's sealed and opened packages whose route was the
dispatch queue (the ChaCha device lane), from
minio_tpu_workloads_sse_packages_total{cipher,route}, in %. 0 when the
shipped cipher runs on the host. It records which route the window took
and is no goal: at 100 % the served path read 15-30x slower than at 0
(PERF.md section 6, PR 26 (e)), hence ``better: lower``."""
import sse_counters
from served import say


def read(run):
    total = sse_counters.delta(run, "packages_total")
    if not total:
        return None
    edges = run["window"]["sse_counters"]
    moved = {k.split("packages_total")[1]: edges[1][k] - edges[0].get(k, 0.0)
             for k in edges[1] if "packages_total" in k}
    say(f"sse.device_package_share: packages by cipher and route {moved}")
    return 100.0 * (sse_counters.delta(run, "packages_total",
                                       route="dispatch") or 0.0) / total
