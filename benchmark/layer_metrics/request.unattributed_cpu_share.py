"""What no record saw: 100 x (1 - the CPU seconds of every record that ended
in the window, requests and the heals and object operations no request was
around, over the server process's CPU seconds over the window,
``run["delta"]["server_cpu_s"]``), in %. Where the program reads the CPU
clock for one record in N only, an API's CPU is the mean of those records
times all its records. The rest is time before a request's
handler runs, threads that belong to no record (the listener, the sampler,
the dispatcher, the scanner) and requests in flight at the window's edges."""
import request_stages
from served import say


def read(run):
    recs = request_stages.records(run)
    total = run["delta"].get("server_cpu_s")
    if not recs or not total:
        return None
    by_api = request_stages.cpu_total_s(recs)
    if not by_api:
        return None
    seen = sum(by_api.values())
    say(f"request.unattributed_cpu_share: {seen:.3f} CPU s in {len(recs)} "
        f"records ({len(request_stages.sampled(recs))} read the CPU clock, "
        f"each API's mean times its records) over {total} CPU s of the "
        "process; an API: "
        + str({k: round(v, 3) for k, v in sorted(by_api.items())}))
    return 100.0 * (1.0 - seen / total)
