"""95th percentile of the latency of the window's GETs of SMALL objects,
those at or under the configuration's ``inline_max_bytes``, in a bucket
where they are served beside GETs of up to ``size_max`` and multipart Completes
(clients' records: request sent to last body byte hashed; the ``size`` a
record of kind ``mixed_sizes`` carries). Its control is ``get_p95_ms`` of
``warp-mixed-small.8p4``, the same path beside nothing but its like."""
import window
from served import say


def read(run):
    small = run["cfg"]["geometry"]["inline_max_bytes"]
    lat = [(r["t1"] - r["t0"]) * 1e3 for r in window.records(run, "GET")
           if 0 <= r.get("size", -1) <= small]
    if not lat:
        return None
    say(f"sizes.small_get_p95_ms: {len(lat)} GETs at or under {small} B: "
        + " ".join(f"p{int(q * 100)}={window.percentile(lat, q)}"
                   for q in (0.5, 0.9, 0.95, 0.99)) + " ms")
    return window.percentile(lat, 0.95)
