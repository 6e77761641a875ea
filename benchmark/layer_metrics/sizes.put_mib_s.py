"""PUT bandwidth: body bytes of the PUT operations acknowledged inside the
window (one request, or a multipart upload at its Complete: kind
``mixed_sizes`` records an upload as ONE ``PUT`` with the object's
``size``), over the window's seconds, in MiB/s (clients' records)."""
import window
from served import say


def read(run):
    done = [r for r in window.records(run, "PUT")
            if r["status"] == 200 and r["t1"] <= run["window"]["t_end"]
            and "size" in r]
    if not done:
        return None
    say(f"sizes.put_mib_s: {len(done)} PUTs acknowledged in the window, "
        f"{sum(r['size'] for r in done)} B, "
        f"{sum('mp' in r for r in done)} of them multipart uploads")
    return sum(r["size"] for r in done) / window.MIB \
        / run["window"]["seconds"]
