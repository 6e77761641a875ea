"""Share of the window's S3 requests the front end refused with 503
(admission, qos/admission), from the clients' status codes, in %."""


def read(run):
    recs = [r for recs in run["window"]["threads"] for r in recs
            if r["op"] in ("GET", "PUT", "STAT", "DELETE")]
    if not recs:
        return None
    return 100.0 * sum(r["status"] == 503 for r in recs) / len(recs)
