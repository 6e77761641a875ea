"""Did every PUT take the route its size names? Over the three routes
(``inline``, ``file``, ``multipart``): the sum of |delta of
``minio_tpu_objectlayer_put_versions_total{route}`` over the window - the
clients' count of PUTs acknowledged in the window whose size names that
route (``lib/sizes_ref.py route_of``)|, over those PUTs, in %. Has to read
0; a PUT committed before the window's edge and answered after it puts it
a fraction of a per cent over (as ``inline.put_share``). A program without
the counter family gives nothing to read."""
import counter_edges
import sizes_ref
import window
from served import say


def read(run):
    moved = counter_edges.moved(
        run, "minio_tpu_objectlayer_put_versions_total")
    if not moved:
        return None
    geom = run["cfg"]["geometry"]
    said = dict.fromkeys(("inline", "file", "multipart"), 0.0)
    for k, v in moved.items():
        said[counter_edges.label(k, "route")] = v
    sent = dict.fromkeys(said, 0)
    for r in window.records(run, "PUT"):
        if r["status"] == 200 and r["t1"] <= run["window"]["t_end"]:
            sent[sizes_ref.route_of(r["size"], geom)] += 1
    if not sum(sent.values()):
        return None
    say(f"sizes.put_route_mismatch_share: versions committed by route "
        f"{said}, PUTs acknowledged by the route their size names {sent}")
    return 100.0 * sum(abs(said[k] - sent[k]) for k in said) \
        / sum(sent.values())
