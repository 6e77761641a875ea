"""Share of the window's GET blocks that were decoded in memory from the
shards the quorum metadata pass brought (route ``inline`` of
minio_tpu_pipeline_get_blocks_total: no shard file opened), in %. In a cell
whose every object is at or under the inline threshold it has to read 100;
on a program without the route it finds nothing to read."""
import counter_edges
from served import say


def read(run):
    blocks = counter_edges.moved(run, "minio_tpu_pipeline_get_blocks_total")
    if not blocks or not sum(blocks.values()):
        return None
    by_route = {counter_edges.label(k, "route"): v for k, v in blocks.items()}
    if "inline" not in by_route:
        return None     # a program that has no such route
    say("inline.get_share: GET blocks by route "
        f"{ {r: v for r, v in by_route.items() if v} }")
    return 100.0 * by_route["inline"] / sum(by_route.values())
