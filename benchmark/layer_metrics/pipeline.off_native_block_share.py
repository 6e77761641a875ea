"""Share of the window's GET blocks that took another route than the two
native calls (``native_fd``: all data shards read; ``native_degraded``: a
shard rebuilt inside the call), from
minio_tpu_pipeline_get_blocks_total{route}, in %. Has to read 0: a drive
that is gone may not push a block onto the Python path."""
import counter_edges
from served import say

NATIVE = ("native_fd", "native_degraded")


def read(run):
    blocks = counter_edges.moved(run, "minio_tpu_pipeline_get_blocks_total")
    if not blocks or not sum(blocks.values()):
        return None
    by_route = {counter_edges.label(k, "route"): v
                for k, v in blocks.items() if v}
    say(f"pipeline.off_native_block_share: GET blocks by route {by_route}")
    off = sum(v for r, v in by_route.items() if r not in NATIVE)
    return 100.0 * off / sum(by_route.values())
