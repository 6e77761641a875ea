"""Share of the window's GET blocks of objects kept as shard files (one
part or many: everything over ``inline_max_bytes``) that took another
route than the two native calls (``native_fd``: all data shards read;
``native_degraded``: a shard rebuilt inside the call), from
minio_tpu_pipeline_get_blocks_total{route} with ``route="inline"`` left
out (half of this kind's objects are inline by design, and an inline
version has no block on a drive to read). Has to read 0: a part boundary,
a short last part, a short last block or a last frame of any length may
not push a block onto the ``plain`` or ``fused`` Python path."""
import counter_edges
from served import say

NATIVE = ("native_fd", "native_degraded")


def read(run):
    blocks = counter_edges.moved(run, "minio_tpu_pipeline_get_blocks_total")
    if not blocks:
        return None
    by_route = {counter_edges.label(k, "route"): v
                for k, v in blocks.items() if v}
    files = {r: v for r, v in by_route.items() if r != "inline"}
    if not sum(files.values()):
        return None
    say(f"sizes.get_off_native_block_share: GET blocks by route {by_route}")
    off = sum(v for r, v in files.items() if r not in NATIVE)
    return 100.0 * off / sum(files.values())
