"""Share of the window's PutObject requests whose version was written as
erasure shards inside the drives' xl.meta (no data directory, nothing
staged): the delta of minio_tpu_objectlayer_inline_versions_total{op="put"}
over the delta of minio_tpu_s3_requests_total{api="putobject"}, in %. In a
cell whose every object is at or under the inline threshold it has to read
100 (a PUT committed but not yet answered when the window closes is in the
first delta and not in the second: a quarter of a per cent over, now and
then); a program that writes shard files at every size has no such counter
and gives nothing to read."""
import counter_edges
from served import say


def read(run):
    inline = counter_edges.moved(
        run, 'minio_tpu_objectlayer_inline_versions_total{op="put"}')
    puts = counter_edges.moved(
        run, 'minio_tpu_s3_requests_total{api="putobject"')
    if not inline or not puts or not sum(puts.values()):
        return None
    say(f"inline.put_share: {sum(inline.values())} inline versions written "
        f"over {sum(puts.values())} PutObject requests")
    return 100.0 * sum(inline.values()) / sum(puts.values())
