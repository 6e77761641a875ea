"""Heal passes the program made in the window for each operation the
clients attempted: the delta of minio_tpu_heal_objects_total (every
``heal_object`` call, whoever asked: MRF heal-on-read and retries, the
auto-heal walk, an admin sequence) over the window's operations. With a
drive dead no pass can heal anything, so every one is a quorum metadata
read and a shard check a drive taken from the requests' turns at the
interpreter lock."""
import counter_edges
from served import say


def read(run):
    ops = sum(len(recs) for recs in run["window"]["threads"])
    if not run["window"].get("counters") or not ops:
        return None
    # the family is as old as heal itself: a program whose store does not
    # hold it yet has made no heal pass since it started
    passes = counter_edges.moved(run, "minio_tpu_heal_objects_total") or {}
    say(f"mrf.heal_passes_per_op: {sum(passes.values())} heal_object "
        f"calls over {ops} operations")
    return sum(passes.values()) / ops
