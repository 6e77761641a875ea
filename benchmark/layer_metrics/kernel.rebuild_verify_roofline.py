"""HBM-roofline share of the fused rebuild+verify program over the traced
sub-window: least bytes of the items the chip finished there / peak HBM
bytes/s / the device time of that program's events, %. Against HBM bytes
only: GF(256) and HighwayHash run on the VPU, for which no peak is
published. Absent when no such event ran in the sub-window."""
import rebuild_verify
import window
from served import say

PROGRAM = "jit_fused"   # the jitted callable of ops/fused.py's rebuild
OP, ROUTE = "fused", "device"


def read(run):
    t = run["trace"]
    if t is None:
        return None
    dev_s = sum(s for name, s in t["program_s"].items()
                if name.startswith(PROGRAM))
    # exact count from the queue's counters; the flush events (a ring that
    # may have turned over) only say which operations those items were
    items = t["at1"]["stats"]["device_items"] \
        - t["at0"]["stats"]["device_items"]
    other = {e.get("op") for e in t["at1"]["events"]
             if e["type"] == "flush_end" and e.get("route") == ROUTE
             and e["ts"] > t["t0"]} - {OP}
    if other:
        say(f"kernel.rebuild_verify_roofline: device flushes of {other} in "
            "the traced sub-window too; the item count is not this "
            "kernel's alone")
        return None
    if not dev_s or not items:
        say(f"kernel.rebuild_verify_roofline: nothing to read ({items} "
            f"device items of op {OP!r}, {dev_s} s of {PROGRAM!r} events in "
            "the traced sub-window)")
        return None
    per_item = rebuild_verify.mean_item_bytes(
        run["cfg"]["geometry"], run["mix"]["object_bytes"], rebuilt=1)
    share = 100.0 * items * per_item / window.peak(run, "hbm_bytes_per_s") \
        / dev_s
    say(f"kernel.rebuild_verify_roofline: {items} items x {per_item:.0f} B "
        f"in {dev_s:.6f} s of device time")
    return share
