"""Object bytes back at full redundancy inside the window, over the
window's seconds: the objects whose shard and ``xl.meta`` are on the
replaced drive again when the window closes (counted on the drive by the
heal driver, whichever healer put them there: the admin sequence or MRF
heal-on-read), plus every object of each drive a sequence finished earlier
in the window, times the object size."""
import window


def read(run):
    heals = [r for r in window.records(run, "HEAL") if "seq" in r]
    if not heals:
        return None
    window.say(f"SAMPLES HEAL sequences={len(heals)} restored="
               f"{[r['restored'] for r in heals]} sequence healed="
               f"{[r['seq']['healed'] for r in heals]} scanned="
               f"{[r['seq']['scanned'] for r in heals]}")
    return sum(r["restored"] for r in heals) * run["mix"]["object_bytes"] \
        / window.MIB / run["window"]["seconds"]
