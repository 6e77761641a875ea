"""Share of the dispatch queue's items of the window that the QoS
scheduler spilled from the device route to the CPU: stats() delta,
spilled_items / items, %."""


def read(run):
    d = run["delta"]
    if not d["items"]:
        return None
    return 100.0 * d["spilled_items"] / d["items"]
