"""Share of the dispatch queue's items of the window that the router sent
to the chip: stats() delta, device_items / (device_items + cpu_items), %."""


def read(run):
    d = run["delta"]
    total = d["device_items"] + d["cpu_items"]
    if not total:
        return None
    return 100.0 * d["device_items"] / total
