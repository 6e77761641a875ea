"""What readers of the program's own counters share: the counter store
(``minio_tpu.obs.metrics.counters_snapshot``) at the window's two edges,
left in the window's record by the traffic kind (``run.py``'s own snapshots
do not hold it), as deltas by family and label. A run whose kind left none,
or a program without the counter, gives nothing to read."""
from __future__ import annotations


def snapshot(prefixes: tuple[str, ...]) -> dict:
    """The program's counters whose name starts with one of ``prefixes``,
    at this moment (a family the program lacks is simply not there)."""
    from minio_tpu.obs import metrics as mx
    return {k: v for k, v in mx.counters_snapshot().items()
            if k.startswith(prefixes)}


def label(key: str, name: str) -> str:
    """The value of label ``name`` in a counter's key ('' if it has none)."""
    return key.partition(f'{name}="')[2].partition('"')[0]


def moved(run: dict, family: str) -> dict | None:
    """How far each counter of ``family`` moved over the window, by its
    key; None when the window's record holds no edges or the later edge
    no such counter."""
    edges = run["window"].get("counters")
    if not edges:
        return None
    keys = [k for k in edges[1] if k.startswith(family)]
    if not keys:
        return None
    return {k: edges[1][k] - edges[0].get(k, 0.0) for k in keys}
