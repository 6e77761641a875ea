"""Arithmetic the metric readers share, over the clients' records of the
window. A latency is ``t1 - t0`` of one record (request sent to last body
byte hashed, or to the acknowledgement); a rate is taken over everything
completed by ``t_end`` and the whole window."""
from __future__ import annotations

import math

from served import say

MIB = 1 << 20


def records(run: dict, op: str) -> list[dict]:
    return [r for recs in run["window"]["threads"] for r in recs
            if r["op"] == op]


def rank(n: int, q: float) -> int:
    """The nearest rank (from 1) of the q-th percentile of ``n`` values;
    ``n - rank`` values lie beyond it."""
    return min(n, max(1, math.ceil(q * n)))


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile of all values; None when there are none."""
    if not values:
        return None
    return sorted(values)[rank(len(values), q) - 1]


def latency_ms(run: dict, op: str, q: float) -> float | None:
    """q-th percentile of the latency of every ``op`` of the window, in
    ms; an operation that failed or was refused counts with the time it
    took (it missed any limit). The sample count, the percentiles around
    the one read and how many samples lie beyond it go on a line of their
    own: a tail that rests on few samples of a flat stretch shows there."""
    lat = [(r["t1"] - r["t0"]) * 1e3 for r in records(run, op)]
    around = " ".join(f"p{int(p * 100)}={percentile(lat, p)}"
                      for p in sorted({0.5, 0.9, q, 0.99}))
    say(f"SAMPLES {op} n={len(lat)} {around} ms; beyond="
        f"{len(lat) - rank(len(lat), q)} of p{int(q * 100)}")
    return percentile(lat, q)


def say_profile(run: dict) -> None:
    """How the window's work spread over its fifths, per operation (by the
    time each ended): a stall or a drift shows here, not in a percentile."""
    w = run["window"]
    by_op: dict[str, list[int]] = {}
    for recs in w["threads"]:
        for r in recs:
            fifth = int(5 * (r["t1"] - w["t_start"]) / w["seconds"])
            if 0 <= fifth < 5:
                by_op.setdefault(r["op"], [0] * 5)[fifth] += 1
    say(f"PROFILE operations ended in each fifth of the window: {by_op}")


def done_bytes(run: dict, op: str) -> int:
    """Bytes of ``op`` bodies read to their end (status 200) by t_end."""
    return sum(r.get("n", 0) for r in records(run, op)
               if r["status"] == 200 and r["t1"] <= run["window"]["t_end"])


def peak(run: dict, key: str) -> float:
    """A published peak of the device this run is on (peaks.json, keyed by
    device_kind). A device that is not in the table is an error."""
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise RuntimeError(f"no peaks for device kind {kind!r} in "
                           "peaks.json")
    return run["peaks"][kind][key]
