#!/usr/bin/env python
"""How far the runs of one cell spread, per end-to-end metric, against the
metric's bound in BENCHMARK.json: what a bound is set from, and what the
next ``benchmark`` issue starts from.

    python benchmark/tests/spread.py <file> [<file> ...]

Each file holds a run's output (its last line is the result line) or result
lines, one a line; all of them are runs of ONE cell. Per metric it prints
the runs' values, the median, IQR / median (PERF.md section 2's measure:
``statistics.quantiles(values, n=4)``), the check's own spread and that
spread as a share of the bound.

The check's own spread, as the driver words it when it cannot tell (ledger,
PR 37): the range of one side's runs, leaving out the run farthest from
their median where that narrows it. ``tell`` is the rule it then applies:
each side's spread against bound x the parent's median."""
from __future__ import annotations

import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def iqr(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def check_spread(values: list[float]) -> float:
    """Range of ``values`` without the one farthest from their median,
    which is always an end of the range (the whole range where there are
    fewer than three)."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))
    kept = kept[:-1] if len(kept) >= 3 else kept
    return max(kept) - min(kept)


def tell(parent: list[float], change: list[float], bound: float,
         better: str) -> str:
    """``unresolved`` where either side spreads by more than the bound
    allows (bound x the parent's median); else ``worse`` where the change's
    median is worse than the parent's by more than that, else ``held``."""
    room = bound * statistics.median(parent)
    if max(check_spread(parent), check_spread(change)) > room:
        return "unresolved"
    moved = statistics.median(change) - statistics.median(parent)
    if better == "higher":
        moved = -moved
    return "worse" if moved > room else "held"


def result_lines(paths: list[str]) -> list[dict]:
    out = []
    for path in paths:
        with open(path, errors="replace") as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        found = []
        for ln in lines:
            if ln.startswith("{") and '"metrics"' in ln:
                try:
                    found.append(json.loads(ln))
                except ValueError:
                    pass
        # a run's output: only its last line counts
        out += found if len(found) == len(lines) else found[-1:]
    return out


def table(lines: list[dict], bounds: dict[str, float]) -> list[dict]:
    """One row a metric the lines hold: values, median, IQR / median, the
    check's own spread over the median, and its share of the bound (None
    for a metric BENCHMARK.json does not bound)."""
    names = sorted({n for ln in lines for n in ln["metrics"]})
    rows = []
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        med = statistics.median(vals)
        own = check_spread(vals) / med
        rows.append({
            "metric": name, "values": vals, "median": med,
            "iqr_share": iqr(vals) / med if len(vals) >= 2 else None,
            "check_spread": check_spread(vals), "check_share": own,
            "bound": bounds.get(name),
            "of_bound": own / bounds[name] if name in bounds else None})
    return rows


def bounds_of(bench_path: str) -> dict[str, float]:
    with open(bench_path) as f:
        return {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}


def main(argv: list[str]) -> int:
    lines = result_lines(argv)
    if not lines:
        print("spread: no result line found", file=sys.stderr)
        return 1
    wrong = sum(not ln.get("correct") for ln in lines)
    print(f"{len(lines)} runs, {wrong} not correct")
    for r in table(lines, bounds_of(os.path.join(REPO, "BENCHMARK.json"))):
        share = "" if r["of_bound"] is None else \
            f" = {100 * r['of_bound']:.0f} % of the bound {r['bound']}"
        iq = "n/a" if r["iqr_share"] is None else f"{r['iqr_share']:.4f}"
        print(f"{r['metric']}: {[round(v, 3) for v in r['values']]} median "
              f"{r['median']:.4f} IQR/median {iq} check's spread "
              f"{r['check_spread']:.4f} = {r['check_share']:.4f} of the "
              f"median{share}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
