"""The window's arithmetic and its start: the nearest-rank percentile and
the ``SAMPLES`` line, the reader of result lines that bounds are set from
(``spread.py``), and the handshake that fixes the window's times once the
clients are ready."""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(BENCH, "lib"))
sys.path.insert(0, HERE)

import procs  # noqa: E402
import rehearse  # noqa: E402
import spread  # noqa: E402
import window  # noqa: E402


@pytest.mark.parametrize("n,q,rank,beyond", [
    (650, 0.95, 618, 32), (700, 0.95, 665, 35), (530, 0.95, 504, 26),
    (20, 0.95, 19, 1), (10, 0.5, 5, 5), (1, 0.95, 1, 0), (3, 0.99, 3, 0),
    (650, 0.99, 644, 6), (650, 0.9, 585, 65)])
def test_nearest_rank_percentile(n, q, rank, beyond):
    values = [float(v) for v in range(n, 0, -1)]    # 1..n, not in order
    assert window.rank(n, q) == rank
    assert n - window.rank(n, q) == beyond
    assert window.percentile(values, q) == float(rank)
    assert window.percentile([], q) is None


def test_samples_line_says_what_the_percentile_rests_on(capsys):
    recs = [{"op": "PUT", "t0": 0.0, "t1": v / 1e3} for v in range(1, 651)]
    recs += [{"op": "GET", "t0": 0.0, "t1": 9.0}]
    run = {"window": {"threads": [recs[:300], recs[300:]]}}
    assert window.latency_ms(run, "PUT", 0.95) == pytest.approx(618.0)
    line = capsys.readouterr().out.strip()
    assert line.startswith("SAMPLES PUT n=650 p50=325.0")
    for part in ("p90=585.0", "p95=618.0", "p99=644.0", "beyond=32 of p95"):
        assert part in line, line
    assert window.latency_ms(run, "DELETE", 0.95) is None
    assert "n=0" in capsys.readouterr().out


def lines_of(name, values, unit="ms"):
    return [{"correct": True, "attempted": 1, "failed": 0, "device": {},
             "metrics": {name: {"value": v, "unit": unit},
                         "setup_s": {"value": 30.0 + i, "unit": "s"}}}
            for i, v in enumerate(values)]


# ledger, PR 37: the parent's seven runs spread by 65.0062 ms on a median of
# 579.36 ms (values made up to give exactly that)
PR37 = [546.0, 555.0, 570.0, 579.36, 600.0, 611.0062, 720.0]


def test_spread_reader_on_made_up_lines(tmp_path):
    assert spread.check_spread(PR37) == pytest.approx(65.0062)
    # the farthest run is left out only where that narrows the range
    assert spread.check_spread([1.0, 2.0, 3.0, 4.0, 5.0]) == 3.0
    assert spread.check_spread([5.0, 7.0]) == 2.0
    rows = {r["metric"]: r for r in spread.table(
        lines_of("put_p95_ms", PR37), {"put_p95_ms": 0.1, "setup_s": 0.25})}
    r = rows["put_p95_ms"]
    assert r["median"] == 579.36
    assert r["check_spread"] == pytest.approx(65.0062)
    assert round(100 * r["of_bound"]) == 112
    assert r["iqr_share"] == pytest.approx((611.0062 - 555.0) / 579.36)
    r2 = spread.table(lines_of("put_p95_ms", PR37), {"put_p95_ms": 0.2})[0]
    assert round(100 * r2["of_bound"]) == 56
    assert rows["setup_s"]["median"] == 33.0
    # files: a run's output (last line counts) and a file of result lines
    out = tmp_path / "a.out"
    out.write_text("PHASES {}\n" + json.dumps(
        lines_of("put_p95_ms", [1.0])[0]) + "\nnoise\n" + json.dumps(
        lines_of("put_p95_ms", [546.0])[0]) + "\n")
    many = tmp_path / "b.jsonl"
    many.write_text("\n".join(json.dumps(ln) for ln in
                              lines_of("put_p95_ms", PR37[1:])))
    got = spread.result_lines([str(out), str(many)])
    assert sorted(ln["metrics"]["put_p95_ms"]["value"] for ln in got) == PR37
    assert spread.main([str(out), str(many)]) == 0


def test_the_checks_rule_tells_under_the_new_bound():
    change = [v + 9.55 for v in PR37[:-1]] + [735.8]
    assert spread.tell(PR37, change, 0.1, "lower") == "unresolved"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bound = {m["name"]: m["bound"] for m in
                 json.load(f)["end_to_end"]}["put_p95_ms"]
    assert bound >= 0.2
    assert spread.tell(PR37, change, bound, "lower") == "held"
    assert spread.tell(PR37, [v * 1.3 for v in PR37], bound,
                       "lower") == "worse"
    assert spread.tell([v * 1.3 for v in PR37], PR37, bound,
                       "lower") == "held"
    assert spread.tell([400.0, 410.0, 405.0], [300.0, 310.0, 305.0], 0.15,
                       "higher") == "worse"


def test_a_client_slower_than_the_lead_still_starts_on_time(monkeypatch):
    """Bodies that take 1.2 s to make, a lead of 0.3 s: the window's times
    are fixed once the clients are ready, so ``run_loop``'s guard (a loop
    that starts late fails the run with a PLAN record) stays silent and
    every loop sends its first request after ``t_start``."""
    sys.path.insert(0, BENCH)
    import run
    monkeypatch.setattr(procs, "CLIENT", os.path.join(HERE,
                                                      "slow_client.py"))
    monkeypatch.setattr(run.served, "observe", lambda: {})
    pool = procs.ClientPool(2, {
        "endpoint": "http://127.0.0.1:9", "ak": "a", "sk": "s",
        "geometry": rehearse.TINY_CFG["geometry"], "prepare_s": 1.2})
    ctx = run.Ctx(mix={"lead_s": 0.3}, pool=pool, tracer=None)
    made, reads = {}, []
    # a kind's own counters: read at the window's two edges
    ctx.edge_reader = lambda: reads.append(time.monotonic()) or len(reads)

    def plans(t_start, t_end):
        made["times"] = (t_start, t_end)
        return [{"type": "loop", "bucket": "b", "keys": ["k1", "k2"],
                 "deck": ["STAT"], "rng": [1, t], "t_start": t_start,
                 "t_end": t_end} for t in range(4)]
    t0 = time.monotonic()
    try:
        threads = ctx.timed(plans, 0.2)
    finally:
        pool.close()
    assert made["times"] == (0.0, 0.2)    # counted from the start to come
    w = ctx.window
    assert w["t_start"] - t0 >= 1.2 + 0.3 - 0.01    # ready, then the lead
    assert w["t_end"] == pytest.approx(w["t_start"] + 0.2)
    assert len(threads) == 4 and all(threads)
    for recs in threads:
        assert not [r for r in recs if r["op"] == "PLAN"], recs[:1]
        assert recs[0]["t0"] >= w["t_start"]
        # the loop looks at the clock, then stamps the request it sends
        assert recs[-1]["t0"] < w["t_end"] + 0.05
    assert ctx.edges == (1, 2)
    assert reads[0] <= w["t_start"] - 0.3 and reads[1] >= w["t_end"]


def test_a_loop_without_t_start_starts_at_once_and_a_late_one_refuses():
    """The window's loops refuse to start late (the guard); a warm-up's
    loops carry no ``t_start`` and start when they can: a host that stalls
    for the warm-up's 0.2 s of lead is no wrong answer."""
    import client
    cfg = {"endpoint": "http://127.0.0.1:9", "ak": "a", "sk": "s"}
    plan = {"type": "loop", "bucket": "b", "keys": ["k"], "deck": ["STAT"],
            "rng": [1], "_puts": []}
    now = time.monotonic()
    late = client.run_loop(cfg, dict(plan, t_start=now - 0.01,
                                     t_end=now + 0.05))
    assert [r["op"] for r in late] == ["PLAN"] and late[0]["status"] == -1
    recs = client.run_loop(cfg, dict(plan, t_end=time.monotonic() + 0.05))
    assert recs and all(r["op"] == "STAT" for r in recs)


def test_a_read_back_less_shards_waits_until_the_keys_are_whole(
        tmp_path, monkeypatch, capsys):
    """``served.whole``: a drive the tracker fenced and a key short of a
    drive's ``xl.meta`` are waited for; drives outside ``dirs`` are not;
    after ``seconds`` it goes on and names what is left."""
    import threading

    import counter_edges
    import served
    dirs = [str(tmp_path / f"d{i}") for i in range(3)]
    other = str(tmp_path / "dead")

    def put(d, key):
        os.makedirs(os.path.join(d, "b", key))
        with open(os.path.join(d, "b", key, "xl.meta"), "w"):
            pass
    for d in dirs[:2]:
        put(d, "k1")
    trips, back = ("minio_tpu_disk_trips_total{disk=\"%s\"}",
                   "minio_tpu_disk_reonline_total{disk=\"%s\"}")
    counters = {trips % dirs[2]: 2.0, back % dirs[2]: 1.0,
                trips % other: 1.0}
    monkeypatch.setattr(counter_edges, "snapshot",
                        lambda prefixes: dict(counters))

    def heal():
        counters[back % dirs[2]] = 2.0
        time.sleep(0.3)
        put(dirs[2], "k1")
    threading.Timer(0.4, heal).start()
    t0 = time.monotonic()
    served.whole(dirs, "b", ["k1"], 10.0)
    assert 0.6 <= time.monotonic() - t0 < 5.0
    assert "NOTE whole: waited" in capsys.readouterr().out
    t0 = time.monotonic()
    served.whole(dirs, "b", ["k1"], 10.0)          # whole: at once, silent
    assert time.monotonic() - t0 < 0.2
    assert capsys.readouterr().out == ""
    t0 = time.monotonic()
    served.whole(dirs, "b", ["k1", "k2"], 0.5)      # never whole: goes on
    assert 0.5 <= time.monotonic() - t0 < 2.0
    out = capsys.readouterr().out
    assert "keys still short of a drive ['k2']" in out
    assert "drives still fenced []" in out
