"""The readers of the program's own request records, over a made-up ``run``:
each one's division, and its None when there is nothing to read (a program
without records, a ring that has turned over, a window no record ended in)."""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, os.path.join(BENCH, "lib"))

import request_stages  # noqa: E402


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rec(api, rid, wall, cpu, sw, stages, pool=None):
    """A record as ``attribution.between`` hands it back (``other`` is the
    thread's time less its stages)."""
    stages = {k: list(v) for k, v in stages.items()}
    stages["other"] = [wall - sum(v[0] for v in stages.values()),
                       cpu - sum(v[1] for v in stages.values()), 1, 0]
    return {"t_end": 1.0, "id": rid, "api": api, "status": 200, "bytes": 0,
            "nested": False, "wall_s": wall, "cpu_s": cpu, "switches": sw,
            "stages": stages, "pool": pool or {}}


RECORDS = [
    # a STAT: 10 ms of wall, 4 ms of CPU on its thread, 6 switches
    rec("headobject", "r1", 0.010, 0.004, 6,
        {"head": [0.0005, 0.0005, 1, 0], "auth": [0.001, 0.001, 1, 0],
         "meta_pass": [0.006, 0.0015, 1, 0],
         "epilogue": [0.0005, 0.0005, 1, 0]}),
    # a PUT: 30 ms of wall, 6 ms on its thread and 12 ms in 6 pool tasks
    rec("putobject", "r2", 0.030, 0.006, 10,
        {"auth": [0.001, 0.001, 1, 0], "commit": [0.020, 0.001, 1, 0],
         "epilogue": [0.001, 0.0015, 1, 0]},
        {"commit.pool": [0.060, 0.012, 6, 24]}),
    # the server's own planes and a heal no request was around
    rec("admin", "r3", 0.002, 0.002, 0, {}),
    rec("heal.object", "", 0.100, 0.010, 40,
        {"rebuild": [0.080, 0.002, 3, 0]},
        {"queue_wait": [0.050, 0.0, 3, 0]}),
]


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(request_stages, "_read",
                        lambda t0, t1: list(RECORDS))
    request_stages._cache.clear()
    return {"window": {"t_start": 10.0, "t_end": 61.0},
            "delta": {"server_cpu_s": 0.05}, "trace": None}


CASES = [
    # CPU of the two request records (4 + 6 + 12 ms) over 2
    ("request.cpu_ms_per_op", 11.0),
    # head, auth, epilogue and other of both: 0.5+1+0.5+0.5 and 1+1.5+2.5
    ("frontend.cpu_ms_per_op", (0.0025 + 0.005) / 2 * 1e3),
    # 0.5 + 1.5 ms of 22 ms
    ("obs.epilogue_cpu_share", 100.0 * 0.002 / 0.022),
    # every record: 22 + 2 + 10 ms of the process's 50
    ("request.unattributed_cpu_share", 100.0 * (1 - 0.034 / 0.05)),
]


@pytest.mark.parametrize("name,value", CASES)
def test_reader_divides_what_it_says(run, name, value, capsys):
    assert reader(name).read(run) == pytest.approx(value, rel=1e-9)
    assert capsys.readouterr().out.startswith(name + ": ")


@pytest.mark.parametrize("name", [n for n, _ in CASES])
@pytest.mark.parametrize("nothing", [None, []])
def test_reader_gives_none_when_nothing_is_there(run, monkeypatch, name,
                                                 nothing):
    monkeypatch.setattr(request_stages, "_read", lambda t0, t1: nothing)
    request_stages._cache.clear()
    assert reader(name).read(run) is None


def test_a_program_without_records_gives_nothing(monkeypatch, capsys):
    from minio_tpu.obs import attribution
    monkeypatch.delattr(attribution, "between")
    assert request_stages._read(0.0, 1.0) is None
    assert "keeps no request records" in capsys.readouterr().out


def test_a_ring_that_turned_over_gives_nothing_and_says_so(monkeypatch,
                                                           capsys):
    from minio_tpu.obs import attribution
    monkeypatch.setattr(attribution, "between", lambda t0, t1: None)
    assert request_stages._read(0.0, 1.0) is None
    assert "turned over" in capsys.readouterr().out


def test_cpu_is_read_from_the_records_that_read_the_clock(run, monkeypatch):
    """One record in N reads the CPU clock where a read is dear: the means
    are over those, and the process's share scales each API's mean by its
    records."""
    unread = [dict(r, sampled=False, cpu_s=0.0, switches=0,
                   stages={k: [v[0], 0.0, v[2], 0]
                           for k, v in r["stages"].items()},
                   pool={k: [v[0], 0.0, v[2], 0] for k, v in r["pool"].items()})
              for r in RECORDS for _ in range(3)]
    monkeypatch.setattr(request_stages, "_read",
                        lambda t0, t1: [dict(r, sampled=True)
                                        for r in RECORDS] + unread)
    request_stages._cache.clear()
    assert reader("request.cpu_ms_per_op").read(run) == pytest.approx(11.0)
    assert reader("frontend.cpu_ms_per_op").read(run) == pytest.approx(3.75)
    run["delta"]["server_cpu_s"] = 0.2
    assert reader("request.unattributed_cpu_share").read(run) == \
        pytest.approx(100.0 * (1 - 4 * 0.034 / 0.2))
    t = request_stages.table(request_stages.s3(run))
    assert t["putobject"]["n"] == 4 and t["putobject"]["n_cpu"] == 1
    assert t["putobject"]["cpu_ms"] == pytest.approx(18.0)
    assert t["putobject"]["wall_ms"] == pytest.approx(30.0)


def test_the_table_is_per_operation(run):
    t = request_stages.table(request_stages.s3(run))
    assert set(t) == {"headobject", "putobject"}
    assert t["putobject"]["cpu_ms"] == pytest.approx(18.0)
    assert t["putobject"]["stages"]["commit.pool"] == [60.0, 12.0, 6, 24]
    assert t["putobject"]["turns"] == 34 and t["headobject"]["turns"] == 6
    assert next(iter(t["putobject"]["stages"])) == "commit.pool"


def test_idle_gaps_are_named_by_the_stage_that_covers_most(monkeypatch):
    named = reader("device.idle_named_share")
    gaps = [(0.0, 1.0), (2.0, 2.5), (5.0, 5.1)]
    stages = [("heal.object/shard_read", 0.1, 0.7),
              ("getobject/decode", 0.6, 0.9),
              ("heal.object/rebuild", 1.9, 2.6)]
    stages += [("getobject/decode", 0.65, 0.95)]    # beside the first
    out = named.name_gaps(gaps, stages)
    assert [(c[0][0], pytest.approx(covered)) for _, c, covered in out] == [
        ("heal.object/shard_read", 0.85), ("heal.object/rebuild", 0.5),
        ("unattributed", 0.0)]
    # a stage's cover is the union of its events, not their sum
    assert out[0][1][1] == ["getobject/decode", pytest.approx(0.35)]
    assert named.STAGE.fullmatch("putobject/commit.pool")
    assert not named.STAGE.fullmatch("PjitFunction(fused)")
    # no trace, no file, no stage on the host planes: nothing to read
    assert named.read({"trace": None}) is None
    monkeypatch.setattr(request_stages, "trace_file", lambda: None)
    assert named.read({"trace": {}}) is None
    monkeypatch.setattr(request_stages, "trace_file", lambda: "x.pb")
    monkeypatch.setattr(named, "host_stages", lambda path: [])
    assert named.read({"trace": {}}) is None


def test_the_trace_is_found_only_when_there_is_one(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_BENCH_DIR", str(tmp_path))
    assert request_stages.trace_file() is None
    for i, root in enumerate(("bench-drives-a", "bench-drives-b")):
        d = tmp_path / root / "trace" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"")
        found = request_stages.trace_file()
        assert (found == str(d / "vm.xplane.pb")) if i == 0 \
            else found is None
