"""A request says where its time went (obs/stages.py, obs/attribution.py):
one record a finished request, its stages on three clocks, the ring they are
kept in, the switch, and the stage names on the profiler's host plane."""
import glob
import io
import os
import threading
import time

import pytest

from s3client import S3Client

from minio_tpu.obs import attribution, profiler, spans, stages, timeline

AK, SK = "stak", "stsecret1"
MIB = 1 << 20
FRONT = {"head", "admit", "route", "auth", "respond", "drain", "epilogue"}


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Every unit of work reads the CPU clock here (a stride of 1), as on
    a host where a read is cheap. ``cpu_stride`` times that read ONCE a
    process, at whichever test first arms a unit: in this sandbox a read
    costs 0.57-0.59 us against a line at 0.6 (1.5 x CPU_READ_BUDGET_NS),
    so beside five busy test workers a process now and then settles on a
    stride of 2 for good, half its records carry no CPU seconds, and a
    case that asks ``cpu_s > 0`` of its one record fails by that draw.
    What the rule makes of a read's cost has its own cases below."""
    monkeypatch.setattr(stages, "_stride", 1)
    os.environ.pop("MINIO_TPU_TIMELINE", None)
    timeline.configure()
    attribution.reset()
    yield
    os.environ.pop("MINIO_TPU_TIMELINE", None)
    timeline.configure()
    attribution.reset()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.server.s3api import S3Server
    from minio_tpu.storage import XLStorage
    root = tmp_path_factory.mktemp("stages")
    ol = ErasureObjects([XLStorage(str(root / f"d{i}")) for i in range(6)],
                        default_parity=2)
    srv = S3Server(ol, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    srv.start_background()
    c = S3Client(srv.endpoint(), AK, SK)
    assert c.put_bucket("b").status_code == 200
    yield srv, c, ol
    srv.shutdown()


def _upload(c) -> str:
    r = c.request("POST", "/b/mp", query={"uploads": ""})
    assert r.status_code == 200, r.text
    return r.text.split("<UploadId>")[1].split("</UploadId>")[0]


def _drive(c, case: str):
    """Send the one request of ``case`` (what it needs is sent first and
    forgotten); returns its response."""
    big, small = os.urandom(MIB), os.urandom(1000)
    if case in ("stat", "get", "delete"):
        assert c.put_object("b", "o", body=big).status_code == 200
    if case == "get_inline":
        assert c.put_object("b", "s", body=small).status_code == 200
    upload = _upload(c) if case == "part" else ""
    for _ in range(50):     # a record is kept after the reply has gone out
        if attribution.between(0.0, time.monotonic() + 1) is not None and \
                not stages._open:
            break
        time.sleep(0.01)
    time.sleep(0.05)
    attribution.reset()
    return {
        "stat": lambda: c.head_object("b", "o"),
        "get": lambda: c.get_object("b", "o"),
        "get_inline": lambda: c.get_object("b", "s"),
        "put": lambda: c.put_object("b", "o", body=big),
        "put_inline": lambda: c.put_object("b", "s", body=small),
        "delete": lambda: c.delete_object("b", "o"),
        "part": lambda: c.request(
            "PUT", "/b/mp", query={"partNumber": "1", "uploadId": upload},
            body=big),
    }[case]()


def _one_record(api: str) -> dict:
    for _ in range(200):
        recs = [r for r in attribution.between(0.0, time.monotonic() + 1)
                if r["api"] == api]
        if recs:
            assert len(recs) == 1, recs
            return recs[0]
        time.sleep(0.01)
    raise AssertionError(f"no record of {api}")


@pytest.mark.parametrize("case,api,has,pool", [
    ("stat", "headobject", {"bucket_check", "meta_pass"}, {}),
    ("get", "getobject", {"bucket_check", "meta_pass", "decode"},
     {"decode.pool": 1}),
    ("get_inline", "getobject", {"bucket_check", "meta_pass"}, {}),
    ("put", "putobject", {"bucket_check", "body_read", "encode_hash",
                          "commit"}, {"commit.pool": 6}),
    ("put_inline", "putobject", {"bucket_check", "body_read", "commit"},
     {"commit.pool": 6}),
    ("delete", "deleteobject", {"bucket_check", "meta_pass", "delete"},
     {"delete.pool": 6}),
    ("part", "putobjectpart", {"meta_pass", "body_read", "commit"},
     {"commit.pool": 6}),
])
def test_a_request_leaves_one_record_whose_stages_sum_to_its_wall(
        served, case, api, has, pool):
    _, c, _ = served
    resp = _drive(c, case)
    assert resp.status_code in (200, 204), resp.text
    rec = _one_record(api)
    assert rec["id"] == resp.headers["x-amz-request-id"]
    assert rec["status"] == resp.status_code and not rec["nested"]
    assert FRONT | {"other"} <= set(rec["stages"]), rec["stages"]
    # (a pipelined PUT encodes beside the request's thread)
    assert has <= set(rec["stages"]) | set(rec["pool"]), rec
    # the stages of the request's thread are self times: with ``other``
    # they are the wall, and their CPU the thread's
    wall = sum(v[0] for v in rec["stages"].values())
    assert wall == pytest.approx(rec["wall_s"], rel=0.01)
    assert sum(v[1] for v in rec["stages"].values()) == \
        pytest.approx(rec["cpu_s"], rel=0.01)
    assert all(v[0] >= -1e-9 for v in rec["stages"].values()), rec
    assert 0 < rec["cpu_s"] <= rec["wall_s"] * 1.05
    # a fan-out over the drives is one pool task a drive, with CPU time
    for name, n in pool.items():
        assert rec["pool"][name][2] == n, rec["pool"]
        assert rec["pool"][name][1] > 0
    if case.startswith("put") or case == "part":
        assert rec["bytes"] >= (1000 if "inline" in case else MIB)


def test_a_quorum_pass_over_remote_drives_is_one_pool_task_a_drive(
        tmp_path):
    """Local drives are read in line (no pool hop); drives that are not
    local take the pool, and ``meta_pass.pool`` holds a task a drive."""
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.storage import XLStorage

    class Remote(XLStorage):
        def is_local(self):
            return False
    ol = ErasureObjects([Remote(str(tmp_path / f"d{i}")) for i in range(6)],
                        default_parity=2)
    ol.make_bucket("b")
    ol.put_object("b", "o", io.BytesIO(b"x" * 4096), 4096)
    attribution.reset()
    u = attribution.begin("rid-1", "headobject")
    ol.get_object_info("b", "o")
    attribution.finish(u, status=200)
    rec, = attribution.between(0.0, time.monotonic() + 1)
    assert rec["stages"]["meta_pass"][2] == 1
    assert rec["pool"]["meta_pass.pool"][2] == 6
    assert rec["pool"]["meta_pass.pool"][1] > 0


def _spin(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("work,cpu_lo,cpu_hi", [
    (lambda: _spin(0.05), 0.045, 0.08),
    (lambda: time.sleep(0.05), 0.0, 0.01),
])
def test_a_spin_reads_as_cpu_and_a_sleep_as_wall_without_cpu(
        work, cpu_lo, cpu_hi):
    with stages.collect() as st:
        with stages.stage("x"):
            work()
    wall, cpu, n, _ = st.own["x"]
    assert n == 1 and wall >= 0.045
    assert cpu_lo <= cpu <= cpu_hi, (wall, cpu)


def test_nested_stages_charge_self_time():
    t0 = time.monotonic()
    with stages.collect() as st:
        with stages.stage("outer"):
            _spin(0.02)
            with stages.stage("inner"):
                _spin(0.03)
                with stages.timed(st, "leaf"):
                    time.sleep(0.02)
    total = time.monotonic() - t0
    assert st.own["inner"][1] == pytest.approx(0.03, abs=0.01)
    assert st.own["outer"][1] == pytest.approx(0.02, abs=0.01)
    assert st.own["leaf"][0] >= 0.02 and st.own["leaf"][1] < 0.005
    # a boundary's own wall leaves out what ran inside and around it (at
    # least their CPU and sleep), however long a busy host makes the whole
    assert st.own["outer"][0] <= total - 0.05 + 0.002
    assert st.own["inner"][0] <= total - 0.04 + 0.002
    assert not stages._open     # every boundary closed behind itself


def test_a_boundary_hands_a_share_of_its_time_on():
    with stages.collect() as st:
        with stages.timed(st, "encode_hash") as b:
            time.sleep(0.04)
            b.split("shard_write", 0.25)
    total = st.own["encode_hash"][0] + st.own["shard_write"][0]
    assert st.own["shard_write"][0] == pytest.approx(total / 4, rel=0.01)
    assert st.seconds["encode_hash"] == pytest.approx(total * 0.75, rel=0.01)


def test_a_pool_task_is_charged_aside_with_its_switches():
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(2) as pool, stages.collect() as st:
        with stages.stage("commit"):
            futs = [pool.submit(spans.wrap_ctx(
                lambda: (time.sleep(0.01), _spin(0.01)))) for _ in range(4)]
            [f.result() for f in futs]
    wall, cpu, n, sw = st.aside["commit.pool"]
    assert n == 4 and cpu == pytest.approx(0.04, abs=0.02)
    assert wall >= 0.08 and sw >= 4       # a sleep gives the processor up
    assert "commit.pool" not in st.own and st.own["commit"][2] == 1
    assert st.seconds["commit.pool"] == wall     # the merged view


def test_an_object_operation_chains_into_the_request_and_is_nested(served):
    _, _, ol = served
    ol.put_object("b", "lib", io.BytesIO(b"y" * MIB), MIB)
    attribution.reset()
    u = attribution.begin("rid-2", "putobject")
    ol.put_object("b", "lib", io.BytesIO(b"y" * MIB), MIB)
    attribution.finish(u, status=200)
    ol.get_object_bytes("b", "lib")          # no request around: its own
    recs = attribution.between(0.0, time.monotonic() + 1)
    assert [r["api"] for r in recs] == ["putobject", "get"]
    assert {"encode_hash", "commit"} <= \
        set(recs[0]["stages"]) | set(recs[0]["pool"])
    rep = attribution.report()
    assert rep["put"]["count"] == 1 and rep["putobject"]["count"] == 1
    assert rep["put"]["stages"]["encode_hash"]["cpu_seconds_total"] > 0
    assert rep["putobject"]["stages"]["commit.pool"]["count"] == 6


def test_where_the_cpu_clock_is_dear_one_unit_in_n_reads_it(monkeypatch):
    """At a stride of 4 one unit in four reads the CPU clock and the
    switches, its record says so, and it stands for four in the sums."""
    monkeypatch.setattr(stages, "_stride", 4)
    reads = {"n": 0}
    real = stages._thread_time

    def counted():
        reads["n"] += 1
        return real()
    monkeypatch.setattr(stages, "_thread_time", counted)
    for _ in range(8):
        u = attribution.begin("rid", "headobject")
        with stages.stage("meta_pass"):
            _spin(0.005)
        attribution.finish(u, status=200)
    recs = attribution.between(0.0, time.monotonic() + 1)
    assert [r["sampled"] for r in recs].count(True) == 2
    assert reads["n"] == 2 * 4          # two ends and one stage, twice
    for r in recs:
        assert r["stages"]["meta_pass"][0] >= 0.005
        assert (r["cpu_s"] > 0.004) == r["sampled"]
        assert (r["stages"]["meta_pass"][1] > 0.004) == r["sampled"]
    rep = attribution.report()["headobject"]
    assert rep["count"] == 8
    assert rep["cpu_seconds_total"] == pytest.approx(0.005 * 8, rel=0.3)
    assert rep["stages"]["meta_pass"]["cpu_seconds_total"] == \
        pytest.approx(0.005 * 8, rel=0.3)


@pytest.mark.parametrize("read_ns,stride", [
    (280, 1), (590, 1), (610, 2), (5900, 15), (12000, 30), (50000, 64)])
def test_the_stride_follows_what_a_read_of_the_cpu_clock_costs(
        monkeypatch, read_ns, stride):
    """``cpu_stride`` is the cost of one ``time.thread_time`` over
    CPU_READ_BUDGET_NS, at least 1 and at most 64, timed once: the
    sandbox's 0.28-0.59 us read every unit, the chip's host (5.9 us) one
    in 15. On a clock that only the reads advance, so under any load."""
    import types
    now = [0]

    def thread_time():
        now[0] += read_ns
        return now[0] / 1e9
    monkeypatch.setattr(stages, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: now[0], thread_time=thread_time))
    monkeypatch.setattr(stages, "_stride", 0)
    assert stages.cpu_stride() == stride
    calls = now[0]
    assert stages.cpu_stride() == stride and now[0] == calls    # once


def test_the_ring_overwrites_and_counts(monkeypatch):
    monkeypatch.setattr(attribution, "RING", 8)
    monkeypatch.setattr(attribution, "_ring", [None] * 8)
    t0 = time.monotonic()
    for i in range(5):
        attribution.record("get", stages.StageTimes(), 0.001)
    mid = time.monotonic()
    assert attribution.overwritten() == 0
    assert len(attribution.between(t0, time.monotonic() + 1)) == 5
    for i in range(7):
        attribution.record("put", stages.StageTimes(), 0.001)
    assert attribution.overwritten() == 4
    # a span the ring has turned over past gives nothing, never a part
    assert attribution.between(t0, time.monotonic() + 1) is None
    later = attribution.between(mid, time.monotonic() + 1)
    assert later is None or [r["api"] for r in later] == ["put"] * 7
    # the sums since the start outlive the ring
    assert attribution.report()["get"]["count"] == 5


def test_between_cuts_by_end_time():
    marks = []
    for api in ("a", "b", "c"):
        marks.append(time.monotonic())
        time.sleep(0.01)
        attribution.record(api, stages.StageTimes(), 0.001)
    marks.append(time.monotonic() + 0.01)
    apis = lambda lo, hi: [r["api"] for r in attribution.between(lo, hi)]  # noqa: E731
    assert apis(marks[0], marks[3]) == ["a", "b", "c"]
    assert apis(marks[1], marks[2]) == ["b"]
    assert apis(marks[2], marks[3]) == ["c"]
    assert apis(marks[3], marks[3] + 1) == []


def test_off_means_no_record_no_annotation_and_no_clock_read(
        served, monkeypatch):
    _, c, _ = served
    assert c.put_object("b", "off", body=b"z" * 70000).status_code == 200
    time.sleep(0.1)
    os.environ["MINIO_TPU_TIMELINE"] = "0"
    timeline.configure()
    attribution.reset()
    reads = {"n": 0}

    def counted(fn):
        def f(*a, **kw):
            reads["n"] += 1
            return fn(*a, **kw)
        return f
    for name in ("_monotonic", "_thread_time", "switches", "_tracing"):
        monkeypatch.setattr(stages, name, counted(getattr(stages, name)))
    monkeypatch.setattr(stages, "_Annotation", counted(lambda *a: None))
    for r in (c.put_object("b", "off", body=b"z" * 70000),
              c.get_object("b", "off"), c.head_object("b", "off"),
              c.delete_object("b", "off")):
        assert r.status_code in (200, 204)
    time.sleep(0.1)
    assert reads["n"] == 0
    assert attribution.between(0.0, time.monotonic() + 1) == []
    assert attribution.report() == {}
    assert stages.stage("x") is stages._NOOP and stages.pool_task() is None


def test_the_sampler_folds_by_op_and_stage():
    """An open boundary names (op, stage) for the sampler, cross-thread."""
    stop = threading.Event()

    def work():
        profiler.set_task_tag("interactive", "s3.get")
        with stages.collect(stages.StageTimes(api="getobject")):
            with stages.stage("meta_pass"):
                profiler.calibrate_spin(5.0, stop)
        profiler.clear_task_tag()
    t = threading.Thread(target=work, name="stage-spin")
    t.start()
    try:
        agg = profiler.capture_window(0.6, hz=97)
    finally:
        stop.set()
        t.join()
    rep = profiler.report_top(agg, 20)
    assert rep["stages"].get("getobject/meta_pass", 0) > 0, rep["stages"]
    assert any("op:getobject;stage:meta_pass;" in s["stack"] and
               "calibrate_spin" in s["stack"] for s in rep["top_stacks"])


def test_stage_names_are_on_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        u = attribution.begin("rid-3", "getobject")
        with stages.stage("meta_pass"):
            t = threading.Thread(target=spans.wrap_ctx(
                lambda: time.sleep(0.002)), name="pool-like")
            t.start()
            t.join()
        attribution.finish(u, status=200)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name: ev.duration_ns
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "getobject/meta_pass" in names, sorted(names)[:20]
    assert "getobject/meta_pass.pool" in names
    assert names["getobject/meta_pass"] >= 2_000_000     # ns: the sleep
