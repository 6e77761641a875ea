"""Flight recorder + attribution tests (ISSUE 9): the ring's bounded
drop-oldest behavior and its drop counter, Chrome-trace export shape
(pid = device lane, monotonic ts), dispatch-plane event emission
(enqueue → plan → flush_start/flush_end → complete), the CPU-salvage
reroute event under an injected kernel fault, the hand-computed
attribution fixture, the promoted kernel/heal histograms with
OpenMetrics exemplars, the stale-between-mutations gauge fix, and the
admin timeline endpoint + madmin client."""
import io
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from minio_tpu.obs import attribution, stages, timeline  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Each test starts with an empty default-config recorder and empty
    attribution aggregates; env overrides are cleared afterwards."""
    for k in ("MINIO_TPU_TIMELINE", "MINIO_TPU_TIMELINE_RING",
              "MINIO_TPU_TIMELINE_SAMPLE"):
        os.environ.pop(k, None)
    timeline.configure()
    timeline.reset()
    attribution.reset()
    yield
    for k in ("MINIO_TPU_TIMELINE", "MINIO_TPU_TIMELINE_RING",
              "MINIO_TPU_TIMELINE_SAMPLE"):
        os.environ.pop(k, None)
    timeline.configure()
    timeline.reset()
    attribution.reset()


# --------------------------------------------------------------------------
# ring mechanics


def test_ring_overflow_drops_oldest_and_counts():
    os.environ["MINIO_TPU_TIMELINE_RING"] = "64"
    timeline.configure()
    timeline.reset()
    for i in range(100):
        timeline.record("plan", op="encode", n=i)
    evs = timeline.snapshot()
    assert len(evs) == 64
    # oldest dropped: the survivors are exactly the newest 64
    assert [e["n"] for e in evs] == list(range(36, 100))
    assert timeline.dropped_total() == 36
    assert timeline.events_total() == 100


def test_ring_resize_via_configure():
    os.environ["MINIO_TPU_TIMELINE_RING"] = "128"
    timeline.configure()
    timeline.reset()
    for i in range(10):
        timeline.record("plan", n=i)
    assert len(timeline.snapshot()) == 10
    assert timeline.dropped_total() == 0


def test_disable_is_a_noop():
    os.environ["MINIO_TPU_TIMELINE"] = "0"
    timeline.configure()
    timeline.record("plan", n=1)
    timeline.record("flush_start", op="encode", flush_id=1)
    assert timeline.snapshot() == []
    assert not timeline.enabled()


def test_sample_zero_sheds_whole_sampled_class():
    """sample=0 means NO high-frequency events (not all of them) —
    structural events keep recording."""
    os.environ["MINIO_TPU_TIMELINE_SAMPLE"] = "0"
    timeline.configure()
    timeline.reset()
    for _ in range(20):
        timeline.record("enqueue", op="encode")
    timeline.record("plan", n=1)
    kinds = [e["type"] for e in timeline.snapshot()]
    assert kinds == ["plan"]


def test_sampling_stride_thins_high_frequency_events_only():
    os.environ["MINIO_TPU_TIMELINE_SAMPLE"] = "0.25"
    timeline.configure()
    timeline.reset()
    for _ in range(40):
        timeline.record("enqueue", op="encode")   # sampled type
    for i in range(10):
        timeline.record("plan", n=i)              # structural type
    evs = timeline.snapshot()
    kinds = [e["type"] for e in evs]
    assert kinds.count("plan") == 10              # never sampled away
    assert 5 <= kinds.count("enqueue") <= 15      # ~40/4


def test_dropped_counter_rides_the_metrics_exposition():
    os.environ["MINIO_TPU_TIMELINE_RING"] = "64"
    timeline.configure()
    timeline.reset()
    for i in range(80):
        timeline.record("plan", n=i)
    from minio_tpu.obs.metrics import _g_device
    text = "\n".join(_g_device(None))
    assert "minio_tpu_timeline_dropped_total 16" in text
    assert "minio_tpu_timeline_events_total 80" in text


# --------------------------------------------------------------------------
# Chrome-trace export


def test_chrome_export_schema_lanes_and_ordering():
    fid1 = timeline.next_flush_id()
    fid2 = timeline.next_flush_id()
    timeline.record("enqueue", op="encode", bytes=1024)
    timeline.record("flush_start", op="encode", lane=("dev0", "dev1"),
                    flush_id=fid1, batch=4, capacity=8, bytes=4096,
                    route="device")
    timeline.record("flush_end", op="encode", lane=("dev0", "dev1"),
                    flush_id=fid1, batch=4, capacity=8, bytes=4096,
                    route="device", dur=0.01)
    timeline.record("flush_start", op="encode", lane=("cpu",),
                    flush_id=fid2, batch=2, capacity=8, bytes=2048,
                    route="cpu")
    timeline.record("flush_end", op="encode", lane=("cpu",),
                    flush_id=fid2, batch=2, capacity=8, bytes=2048,
                    route="cpu", dur=0.005)
    out = timeline.export_chrome()
    doc = json.loads(json.dumps(out))     # schema-valid JSON round-trip
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert {"lane:dev0", "lane:dev1", "lane:cpu"} <= names
    # one pid per lane, distinct
    pids = {e["args"]["name"]: e["pid"] for e in meta}
    assert len(set(pids.values())) == len(pids)
    # the paired device flush is ONE complete event per occupied lane
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["pid"] for e in xs if e["args"]["route"] == "device"} == \
        {pids["lane:dev0"], pids["lane:dev1"]}
    for e in xs:
        assert e["dur"] > 0
    # instants exist (the enqueue) and timestamps are monotonic
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert any(e["ph"] == "i" and e["name"].startswith("enqueue")
               for e in evs)


def test_chrome_export_orphan_start_is_instant():
    fid = timeline.next_flush_id()
    timeline.record("flush_start", op="encode", lane=("cpu",),
                    flush_id=fid, batch=1, capacity=8, bytes=1,
                    route="cpu")
    evs = timeline.export_chrome()["traceEvents"]
    assert not [e for e in evs if e["ph"] == "X"]
    assert any(e["ph"] == "i" and e["name"].startswith("flush_start")
               for e in evs)


# --------------------------------------------------------------------------
# utilization accounting


def test_lane_accounting_is_thread_safe():
    """Concurrent flush_end callbacks on the shared cpu lane must not
    lose busy seconds to the epoch check-then-reset race."""
    import threading as th
    N, PER = 8, 50

    def worker(seed):
        for i in range(PER):
            fid = timeline.next_flush_id()
            timeline.record("flush_end", op="encode", lane=("cpu",),
                            flush_id=fid, batch=1, capacity=8,
                            bytes=10, route="cpu", dur=0.001)
    threads = [th.Thread(target=worker, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lane = timeline.utilization()["lanes"]["cpu"]
    assert lane["flushes"] == N * PER
    assert lane["items"] == N * PER
    assert lane["busy_seconds_total"] == pytest.approx(N * PER * 0.001)


def test_lane_utilization_integrates_flushes():
    for i in range(4):
        fid = timeline.next_flush_id()
        timeline.record("flush_start", op="encode", lane=("dev0",),
                        flush_id=fid, batch=4, capacity=8, bytes=100,
                        route="device")
        timeline.record("flush_end", op="encode", lane=("dev0",),
                        flush_id=fid, batch=4, capacity=8, bytes=100,
                        route="device", dur=0.25)
    util = timeline.utilization()
    lane = util["lanes"]["dev0"]
    assert lane["flushes"] == 4
    assert lane["items"] == 16
    assert lane["bytes"] == 400
    assert lane["busy_seconds_total"] == pytest.approx(1.0)
    # 1 busy second inside a 60 s window
    assert lane["busy_ratio"] == pytest.approx(1 / 60, rel=0.25)
    assert lane["batch_fill_avg"] == pytest.approx(0.5)
    assert lane["batch_fill_hist"]["le_0.5"] == 4


def test_overlong_flush_clamps_to_window():
    """A flush whose dur exceeds the 60 s window must not wrap the busy
    ring and zero the slots it just filled — a saturated lane would
    read near-idle."""
    fid = timeline.next_flush_id()
    timeline.record("flush_end", op="encode", lane=("dev0",),
                    flush_id=fid, batch=1, capacity=8, bytes=10,
                    route="device", dur=500.0)
    lane = timeline.utilization()["lanes"]["dev0"]
    assert lane["busy_ratio"] == pytest.approx(1.0)
    assert lane["busy_seconds_total"] == pytest.approx(500.0)


def test_queue_depth_distribution():
    for d in (0, 0, 1, 2, 100):
        timeline.note_queue_depth(d)
    util = timeline.utilization()["queue_depth"]
    assert util["samples"] == 5
    assert util["last"] == 100
    assert util["p50"] <= 2
    assert util["p99"] >= 100


# --------------------------------------------------------------------------
# dispatch-plane emission


def test_dispatch_emits_event_chain(monkeypatch):
    from minio_tpu.ops.rs_jax import get_codec, pack_shards
    from minio_tpu.runtime.dispatch import DispatchQueue
    monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", "cpu")
    q = DispatchQueue(max_batch=8, max_delay=0.001)
    codec = get_codec(4, 2)
    d = np.random.default_rng(0).integers(0, 256, (4, 1024), np.uint8)
    futs = [q.encode(codec, pack_shards(d)) for _ in range(6)]
    for f in futs:
        f.result(timeout=10)
    q.stop()
    evs = timeline.snapshot()
    kinds = {e["type"] for e in evs}
    assert {"enqueue", "plan", "flush_start", "flush_end",
            "complete"} <= kinds
    flush_ends = [e for e in evs if e["type"] == "flush_end"]
    assert all(e["lanes"] == ["cpu"] and e["op"] == "encode"
               and e["route"] == "cpu" for e in flush_ends)
    # paired: every end has a start with the same flush_id
    starts = {e["flush_id"] for e in evs if e["type"] == "flush_start"}
    assert all(e["flush_id"] in starts for e in flush_ends)
    # utilization integrated the cpu lane
    assert timeline.utilization()["lanes"]["cpu"]["flushes"] >= 1


def test_chaos_flush_shows_salvage_event():
    """The acceptance-criterion chaos case: a fault-injected device
    flush reroutes to the CPU executor and the timeline records the
    salvage event — results stay correct."""
    from minio_tpu import fault
    from minio_tpu.ops.rs_jax import get_codec, pack_shards, unpack_shards
    from minio_tpu.runtime.dispatch import DispatchQueue
    rid = fault.arm("kernel:device:encode:error(FaultyDisk)")
    try:
        q = DispatchQueue(max_batch=8, max_delay=0.001)
        codec = get_codec(4, 2)
        d = np.random.default_rng(1).integers(0, 256, (4, 1024), np.uint8)
        got = unpack_shards(q.encode(codec, pack_shards(d)).result(
            timeout=10))
        np.testing.assert_array_equal(got, codec.encode(d))
        q.stop()
    finally:
        fault.disarm(rid)
    evs = timeline.snapshot()
    sal = [e for e in evs if e["type"] == "salvage"]
    assert sal and sal[0]["reason"] == "injected"
    assert sal[0]["op"] == "encode"
    # the salvage still produced a truthful CPU flush pair
    assert any(e["type"] == "flush_end" and e["lanes"] == ["cpu"]
               for e in evs)


# --------------------------------------------------------------------------
# attribution


def test_attribution_matches_hand_computed_fixture():
    """Shares are exact ratios of the cumulative sums; p50/p99 come
    from the log-bucketed last-minute window, so they match the fixture
    within the documented <=20% quantization."""
    for _ in range(10):
        st = stages.StageTimes()
        st.add("encode_hash", 0.010)
        st.add("shard_write", 0.030)
        attribution.record("put", st, wall_s=0.050)
    rep = attribution.report()["put"]
    assert rep["count"] == 10
    assert rep["wall_seconds_total"] == pytest.approx(0.5)
    eh = rep["stages"]["encode_hash"]
    sw = rep["stages"]["shard_write"]
    assert eh["seconds_total"] == pytest.approx(0.10)
    assert eh["share_of_wall"] == pytest.approx(0.2)
    assert sw["share_of_wall"] == pytest.approx(0.6)
    # identical samples: p50 == p99, inside one log bucket of the truth
    assert eh["p50_s"] == pytest.approx(0.010, rel=0.25)
    assert eh["p99_s"] == pytest.approx(0.010, rel=0.25)
    assert sw["p50_s"] == pytest.approx(0.030, rel=0.25)


def test_attribution_chains_to_outer_collector():
    """A caller may arm an outer collector of its own; the always-on
    attribution must feed it, not starve it."""
    with stages.collect() as outer:
        with attribution.observed("put"):
            inner = stages.active()
            assert inner is not outer
            inner.add("body_read", 0.5)
    assert outer.seconds["body_read"] == pytest.approx(0.5)
    assert attribution.report()["put"]["stages"]["body_read"][
        "seconds_total"] == pytest.approx(0.5)


def test_attribution_covers_put_get_heal_e2e(tmp_path):
    """Real object traffic populates standing stage breakdowns for all
    three ops — including a degraded heal that actually rebuilds."""
    import shutil

    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.storage import XLStorage
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(6)]
    ol = ErasureObjects(disks, default_parity=2)
    ol.make_bucket("b")
    body = np.random.default_rng(2).integers(
        0, 256, 1 << 20, dtype=np.uint8).tobytes()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    assert ol.get_object_bytes("b", "o") == body
    # lose one disk's shard dir -> the heal rebuilds through
    # erasure_heal and charges shard_read/rebuild/shard_write
    shutil.rmtree(str(tmp_path / "d0" / "b" / "o"), ignore_errors=True)
    ol.heal_object("b", "o")
    rep = attribution.report()
    assert rep["put"]["stages"]["encode_hash"]["seconds_total"] > 0
    assert rep["put"]["stages"]["shard_write"]["seconds_total"] > 0
    assert rep["get"]["count"] >= 1 and rep["get"]["stages"]
    assert rep["heal.object"]["stages"].get("rebuild", {}).get(
        "seconds_total", 0) > 0
    assert rep["heal.object"]["stages"]["shard_write"]["seconds_total"] > 0


def test_attribution_disabled_with_recorder():
    os.environ["MINIO_TPU_TIMELINE"] = "0"
    timeline.configure()
    with attribution.observed("put") as st:
        assert st is None
    assert attribution.report() == {}


# --------------------------------------------------------------------------
# promoted histograms + exemplars (satellite 1)


def test_kernel_histogram_families_and_gauges_coexist():
    from minio_tpu.obs import latency as lat
    from minio_tpu.obs.metrics import _g_kernel
    lat.reset_window("kernel", op="encode")
    for v in (0.001, 0.002, 0.004, 0.2):
        lat.observe("kernel", v, 1 << 20, op="encode")
    text = "\n".join(_g_kernel(None))
    # legacy gauge names intact (dashboard compatibility)
    assert 'minio_tpu_kernel_op_latency_seconds{op="encode",' in text
    # real histogram series for the same window
    assert 'minio_tpu_kernel_op_duration_seconds_bucket{op="encode",' \
        in text
    assert 'minio_tpu_kernel_op_duration_seconds_count{op="encode"} 4' \
        in text
    assert 'le="+Inf"' in text
    # heal-shard histogram twin always present
    assert "minio_tpu_heal_shard_duration_seconds_count" in text
    # cumulative: counts never decrease along the le sequence
    import re
    cums = [int(m.group(1)) for m in re.finditer(
        r'minio_tpu_kernel_op_duration_seconds_bucket\{op="encode",'
        r'le="[^"]+"\} (\d+)', text)]
    assert cums and cums == sorted(cums) and cums[-1] == 4


def test_heal_histogram_carries_fetchable_exemplar():
    from minio_tpu.obs import latency as lat
    from minio_tpu.obs import spans
    from minio_tpu.obs.metrics import _g_kernel
    tid = "e" * 32
    spans.store().put({"trace_id": tid, "time": 0.0, "name": "t",
                       "duration_s": 1.0, "spans": []})
    lat.reset_window("kernel", op="heal_shard")
    lat.observe("kernel", 0.5, 1 << 20, trace_id=tid, op="heal_shard")
    text = "\n".join(_g_kernel(None))
    assert f'# {{trace_id="{tid}"}} 0.5' in text
    # NOT advertised when the trace is no longer fetchable
    spans.store().clear()
    text = "\n".join(_g_kernel(None))
    assert "# {trace_id=" not in text


def test_exemplars_only_on_openmetrics_negotiation():
    """Classic text-format scrapes must NOT carry exemplar suffixes (a
    0.0.4 parser reads the trailing '#' as an invalid timestamp and
    fails the whole scrape); OpenMetrics-negotiated renders keep them
    and terminate with # EOF."""
    from minio_tpu.obs import latency as lat
    from minio_tpu.obs import spans
    from minio_tpu.obs.metrics import render_prometheus

    class _Srv:
        obj = None
    tid = "f" * 32
    spans.store().put({"trace_id": tid, "time": 0.0, "name": "t",
                       "duration_s": 1.0, "spans": []})
    lat.reset_window("kernel", op="heal_shard")
    lat.observe("kernel", 0.5, 1 << 20, trace_id=tid, op="heal_shard")
    try:
        classic = render_prometheus(_Srv(), "node").decode()
        assert "# {trace_id=" not in classic
        assert not classic.rstrip().endswith("# EOF")
        # the histogram itself still renders in classic form
        assert "minio_tpu_heal_shard_duration_seconds_bucket" in classic
        om = render_prometheus(_Srv(), "node", openmetrics=True).decode()
        assert f'# {{trace_id="{tid}"}} 0.5' in om
        assert om.rstrip().endswith("# EOF")
    finally:
        spans.store().clear()
        lat.reset_window("kernel", op="heal_shard")


def test_report_surfaces_wall_percentiles():
    st = stages.StageTimes()
    st.add("decode", 0.01)
    attribution.record("get", st, wall_s=0.040)
    rep = attribution.report()["get"]
    assert rep["wall_p50_s"] == pytest.approx(0.040, rel=0.25)
    assert rep["wall_p99_s"] == pytest.approx(0.040, rel=0.25)
    from minio_tpu.obs.metrics import _attribution_lines
    text = "\n".join(_attribution_lines())
    assert 'minio_tpu_stage_latency_seconds{op="get",stage="wall",' \
        in text


def test_exemplar_lines_keep_exposition_well_formed(tmp_path):
    """The full annotated exposition stays parseable with exemplar
    suffixes and histogram families present."""
    from minio_tpu.obs.metrics import _annotate
    out = _annotate([
        "# TYPE minio_tpu_x_duration_seconds histogram",
        'minio_tpu_x_duration_seconds_bucket{le="0.1"} 1 '
        '# {trace_id="abc"} 0.05',
        'minio_tpu_x_duration_seconds_bucket{le="+Inf"} 1',
        "minio_tpu_x_duration_seconds_sum 0.05",
        "minio_tpu_x_duration_seconds_count 1",
    ])
    assert "# TYPE minio_tpu_x_duration_seconds histogram" in out
    # exactly one TYPE line for the family
    assert sum(1 for ln in out
               if ln.startswith("# TYPE minio_tpu_x_duration")) == 1


# --------------------------------------------------------------------------
# stale-between-mutations gauge fix (satellite 2)


def test_queue_depth_and_bufpool_gauges_sample_at_scrape_time():
    """The collector callback bypasses group caching: a mutation right
    after a scrape is visible on the very next scrape."""
    from minio_tpu.obs.metrics import _c_live_gauges
    from minio_tpu.runtime import bufpool, dispatch
    from minio_tpu.runtime.dispatch import DispatchQueue
    pool = bufpool.BufferPool(min_pooled=1024)
    old_pool, bufpool._global = bufpool._global, pool
    q = DispatchQueue(max_batch=8, max_delay=5.0)
    old_q, dispatch._global = dispatch._global, q
    try:
        arr = pool.get(4096)
        text = "\n".join(_c_live_gauges(None))
        assert "minio_tpu_pipeline_bufpool_retained_bytes 0" in text
        assert "minio_tpu_dispatch_queue_depth 0" in text
        pool.put(arr)    # mutation between scrapes
        text = "\n".join(_c_live_gauges(None))
        assert "minio_tpu_pipeline_bufpool_retained_bytes 4096" in text
    finally:
        bufpool._global = old_pool
        dispatch._global = old_q
        q.stop()


def test_render_prometheus_includes_collectors_and_attribution():
    """Full render path: collector families render without a server
    wired, and ?attribution=1 appends the stage families."""
    from minio_tpu.obs.metrics import render_prometheus

    class _Srv:      # minimal server double for the group generators
        obj = None
    st = stages.StageTimes()
    st.add("decode", 0.01)
    attribution.record("get", st, wall_s=0.02)
    text = render_prometheus(_Srv(), "node").decode()
    assert "minio_tpu_timeline_events_total" in text
    assert "minio_tpu_stage_latency_seconds" not in text
    text = render_prometheus(_Srv(), "node", attribution=True).decode()
    assert ('minio_tpu_stage_share_of_wall{op="get",stage="decode"} '
            "0.5") in text
    assert "# TYPE minio_tpu_stage_latency_seconds gauge" in text


# --------------------------------------------------------------------------
# admin endpoint + madmin client


AK, SK = "tlak", "tlsecret1"


@pytest.fixture
def srv(tmp_path):
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.server import S3Server
    from minio_tpu.storage import XLStorage
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=2)
    server = S3Server(obj, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    server.start_background()
    yield server
    server.shutdown()


def test_admin_timeline_endpoint_and_madmin(srv):
    from minio_tpu.madmin import AdminClient
    from s3client import S3Client
    c = S3Client(srv.endpoint(), AK, SK)
    c.request("PUT", "/tb")
    c.request("PUT", "/tb/o", body=b"x" * (1 << 16))
    c.request("GET", "/tb/o")
    adm = AdminClient(srv.endpoint(), AK, SK)
    out = adm.timeline(attribution=True)
    assert out["enabled"] is True and out["ring"] >= 64
    assert "events" in out and "utilization" in out
    assert out["attribution"]["put"]["count"] >= 1
    assert out["attribution"]["get"]["count"] >= 1
    # incremental poll: since=now yields nothing older
    out2 = adm.timeline(since=out["now"])
    assert all(e["ts"] > out["now"] for e in out2["events"])
    # chrome export round-trips and names lanes
    chrome = adm.timeline(fmt="chrome")
    assert "traceEvents" in chrome
    assert any(e.get("ph") == "M" for e in chrome["traceEvents"])
    # metrics endpoint grows stage families only on ?attribution=1
    r = c.request("GET", "/minio/v2/metrics/node",
                  query={"attribution": "1"})
    assert r.status_code == 200
    assert "minio_tpu_stage_op_wall_seconds_total" in r.text
    r = c.request("GET", "/minio/v2/metrics/node")
    assert "minio_tpu_stage_op_wall_seconds_total" not in r.text
    # an OM-negotiating Accept header must NOT flip the exposition (the
    # classic counter naming fails strict OM parsers — modern Prometheus
    # sends this Accept by default); only explicit ?openmetrics=1 does
    r = c.request("GET", "/minio/v2/metrics/node", headers={
        "Accept": "application/openmetrics-text;version=1.0.0,"
                  "text/plain;version=0.0.4;q=0.5"})
    assert r.headers["Content-Type"].startswith("text/plain")
    assert "# EOF" not in r.text and "# {trace_id=" not in r.text
    r = c.request("GET", "/minio/v2/metrics/node",
                  query={"openmetrics": "1"})
    assert r.headers["Content-Type"].startswith(
        "application/openmetrics-text")
    assert r.text.rstrip().endswith("# EOF")
