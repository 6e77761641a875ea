"""Per-bucket analytics plane (minio_tpu/obs/bucketstats.py, ISSUE 18):
bounded-cardinality fold behavior under a bucket storm, live usage
deltas reconciling to zero drift, SLO breach attribution naming the
offending bucket, capacity-projection math on synthetic snapshots, and
the metric rendering staying inside the documented family set."""
import os

import pytest

from minio_tpu.obs import bucketstats as bs
from minio_tpu.obs import slo

NOW = 1_000_000.0  # fixed clock: ring minutes + Window slots determinate


@pytest.fixture(autouse=True)
def _fresh():
    bs.reset()
    slo.reset()
    yield
    bs.reset()
    slo.reset()


def _snapshot(buckets: dict, ts: float) -> dict:
    return {
        "size_total": sum(v["size"] for v in buckets.values()),
        "objects_total": sum(v.get("objects", 0)
                             for v in buckets.values()),
        "last_update": ts,
        "buckets": buckets,
    }


# --- fold / cardinality bound ------------------------------------------------


def test_fold_storm_bounds_cardinality(monkeypatch):
    """4096 distinct buckets against top_n=4: exactly 4 tracked rows,
    everything else folds into _overflow_, and the scrape carries at
    most top_n + 1 distinct bucket label values."""
    monkeypatch.setenv("MINIO_TPU_BUCKETSTATS_TOP_N", "4")
    for i in range(4096):
        bs.record_request(f"b{i:04d}", "getobject", 200, 0.001,
                          bytes_out=64, now=NOW)
    rep = bs.report(now=NOW)
    assert rep["tracked"] == 4
    assert rep["folds"] == 4096 - 4
    assert set(rep["buckets"]) == {"b0000", "b0001", "b0002", "b0003",
                                   bs.OVERFLOW}
    # the overflow row absorbed every folded charge
    assert rep["buckets"][bs.OVERFLOW]["requests_total"] == 4092
    labels = {line.split('bucket="', 1)[1].split('"', 1)[0]
              for line in bs.metric_lines(now=NOW)
              if 'bucket="' in line}
    assert len(labels) <= 5, labels


def test_fold_label_is_the_admission_gate(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_BUCKETSTATS_TOP_N", "2")
    assert bs.fold_label("alpha") == "alpha"
    assert bs.fold_label("beta") == "beta"
    assert bs.fold_label("gamma") == bs.OVERFLOW
    # admit=False never admits, even with free slots
    bs.reset()
    assert bs.fold_label("alpha", admit=False) == bs.OVERFLOW
    # disabled plane folds everything
    monkeypatch.setenv("MINIO_TPU_BUCKETSTATS", "0")
    assert bs.fold_label("alpha") == bs.OVERFLOW


def test_idle_eviction_frees_slot_for_active_tenant(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_BUCKETSTATS_TOP_N", "2")
    monkeypatch.setenv("MINIO_TPU_BUCKETSTATS_FOLD_IDLE_CYCLES", "1")
    bs.record_request("kept", "getobject", 200, 0.001, now=NOW)
    bs.record_request("idle", "getobject", 200, 0.001, now=NOW)
    bs.record_request("newcomer", "getobject", 200, 0.001, now=NOW)
    assert bs.fold_label("newcomer", admit=False) == bs.OVERFLOW
    snap = _snapshot({"kept": {"size": 10, "objects": 1}}, NOW)
    bs.reconcile(snap, now=NOW)                 # both go idle
    bs.record_request("kept", "getobject", 200, 0.001, now=NOW)
    bs.reconcile(_snapshot({"kept": {"size": 10, "objects": 1}},
                           NOW + 60), now=NOW)  # idle evicted, kept not
    rep = bs.report(now=NOW)
    assert "idle" not in rep["buckets"]
    assert rep["evictions"] >= 1
    # the freed slot is re-admittable even though _overflow_ exists
    assert bs.fold_label("newcomer") == "newcomer"


# --- live usage + drift reconcile -------------------------------------------


def test_usage_deltas_move_live_and_drift_reconciles_to_zero():
    bs.on_put("data", 1000)
    bs.on_put("data", 500)
    bs.on_delete("data", 200)
    usage = bs.report(now=NOW)["buckets"]["data"]["usage"]
    assert usage["bytes"] == 1300
    assert usage["objects"] == 1
    assert usage["versions"] == 1
    # scanner says the truth is 1250: drift +50 recorded, then zeroed
    snap = _snapshot({"data": {"size": 1250, "objects": 2,
                               "versions": 2}}, NOW)
    drift = bs.reconcile(snap, now=NOW)
    assert drift["data"] == 50
    usage = bs.report(now=NOW)["buckets"]["data"]["usage"]
    assert usage["bytes"] == 1250
    assert usage["objects"] == 2
    # a second cycle with no traffic in between: zero drift
    bs.record_request("data", "getobject", 200, 0.001, now=NOW)
    drift = bs.reconcile(_snapshot(
        {"data": {"size": 1250, "objects": 2, "versions": 2}},
        NOW + 60), now=NOW)
    assert drift.get("data", 0) == 0
    # delete-marker shape: +1 version, +0 objects, +0 bytes
    bs.on_put("data", 0, versions=1, objects=0)
    usage = bs.report(now=NOW)["buckets"]["data"]["usage"]
    assert usage["versions"] == 3 and usage["objects"] == 2


def test_history_persists_through_config_plane():
    class FakeLayer:
        def __init__(self):
            self.store = {}

        def get_config(self, path):
            return self.store[path]

        def put_config(self, path, data):
            self.store[path] = data

    layer = FakeLayer()
    bs.reconcile(_snapshot({"a": {"size": 100}}, NOW), objlayer=layer,
                 now=NOW)
    assert bs.HISTORY_PATH in layer.store
    # a fresh process (reset) reloads the persisted window
    bs.reset()
    bs.reconcile(_snapshot({"a": {"size": 200}}, NOW + 3600),
                 objlayer=layer, now=NOW)
    assert bs.projection(now=NOW)["24h"]["samples"] == 2


# --- SLO burn attribution ----------------------------------------------------


def test_breach_attribution_names_offending_bucket():
    """One bucket throwing 5xx while others stay clean: the slo report's
    class entry (and the health rollup built from it) names that bucket
    with its share of the bad events."""
    for _ in range(20):
        slo.record("interactive", 0.001, status=503, bucket="victim",
                   now=NOW)
    for _ in range(80):
        slo.record("interactive", 0.001, bucket="innocent", now=NOW)
    rep = slo.report(now=NOW)
    tops = rep["classes"]["interactive"]["top_buckets"]["availability"]
    assert tops[0]["bucket"] == "victim"
    assert tops[0]["bad"] == 20
    assert tops[0]["share"] == pytest.approx(1.0)
    # the health rollup surfaces the same attribution on breach rows
    from minio_tpu.obs import health
    node = {"endpoint": "127.0.0.1:9000", "slo": rep}
    roll = health._rollup([node])
    brow = [b for b in roll["slo_breaches"]
            if b["slo"] == "availability"]
    assert brow and brow[0]["top_bucket"] == "victim"
    assert brow[0]["top_bucket_share"] == pytest.approx(1.0)


def test_top_offenders_share_includes_overflow(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_BUCKETSTATS_TOP_N", "1")
    bs.record_slo("tracked", "interactive", True, False, now=NOW)
    bs.record_slo("folded-a", "interactive", True, False, now=NOW)
    bs.record_slo("folded-b", "interactive", True, False, now=NOW)
    rows = bs.top_offenders("interactive", "availability", 300.0,
                            now=NOW)
    byname = {r["bucket"]: r for r in rows}
    assert byname[bs.OVERFLOW]["bad"] == 2
    assert byname[bs.OVERFLOW]["share"] == pytest.approx(2 / 3,
                                                         abs=1e-3)
    assert byname["tracked"]["share"] == pytest.approx(1 / 3, abs=1e-3)


def test_latency_kind_counts_slow_not_errors():
    bs.record_slo("b", "interactive", False, True, now=NOW)
    bs.record_slo("b", "interactive", True, False, now=NOW)
    lat = bs.top_offenders("interactive", "latency", 300.0, now=NOW)
    avail = bs.top_offenders("interactive", "availability", 300.0,
                             now=NOW)
    assert lat[0]["bad"] == 1 and avail[0]["bad"] == 1


# --- capacity projection -----------------------------------------------------


def test_projection_math_on_synthetic_snapshots():
    """1 GiB of growth across one hour = 24 GiB/day, per bucket and
    cluster-wide; a window with <2 samples projects zero."""
    gib = 1 << 30
    bs.record_request("grow", "putobject", 200, 0.001, now=NOW)
    bs.reconcile(_snapshot({"grow": {"size": gib}}, NOW), now=NOW)
    proj = bs.projection(now=NOW)
    assert proj["1h"]["cluster_gib_per_day"] == 0.0
    bs.record_request("grow", "putobject", 200, 0.001, now=NOW)
    bs.reconcile(_snapshot({"grow": {"size": 2 * gib}}, NOW + 3600),
                 now=NOW)
    proj = bs.projection(now=NOW)
    for win in ("1h", "24h"):
        assert proj[win]["samples"] == 2
        assert proj[win]["cluster_gib_per_day"] == pytest.approx(24.0)
        assert proj[win]["buckets"]["grow"] == pytest.approx(24.0)
    # the same numbers ride the admin report + metric lines
    assert bs.report(now=NOW)["projection"]["1h"][
        "cluster_gib_per_day"] == pytest.approx(24.0)
    assert any("minio_tpu_cluster_growth_gib_per_day" in line
               for line in bs.metric_lines(now=NOW))


def test_projection_out_of_order_cycles_deduped():
    bs.reconcile(_snapshot({"a": {"size": 100}}, NOW), now=NOW)
    bs.reconcile(_snapshot({"a": {"size": 999}}, NOW), now=NOW)
    bs.reconcile(_snapshot({"a": {"size": 999}}, NOW - 60), now=NOW)
    assert bs.projection(now=NOW)["24h"]["samples"] == 1


# --- request charging / api classes -----------------------------------------


def test_request_charging_and_api_taxonomy():
    bs.record_request("b", "getobject", 200, 0.010, ttfb_s=0.002,
                      bytes_out=4096, now=NOW)
    bs.record_request("b", "putobject", 200, 0.020, bytes_in=8192,
                      now=NOW)
    bs.record_request("b", "listobjectsv2", 200, 0.005, now=NOW)
    bs.record_request("b", "deleteobject", 204, 0.003, now=NOW)
    bs.record_request("b", "getobject", 503, 0.001, now=NOW)
    row = bs.report(now=NOW)["buckets"]["b"]
    assert row["requests_total"] == 5
    assert row["errors_5xx"] == 1
    assert row["requests"]["read"]["2xx"] == 1
    assert row["requests"]["read"]["5xx"] == 1
    assert row["requests"]["write"]["2xx"] == 1
    assert row["requests"]["list"]["2xx"] == 1
    assert row["requests"]["delete"]["2xx"] == 1
    assert row["bytes_in"] == 8192 and row["bytes_out"] == 4096
    assert row["latency"]["read"]["count"] == 2
    assert row["latency"]["read"]["ttfb_p50_s"] > 0
    for api, want in (("headobject", "read"), ("copyobject", "write"),
                      ("completemultipartupload", "write"),
                      ("abortmultipartupload", "delete"),
                      ("listmultipartuploads", "list"),
                      ("selectobjectcontent", "write"),
                      ("assumerole", "other")):
        assert bs.api_class(api) == want, api


# --- rendering hygiene -------------------------------------------------------


def test_metric_lines_families_documented_and_well_formed():
    """Every family the renderer can emit appears in
    docs/observability.md (the GL004 contract holds for the RENDERED
    lines, not just the source literals), is snake_case and
    minio_tpu_-prefixed, and every # TYPE has samples."""
    import re
    bs.record_request("doc", "getobject", 200, 0.01, ttfb_s=0.001,
                      bytes_in=1, bytes_out=1, now=NOW)
    bs.record_slo("doc", "interactive", True, False, now=NOW)
    bs.reconcile(_snapshot({"doc": {"size": 1 << 30}}, NOW), now=NOW)
    bs.reconcile(_snapshot({"doc": {"size": 2 << 30}}, NOW + 3600),
                 now=NOW)
    lines = bs.metric_lines(now=NOW)
    docs = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                             "observability.md")).read()
    fam_re = re.compile(r"^[a-z][a-z0-9_]*$")
    families = set()
    for line in lines:
        if line.startswith("# TYPE "):
            families.add(line.split()[2])
            continue
        name = line.split("{", 1)[0].split(" ", 1)[0]
        families.add(re.sub(r"_(bucket|sum|count)$", "", name))
    for fam in families:
        assert fam.startswith("minio_tpu_"), fam
        assert fam_re.match(fam), fam
        assert fam in docs, f"{fam} missing from docs/observability.md"
    # samples exist for each declared type (no orphan TYPE lines)
    declared = {line.split()[2] for line in lines
                if line.startswith("# TYPE ")}
    sampled = {line.split("{", 1)[0].split(" ", 1)[0] for line in lines
               if not line.startswith("#")}
    assert declared <= sampled, declared - sampled


def test_metrics_group_scrape_carries_bucket_families():
    """The bucket group is registered in the exposition: a node scrape
    renders the registry against a bare server stand-in (server-bound
    groups fail shielded and render empty; the bucket group is global
    state and must still show)."""
    from minio_tpu.obs import metrics as mx
    bs.record_request("scraped", "getobject", 200, 0.01, now=NOW)

    class _Srv:  # bare object() is not weak-referenceable
        pass

    text = mx.render_prometheus(_Srv(), scope="node").decode()
    assert "minio_tpu_bucket_stats_tracked" in text
    assert 'bucket="scraped"' in text


def test_concurrent_tenants_over_http_stay_bounded(tmp_path, monkeypatch):
    """More tenants than the cap, charged by concurrent requests through
    a live server: the registry tracks at most top_n rows, the scrape
    carries at most top_n + 1 bucket label values (the fold row), and
    no request is lost between the rows."""
    import threading
    import time

    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.server import S3Server
    from minio_tpu.storage import XLStorage
    from s3client import S3Client
    monkeypatch.setenv("MINIO_TPU_BUCKETSTATS_TOP_N", "4")
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=2)
    srv = S3Server(obj, "127.0.0.1", 0, access_key="bsak",
                   secret_key="bssecret1")
    srv.start_background()
    tenants = [f"tenant-{i:02d}" for i in range(12)]
    codes: list[int] = []

    def client(wid: int) -> None:
        c = S3Client(srv.endpoint(), "bsak", "bssecret1")
        for b in tenants[wid::4]:
            codes.append(c.put_bucket(b).status_code)
            for j in range(3):
                codes.append(c.put_object(b, f"o{j}", b"x" * 512)
                             .status_code)
                codes.append(c.get_object(b, f"o{j}").status_code)
    try:
        ths = [threading.Thread(target=client, args=(w,), daemon=True,
                                name=f"tenant-client-{w}")
               for w in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        assert codes and set(codes) == {200}, sorted(set(codes))

        def charged():
            return sum(r["requests_total"]
                       for r in bs.report()["buckets"].values())
        # a request is charged after its reply has gone out
        deadline = time.monotonic() + 10
        while charged() != len(codes) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert charged() == len(codes)
        rep = bs.report()
        assert rep["tracked"] <= 4 and rep["folds"] > 0, rep
        import requests
        text = requests.get(srv.endpoint() + "/minio/v2/metrics/cluster",
                            timeout=30).text
        labels = {line.split('bucket="', 1)[1].split('"', 1)[0]
                  for line in text.splitlines()
                  if line.startswith("minio_tpu_bucket_")
                  and 'bucket="' in line
                  # the replication monitor's family: process-global,
                  # named from bucket configs, not from requests
                  and not line.startswith("minio_tpu_bucket_bandwidth_")}
        assert bs.OVERFLOW in labels and len(labels) <= 5, labels
    finally:
        srv.shutdown()
