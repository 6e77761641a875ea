"""SLO plane (minio_tpu/obs/slo.py): objective seeding/override,
window math and burn rates with faked clocks, breach verdicts, the
metrics family, the s3api request feed, and the admin endpoints."""
import time

import pytest

from minio_tpu.obs import slo

AK, SK = "sloadmin", "sloadmin-secret"


@pytest.fixture(autouse=True)
def _fresh():
    slo.reset()
    yield
    slo.reset()


# --- objectives --------------------------------------------------------------


def test_objective_seeded_from_qos_budget(monkeypatch):
    """Latency thresholds default to the qos.budget class budgets, so
    the SLO plane and the dispatch scheduler judge 'slow' identically;
    an explicit slo key overrides the seed."""
    monkeypatch.setenv("MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS", "250")
    obj = slo.objective("interactive")
    assert obj["latency_threshold_s"] == pytest.approx(0.25)
    assert obj["latency_threshold_source"] == "qos.budget"
    # control seeds from the interactive budget (same request plane)
    assert slo.objective("control")["latency_threshold_s"] == \
        pytest.approx(0.25)
    monkeypatch.setenv("MINIO_TPU_SLO_INTERACTIVE_LATENCY_MS", "42")
    obj = slo.objective("interactive")
    assert obj["latency_threshold_s"] == pytest.approx(0.042)
    assert obj["latency_threshold_source"] == "slo"
    assert slo.objective("background")["latency_threshold_s"] == \
        pytest.approx(5.0)


def test_objective_targets_overridable(monkeypatch):
    assert slo.objective("interactive")["availability"] == \
        pytest.approx(0.999)
    monkeypatch.setenv("MINIO_TPU_SLO_INTERACTIVE_AVAILABILITY", "95")
    monkeypatch.setenv("MINIO_TPU_SLO_INTERACTIVE_LATENCY_TARGET", "90")
    obj = slo.objective("interactive")
    assert obj["availability"] == pytest.approx(0.95)
    assert obj["latency_target"] == pytest.approx(0.90)


# --- window math / burn rates ------------------------------------------------


def test_burn_rates_and_ratios_faked_clock():
    """99 ok + 1 error = 0.99 availability = burn 10 against a 99.9%
    objective; 1 slow good request out of 99 burns latency budget
    ~1.01/1% = ~1.01x... both windows see the same data here."""
    now = 1_000_000.0
    for _ in range(98):
        slo.record("interactive", 0.01, now=now)
    slo.record("interactive", 0.01, status=503, now=now)
    slo.record("interactive", 3.0, trace_id="tr-slow", now=now)
    rep = slo.report(now=now)
    ent = rep["classes"]["interactive"]
    for win in ("5m", "1h"):
        w = ent["windows"][win]
        assert w["requests"] == 100
        assert w["errors"] == 1
        assert w["slow"] == 1
        assert w["availability"] == pytest.approx(0.99)
        # burn = (1 - 0.99) / (1 - 0.999) = 10
        assert w["availability_burn"] == pytest.approx(10.0, rel=1e-3)
        # latency: 1 slow / 99 good vs 1% budget
        assert w["latency_burn"] == pytest.approx(
            (1 / 99) / 0.01, rel=1e-3)
    # burn 10 < default alert 14.4 in both windows: no breach
    assert ent["breach"] == {"availability": False, "latency": False}
    assert ent["worst_breach"]["trace_id"] == "tr-slow"
    assert ent["worst_breach"]["seconds"] == pytest.approx(3.0)
    # not in the slow-trace store -> not advertised as fetchable
    assert ent["worst_breach"]["stored"] is False


def test_breach_needs_both_windows_burning():
    """Errors older than the fast window keep the slow window burning
    but clear the fast one — multiwindow alerting's whole point: the
    breach verdict drops once 'now' recovers."""
    now = 2_000_000.0
    for _ in range(8):
        slo.record("interactive", 0.01, status=500, now=now)
    for _ in range(8):
        slo.record("interactive", 0.01, now=now)
    rep = slo.report(now=now)
    ent = rep["classes"]["interactive"]
    assert ent["windows"]["5m"]["availability_burn"] > 14.4
    assert ent["breach"]["availability"] is True
    # 6 minutes later: fast window expired, slow window still burns
    later = now + 360
    rep = slo.report(now=later)
    ent = rep["classes"]["interactive"]
    assert ent["windows"]["5m"]["requests"] == 0
    assert ent["windows"]["5m"]["availability_burn"] == 0.0
    assert ent["windows"]["1h"]["errors"] == 8
    assert ent["windows"]["1h"]["availability_burn"] > 14.4
    assert ent["breach"]["availability"] is False


def test_breach_needs_minimum_traffic():
    """A single 5xx on an otherwise idle class burns at 1000x but must
    NOT page — the breach verdict carries a minimum-traffic floor
    (BREACH_MIN_REQUESTS in the fast window)."""
    now = 2_500_000.0
    slo.record("interactive", 0.01, status=500, now=now)
    ent = slo.report(now=now)["classes"]["interactive"]
    assert ent["windows"]["5m"]["availability_burn"] > 14.4
    assert ent["breach"]["availability"] is False
    # the same error RATE with real traffic does page
    for _ in range(5):
        slo.record("interactive", 0.01, status=500, now=now)
    for _ in range(6):
        slo.record("interactive", 0.01, now=now)
    ent = slo.report(now=now)["classes"]["interactive"]
    assert ent["windows"]["5m"]["requests"] >= slo.BREACH_MIN_REQUESTS
    assert ent["breach"]["availability"] is True


def test_4xx_counts_as_good():
    now = 3_000_000.0
    slo.record("interactive", 0.01, status=404, now=now)
    w = slo.report(now=now)["classes"]["interactive"]["windows"]["5m"]
    assert w["requests"] == 1 and w["errors"] == 0
    assert w["availability"] == 1.0


def test_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_SLO", "0")
    slo.record("interactive", 0.01, now=4_000_000.0)
    monkeypatch.setenv("MINIO_TPU_SLO", "1")
    w = slo.report(
        now=4_000_000.0)["classes"]["interactive"]["windows"]["5m"]
    assert w["requests"] == 0


def test_unknown_class_ignored():
    slo.record("martian", 0.01, now=5_000_000.0)
    assert "martian" not in slo.report()["classes"]


# --- metrics family ----------------------------------------------------------


def test_slo_metric_family_renders():
    from minio_tpu.obs.metrics import _g_slo
    now = 6_000_000.0
    slo.record("interactive", 0.01, now=now)
    slo.record("interactive", 0.01, status=500, now=now)
    lines = _g_slo(None)
    text = "\n".join(lines)
    for fam in ("minio_tpu_slo_availability_objective",
                "minio_tpu_slo_latency_threshold_seconds",
                "minio_tpu_slo_window_requests",
                "minio_tpu_slo_availability_ratio",
                "minio_tpu_slo_burn_rate",
                "minio_tpu_slo_breach"):
        assert fam in text, fam
    assert 'slo="availability"' in text and 'slo="latency"' in text
    assert 'window="5m"' in text and 'window="1h"' in text
    for cls in slo.CLASSES:
        assert f'class="{cls}"' in text


# --- request-plane feed + admin endpoints ------------------------------------


@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.server import S3Server
    from minio_tpu.storage import XLStorage
    tmp = tmp_path_factory.mktemp("slosrv")
    disks = [XLStorage(str(tmp / f"d{i}")) for i in range(6)]
    obj = ErasureObjects(disks, default_parity=2)
    server = S3Server(obj, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    server.start_background()
    yield server
    server.shutdown()


def _settled_report(adm, want, timeout=20.0):
    """The SLO report once ``want(report)`` holds. The server feeds the
    windows in its handler's ``finally``, after the response has gone
    out, so a client that has its answer may still be ahead of the
    count it caused."""
    deadline = time.monotonic() + timeout
    while True:
        rep = adm.slo_report()
        if want(rep) or time.monotonic() > deadline:
            return rep
        time.sleep(0.02)


def _requests(rep, cls):
    return rep["classes"][cls]["windows"]["5m"]["requests"]


def test_request_feed_and_admin_endpoints(srv):
    import os
    import sys
    import threading

    import requests

    from minio_tpu.madmin import AdminClient
    slo.reset()
    adm = AdminClient(srv.endpoint(), AK, SK)
    sys.path.insert(0, os.path.dirname(__file__))
    from s3client import S3Client
    c = S3Client(srv.endpoint(), AK, SK)
    assert c.put_bucket("slob").status_code == 200
    assert c.put_object("slob", "k", b"x" * 128).status_code == 200
    assert c.get_object("slob", "k").status_code == 200
    # the object PUT + GET, and the bucket PUT: all three have landed
    rep = _settled_report(adm, lambda r: _requests(r, "interactive") >= 2
                          and _requests(r, "control") >= 1)
    assert _requests(rep, "interactive") == 2
    assert _requests(rep, "control") == 1
    # exempt planes never feed the SLO windows: the health probe and the
    # admin reports themselves leave the counts where they stand, seen
    # from behind one more counted request (handlers finish in order of
    # arrival only by chance)
    requests.get(srv.endpoint() + "/minio/health/live", timeout=5)
    assert c.get_object("slob", "k").status_code == 200
    rep2 = _settled_report(adm, lambda r: _requests(r, "interactive") >= 3)
    assert _requests(rep2, "interactive") == 3
    assert _requests(rep2, "control") == 1
    # admission 503s burn availability: pinch the gate and burst
    srv.qos_admission.reconfigure(1)
    os.environ["MINIO_TPU_QOS_MAX_WAIT_MS"] = "1"
    try:
        errs = [0]

        def hit():
            r = S3Client(srv.endpoint(), AK, SK).get_object("slob", "k")
            if r.status_code == 503:
                assert r.headers.get("Retry-After")
                errs[0] += 1

        ths = [threading.Thread(target=hit) for _ in range(6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=30)
    finally:
        os.environ.pop("MINIO_TPU_QOS_MAX_WAIT_MS", None)
        srv.qos_admission.reconfigure(256)
    assert errs[0] > 0
    w = _settled_report(adm, lambda r: _requests(r, "interactive") >= 9)[
        "classes"]["interactive"]["windows"]["5m"]
    assert w["requests"] == 9 and w["errors"] == errs[0]
    # the health snapshot embeds the same verdicts (single node)
    h = adm.cluster_health()
    assert h["cluster"]["nodes"] == 1
    assert h["nodes"][0]["slo"]["classes"]["interactive"][
        "windows"]["5m"]["requests"] == w["requests"]
    # burn-rate family live on the metrics endpoint
    text = requests.get(srv.endpoint() + "/minio/v2/metrics",
                        timeout=10).text
    assert "minio_tpu_slo_burn_rate" in text
    assert "minio_tpu_slo_requests_total" in text


def test_worst_breach_type_line_emitted_once():
    """Two classes with STORED worst breaches must share one
    `# TYPE minio_tpu_slo_worst_breach_seconds` declaration — per-class
    emission duplicated it and tripped the exposition lint exactly when
    a multi-class latency incident made the metric interesting."""
    from minio_tpu.obs import spans as sp
    from minio_tpu.obs.metrics import _g_slo
    st = sp.store()
    st.put({"trace_id": "wb-t1", "spans": [{"span_id": "a"}]})
    st.put({"trace_id": "wb-t2", "spans": [{"span_id": "b"}]})
    slo.record("interactive", 9.0, trace_id="wb-t1")
    slo.record("control", 9.0, trace_id="wb-t2")
    lines = _g_slo(None)
    types = [ln for ln in lines if ln.startswith(
        "# TYPE minio_tpu_slo_worst_breach_seconds")]
    samples = [ln for ln in lines if ln.startswith(
        "minio_tpu_slo_worst_breach_seconds{")]
    assert len(samples) == 2
    assert len(types) == 1
