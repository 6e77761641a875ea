"""QoS subsystem tests: cost model, deadline-aware spill scheduling,
admission control (503 SlowDown + Retry-After over real HTTP), class
tagging, config knobs, admin status, and the minio_tpu_qos_* metrics."""
import threading
import time

import numpy as np
import pytest

from minio_tpu import qos
from minio_tpu.qos.admission import (AdmissionController, TokenBucket,
                                     classify_request)
from minio_tpu.qos.budget import CostModel
from minio_tpu.qos.scheduler import QosScheduler


class FakeProfile:
    """Stand-in for dispatch.LinkProfile with controllable rates."""

    def __init__(self, rt_s=0.1, up_gibs=0.01, down_gibs=0.01,
                 cpu_gibs=1.0):
        self.rt_s = rt_s
        self.up_gibs = up_gibs
        self.down_gibs = down_gibs
        self.cpu_gibs = cpu_gibs

    def device_flush_s(self, bytes_in, bytes_out, kernel_s=2e-3):
        return self.rt_s + bytes_in / self.up_gibs / (1 << 30) \
            + bytes_out / self.down_gibs / (1 << 30) + kernel_s


# -- cost model ---------------------------------------------------------------


def test_cost_model_ewma_correction_converges():
    c = CostModel()
    prof = FakeProfile(rt_s=0.0, up_gibs=1.0, down_gibs=1.0, cpu_gibs=1.0)
    base = c.device_s(prof, 1 << 20, 1 << 20)
    # the route consistently takes 2x the analytic estimate
    for _ in range(40):
        c.observe("device", c.device_s(prof, 1 << 20, 1 << 20), 2 * base)
    corrected = c.device_s(prof, 1 << 20, 1 << 20)
    assert corrected > 1.5 * base, (base, corrected)
    # correction is clamped: one absurd observation can't blow it up
    c2 = CostModel()
    c2.observe("cpu", 1e-6, 1e3)
    assert c2._corr["cpu"] <= 10.0


def test_class_budgets_env(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS", "7")
    assert CostModel.budget_s(qos.CLASS_INTERACTIVE) == pytest.approx(
        0.007)
    monkeypatch.delenv("MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS")
    assert CostModel.budget_s(qos.CLASS_BACKGROUND) >= \
        CostModel.budget_s(qos.CLASS_INTERACTIVE)


# -- scheduler spill decisions ------------------------------------------------


def test_plan_spills_on_slow_link_forced_device():
    """Forced-device mode through a saturated/slow link: the per-item
    walk must spill the tail (or all) of the flush to CPU instead of
    queueing 21 s of backlog (round-5 verdict weak-item 2)."""
    s = QosScheduler()
    slow = FakeProfile(rt_s=0.1, up_gibs=0.016, down_gibs=0.016,
                       cpu_gibs=2.0)
    sizes = [(1 << 20, 128 << 10)] * 128  # 128 x 1 MiB heal items
    n_dev = s.plan("device", slow, qos.CLASS_INTERACTIVE, sizes,
                   backlog_s=0.0, cpu_workers=8)
    assert n_dev < 128
    assert s.spilled_items == 128 - n_dev
    assert s.spilled_batches == 1
    assert sum(s.spill_reasons.values()) == 1


def test_plan_keeps_device_on_fast_link():
    s = QosScheduler()
    fast = FakeProfile(rt_s=2e-4, up_gibs=8.0, down_gibs=8.0,
                       cpu_gibs=0.5)
    sizes = [(1 << 20, 256 << 10)] * 16
    n_dev = s.plan("device", fast, qos.CLASS_INTERACTIVE, sizes,
                   backlog_s=0.0, cpu_workers=8)
    assert n_dev == 16
    assert s.spilled_items == 0


def test_plan_respects_backlog_and_queue_cap(monkeypatch):
    s = QosScheduler()
    fast = FakeProfile(rt_s=2e-4, up_gibs=8.0, down_gibs=8.0,
                       cpu_gibs=0.5)
    sizes = [(1 << 20, 256 << 10)] * 8
    # a huge existing backlog forces a spill even on a fast link
    assert s.plan("device", fast, qos.CLASS_INTERACTIVE, sizes,
                  backlog_s=30.0, cpu_workers=8) == 0
    assert s.spill_reasons.get("backlog") == 1
    # queued-bytes cap: pretend the device queue is nearly full
    monkeypatch.setenv("MINIO_TPU_QOS_DEVICE_QUEUE_BYTES",
                       str(2 << 20))
    s2 = QosScheduler()
    s2.device_dispatched(1 << 20)
    n = s2.plan("device", fast, qos.CLASS_INTERACTIVE, sizes,
                backlog_s=0.0, cpu_workers=8)
    assert n <= 1, n
    assert s2.spill_reasons.get("bytes_cap") == 1
    s2.device_completed(1 << 20)
    assert s2.device_queued_bytes() == 0


def test_plan_modes_without_profile():
    s = QosScheduler()
    sizes = [(1 << 20, 1 << 18)] * 4
    # cpu mode never uses the device; auto without a profile stays cpu;
    # forced device without a profile trusts the operator
    assert s.plan("cpu", None, qos.CLASS_INTERACTIVE, sizes, 0.0, 8) == 0
    assert s.plan("auto", None, qos.CLASS_INTERACTIVE, sizes, 0.0, 8) == 0
    assert s.plan("device", None, qos.CLASS_INTERACTIVE, sizes,
                  0.0, 8) == 4


# -- dispatch integration: forced-device spill end-to-end ---------------------


def test_forced_device_spill_bounds_latency(monkeypatch):
    """Heal-shard style load in FORCED-device mode against a synthetic
    slow-link profile: items spill to the CPU route, results stay
    bit-exact, spill counters surface in stats(), and per-item wall
    latency stays bounded (tens of ms, not seconds)."""
    from minio_tpu.ops.rs_jax import get_codec, pack_shards
    from minio_tpu.runtime.dispatch import DispatchQueue, LinkProfile
    monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", "device")
    monkeypatch.setenv("MINIO_TPU_DISPATCH", "1")
    q = DispatchQueue(max_batch=128, max_delay=0.001)
    try:
        # wait out the init-time background probe, THEN install a
        # synthetic slow-link profile (16 MiB/s, 100 ms RT) so
        # the scheduler sees a link it must spill around — a probe
        # landing mid-test would overwrite it
        t = getattr(q, "_probe_thread", None)
        if t is not None:
            t.join(timeout=60)
        slow = LinkProfile(rt_s=0.1, up_gibs=0.016, down_gibs=0.016,
                           cpu_gibs=2.0)
        with q._profile_lock:
            q._profile = slow
            q._profile_failed = False
        codec = get_codec(16, 4)
        data = np.random.default_rng(0).integers(
            0, 256, (16, 65536), dtype=np.uint8)
        words = pack_shards(data)
        present = tuple(i for i in range(20) if i not in (3, 17))[:16]
        masks = codec.target_masks_np(present, (3, 17))
        t0 = time.monotonic()
        futs = [q.masked(codec, words, masks) for _ in range(64)]
        outs = [f.result(timeout=60) for f in futs]
        wall = time.monotonic() - t0
        want = outs[0]
        for o in outs[1:]:
            np.testing.assert_array_equal(o, want)
        st = q.stats()
        # most items must have spilled off the 16 MiB/s link (sending
        # all 64 x 1 MiB through it would take > 4 s up alone)
        assert st["spilled_items"] > 0, st
        assert st["cpu_items"] > 0, st
        assert wall < 10.0, wall
        assert st["class_items"]["interactive"] == 64
    finally:
        q.stop()


def test_background_class_tagging_and_priority():
    """Items submitted under qos.background() land in background-class
    buckets (separate flushes, counted per class)."""
    from minio_tpu.ops.rs_jax import get_codec, pack_shards
    from minio_tpu.runtime.dispatch import DispatchQueue
    q = DispatchQueue(max_batch=8, max_delay=0.002)
    try:
        codec = get_codec(4, 2)
        d = np.random.default_rng(1).integers(0, 256, (4, 1024),
                                              dtype=np.uint8)
        w = pack_shards(d)
        f1 = q.encode(codec, w)
        with qos.background():
            assert qos.current_class() == qos.CLASS_BACKGROUND
            f2 = q.encode(codec, w)
        assert qos.current_class() == qos.CLASS_INTERACTIVE
        np.testing.assert_array_equal(f1.result(timeout=20),
                                      f2.result(timeout=20))
        st = q.stats()
        assert st["class_items"][qos.CLASS_INTERACTIVE] >= 1
        assert st["class_items"][qos.CLASS_BACKGROUND] >= 1
        # classes never share a bucket => at least two flushes
        assert st["batches"] >= 2
    finally:
        q.stop()


# -- admission control --------------------------------------------------------


def test_token_bucket_refill():
    b = TokenBucket(rate=10.0, burst=2.0)
    now = 100.0
    assert b.take(now) == 0.0
    assert b.take(now) == 0.0
    retry = b.take(now)
    assert retry > 0.0
    # after the Retry-After hint elapses, a token is available (epsilon
    # covers float residue in the refill arithmetic)
    assert b.take(now + retry + 1e-6) == 0.0


def test_classify_request():
    assert classify_request("GET", "/b/key") == "interactive"
    assert classify_request("PUT", "/b/dir/obj?partNumber=1") == \
        "interactive"
    assert classify_request("GET", "/b") == "control"
    assert classify_request("GET", "/") == "control"
    assert classify_request("POST", "/minio/webrpc") == "control"
    # exempt planes
    assert classify_request("GET", "/minio/health/live") is None
    assert classify_request("GET", "/minio/v2/metrics/cluster") is None
    assert classify_request("GET", "/minio/admin/v3/qos") is None
    # internal RPC exemption covers ONLY the mounted service names —
    # the console plane stays throttled on distributed nodes too
    assert classify_request("POST", "/minio/storage/v1/read",
                            internal={"storage", "lock", "peer"}) is None
    assert classify_request("POST", "/minio/storage/v1/read") == "control"
    assert classify_request("POST", "/minio/webrpc",
                            internal={"storage"}) == "control"
    assert classify_request("GET", "/minio/zip",
                            internal={"storage"}) == "control"


def test_admission_concurrency_bounded_wait():
    adm = AdmissionController(max_requests=2, max_wait_s=0.05)
    g1, g2 = adm.admit("interactive"), adm.admit("interactive")
    assert g1.ok and g2.ok
    t0 = time.monotonic()
    g3 = adm.admit("interactive")
    waited = time.monotonic() - t0
    assert not g3.ok and g3.reason == "concurrency"
    assert 0.04 <= waited < 1.0
    assert g3.retry_after_s > 0
    adm.release(g1)
    g4 = adm.admit("interactive")
    assert g4.ok  # freed slot admits immediately
    adm.release(g2)
    adm.release(g4)
    st = adm.stats()
    assert st["inflight_total"] == 0
    assert st["rejected"]["interactive"] == 1


def test_admission_waiter_wakes_on_release():
    adm = AdmissionController(max_requests=1, max_wait_s=2.0)
    g1 = adm.admit("interactive")
    got = {}

    def waiter():
        got["g"] = adm.admit("interactive")

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    adm.release(g1)
    t.join(timeout=5)
    assert got["g"].ok
    adm.release(got["g"])


def test_concurrency_reject_refunds_rate_token():
    """A request that passes the rate check but times out on the
    concurrency gate was never admitted — its token must be refunded or
    saturation silently burns the configured rate budget."""
    adm = AdmissionController(max_requests=1, max_wait_s=0.01,
                              rates={"interactive": 1.0})
    hold = adm.admit("interactive")
    assert hold.ok
    bucket = adm._buckets["interactive"]
    before = bucket.tokens
    g = adm.admit("interactive")
    assert not g.ok and g.reason == "concurrency"
    assert bucket.tokens == pytest.approx(before, abs=0.05)
    adm.release(hold)


def test_admission_rate_limit_rejects():
    adm = AdmissionController(max_requests=100, max_wait_s=0.01,
                              rates={"interactive": 1.0})
    # burst floor is 8: drain it, then the next request is rate-limited
    grants = [adm.admit("interactive") for _ in range(8)]
    assert all(g.ok for g in grants)
    g = adm.admit("interactive")
    assert not g.ok and g.reason == "rate" and g.retry_after_s > 0
    assert int(AdmissionController.retry_after_header(g)) >= 1
    for gr in grants:
        adm.release(gr)


# -- HTTP plane: 503 SlowDown under synthetic overload ------------------------


@pytest.fixture()
def qsrv(tmp_path):
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.server import S3Server
    from minio_tpu.storage import XLStorage
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=1)
    srv = S3Server(obj, "127.0.0.1", 0, access_key="qos",
                   secret_key="qos-secret")
    srv.start_background()
    yield srv
    srv.shutdown()


def test_http_slowdown_on_concurrency_overload(qsrv):
    """Synthetic overload: capacity 1 + a request that holds the slot.
    The concurrent request gets S3-semantic 503 SlowDown + Retry-After
    instead of queueing unboundedly; after release, service resumes."""
    import requests

    from s3client import S3Client
    c = S3Client(qsrv.endpoint(), "qos", "qos-secret")
    assert c.request("PUT", "/qb").status_code == 200
    assert c.request("PUT", "/qb/o", body=b"x" * 1024).status_code == 200
    qsrv.qos_admission.reconfigure(max_requests=1)
    # hold the single slot from this thread...
    hold = qsrv.qos_admission.admit("interactive")
    assert hold.ok
    try:
        t0 = time.monotonic()
        r = c.request("GET", "/qb/o")
        waited = time.monotonic() - t0
        assert r.status_code == 503, r.content
        assert b"<Code>SlowDown</Code>" in r.content
        assert int(r.headers["Retry-After"]) >= 1
        assert waited < 5.0  # bounded wait, not a pile-up
        # exempt planes still answer under overload
        assert requests.get(qsrv.endpoint() + "/minio/health/live",
                            timeout=10).status_code == 200
        m = requests.get(qsrv.endpoint() + "/minio/v2/metrics/node",
                         timeout=10)
        assert m.status_code == 200
        assert b"minio_tpu_qos_admission_rejects_total" in m.content
    finally:
        qsrv.qos_admission.release(hold)
        qsrv.qos_admission.reconfigure(max_requests=256)
    r = c.request("GET", "/qb/o")
    assert r.status_code == 200 and r.content == b"x" * 1024


def test_http_slowdown_on_rate_limit(qsrv, monkeypatch):
    """Per-class token bucket drained => immediate SlowDown, while the
    control-plane class keeps its own budget."""
    monkeypatch.setenv("MINIO_TPU_QOS_INTERACTIVE_RPS", "1")
    from s3client import S3Client
    c = S3Client(qsrv.endpoint(), "qos", "qos-secret")
    c.request("PUT", "/rb")
    rs = [c.request("GET", "/rb/miss-%d" % i) for i in range(12)]
    codes = [r.status_code for r in rs]
    assert 503 in codes, codes
    # the rejection leaves the body unread and closes; unannounced, the
    # next request on the kept-alive connection races that close
    # (RemoteDisconnected under load)
    assert all(r.headers.get("Connection") == "close"
               for r in rs if r.status_code == 503)
    # bucket listing is "control" class: separate budget, still served
    assert c.request("GET", "/rb").status_code == 200
    st = qsrv.qos_admission.stats()
    assert st["rejected"].get("interactive", 0) >= 1


def test_admin_qos_status_and_madmin(qsrv):
    from minio_tpu.madmin import AdminClient
    adm = AdminClient(qsrv.endpoint(), "qos", "qos-secret")
    st = adm.qos_status()
    assert "admission" in st and "classes" in st
    assert st["admission"]["max_requests"] >= 1
    # scheduler section appears once the global dispatch queue exists
    from minio_tpu.runtime.dispatch import global_queue
    global_queue()
    st = adm.qos_status()
    assert "scheduler" in st
    assert "spilled_items" in st["scheduler"]


def test_qos_config_registered():
    from minio_tpu.config.kvs import DYNAMIC, SUB_SYSTEMS
    assert "qos" in SUB_SYSTEMS and "qos" in DYNAMIC
    keys = SUB_SYSTEMS["qos"]
    for k in ("spill_factor", "device_queue_bytes",
              "interactive_budget_ms", "background_budget_ms",
              "max_wait_ms", "interactive_rps", "control_rps"):
        assert k in keys, k


# -- per-device flush lanes (mesh placement, ISSUE 11) ------------------------


def test_lane_saturation_spills_to_sibling_before_cpu(monkeypatch):
    """THE spill-order pin: device-lane → sibling-lane → CPU. A flush
    whose preferred (affinity) lane is over its per-lane queued-bytes
    cap lands on the least-loaded SIBLING at full strength; only when
    every lane is saturated does plan() spill items to the CPU
    executor (reason lane_cap)."""
    monkeypatch.setenv("MINIO_TPU_QOS_DEVICE_QUEUE_BYTES",
                       str(256 << 20))
    monkeypatch.setenv("MINIO_TPU_QOS_LANE_QUEUE_BYTES", str(4 << 20))
    s = QosScheduler()
    s.configure_lanes(4)
    fast = FakeProfile(rt_s=2e-4, up_gibs=8.0, down_gibs=8.0,
                       cpu_gibs=0.5)
    sizes = [(1 << 20, 256 << 10)] * 2
    aff = 17                       # preferred lane = 17 % 4 = 1
    assert s.pick_lane(aff) == 1   # empty lanes: affinity wins
    # saturate the preferred lane past its per-lane cap
    s.device_dispatched(8 << 20, lane=1, flush_s=5.0)
    lane = s.pick_lane(aff)
    assert lane != 1, "saturated lane must divert to a sibling"
    assert s.lane_diverts >= 1
    n = s.plan("device", fast, qos.CLASS_INTERACTIVE, sizes,
               backlog_s=s.lane_backlog_s(lane), cpu_workers=8,
               lane=lane)
    assert n == len(sizes), "sibling lane absorbs the flush — no CPU"
    assert s.spilled_items == 0
    # saturate EVERY lane: now (and only now) items spill to CPU
    for i in range(4):
        s.device_dispatched(8 << 20, lane=i)
    lane = s.pick_lane(aff)
    n = s.plan("device", fast, qos.CLASS_INTERACTIVE, sizes,
               backlog_s=0.0, cpu_workers=8, lane=lane)
    assert n == 0
    assert s.spill_reasons.get("lane_cap") == 1
    # completion drains the lane model symmetrically
    s.device_completed(8 << 20, lane=1)
    s.device_completed(8 << 20, lane=1)
    assert s.lane_queued_bytes()[1] == 0
    assert s.lane_backlog_s(1) == 0.0


def test_lane_accounting_and_stats(monkeypatch):
    s = QosScheduler()
    s.configure_lanes(3)
    # an SPMD (lane=None) flush charges only the global counter but
    # extends EVERY lane's busy-until — all chips are occupied
    s.device_dispatched(6 << 20, lane=None, flush_s=2.0)
    assert s.device_queued_bytes() == 6 << 20
    assert s.lane_queued_bytes() == [0, 0, 0]
    assert all(s.lane_backlog_s(i) > 1.0 for i in range(3))
    s.device_completed(6 << 20, lane=None)
    st = s.stats()
    assert st["lanes"] == 3
    assert st["lane_queued_bytes"] == [0, 0, 0]
    assert "lane_queue_bytes_cap" in st and "lane_diverts" in st
    # derived per-lane cap = device cap / lanes when the knob is 0
    monkeypatch.setenv("MINIO_TPU_QOS_DEVICE_QUEUE_BYTES", str(96 << 20))
    monkeypatch.delenv("MINIO_TPU_QOS_LANE_QUEUE_BYTES", raising=False)
    from minio_tpu.qos.scheduler import lane_queue_bytes_cap
    assert lane_queue_bytes_cap(3) == 32 << 20


def test_lane_affinity_context_and_key():
    assert qos.current_affinity() is None
    with qos.lane_affinity(qos.set_affinity_key(0, 3)):
        a = qos.current_affinity()
        assert isinstance(a, int) and a >= 0
        with qos.lane_affinity(None):
            assert qos.current_affinity() is None
        assert qos.current_affinity() == a
    assert qos.current_affinity() is None
    # stable across calls/processes (crc32, not PYTHONHASHSEED)
    assert qos.set_affinity_key(1, 2) == qos.set_affinity_key(1, 2)
    assert qos.set_affinity_key(0, 0) != qos.set_affinity_key(0, 1)


def test_parallel_pinned_lanes_read_busiest_not_serial_sum():
    """Pinned flushes on distinct lanes run in PARALLEL: the backlog an
    SPMD all-lanes flush plans against is the busiest single lane, not
    the serial sum of every lane's wall (which read ~Nx the real drain
    time and spilled idle-mesh work to CPU)."""
    s = QosScheduler()
    s.configure_lanes(4)
    for i in range(4):
        s.device_dispatched(1 << 20, lane=i, flush_s=1.0)
    assert s.max_lane_backlog_s() <= 1.1  # not ~4s


def test_spmd_drain_resyncs_lane_model():
    """SPMD (lane=None) dispatches extend every lane's busy-until but
    have no per-lane completion; the full-pipeline drain must clamp the
    whole lane model or it only ever ratchets up."""
    s = QosScheduler()
    s.configure_lanes(4)
    s.device_dispatched(1 << 20, lane=None, flush_s=5.0)
    assert s.max_lane_backlog_s() > 4.0
    s.device_completed(1 << 20, lane=None)  # queued hits 0: full resync
    assert s.max_lane_backlog_s() == 0.0
    assert all(b == 0 for b in s.lane_queued_bytes())


def test_pinned_flushes_do_not_inflate_global_spmd_backlog():
    """dispatch._backlog_s(None) joins the global serial model with the
    busiest lane — pinned flushes live only in the lane model, so
    concurrent per-lane traffic must not stack up as serial global
    backlog in an SPMD flush's plan."""
    from minio_tpu.runtime.dispatch import DispatchQueue
    q = DispatchQueue()
    try:
        q.qos.configure_lanes(8)
        for i in range(8):
            q.qos.device_dispatched(1 << 20, lane=i, flush_s=2.0)
        b = q._backlog_s(None)
        assert 1.5 < b <= 2.1, b  # busiest lane, not 16s serial
        with q._profile_lock:
            assert q._dev_busy_until == 0.0
    finally:
        q.stop()


def test_affinity_slot_folds_to_lane_or_none(monkeypatch):
    """Bucket keys carry the flush-lane SLOT, not the raw crc32 key:
    single-device hosts (and lanes-off config) fold every affinity to
    None so cross-set coalescing survives, multi-lane hosts fold to
    key % lanes so sets sharing a lane share a flush; an unknown
    topology passes the raw key through (submit must never initialize
    the backend)."""
    from minio_tpu.runtime import dispatch as dp
    monkeypatch.delenv("MINIO_TPU_DISPATCH_MODE", raising=False)
    q = dp.DispatchQueue()
    try:
        assert q._affinity_slot(None) is None
        q.__dict__.pop("_lanes_cache", None)  # topology unknown
        assert q._affinity_slot(13) == 13
        # forced-CPU mode: no device flush will ever resolve the
        # topology, so the conservative split must not become permanent
        monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", "cpu")
        assert q._affinity_slot(13) is None
        monkeypatch.delenv("MINIO_TPU_DISPATCH_MODE")
        q._lanes_cache = ("dev0",)            # single-chip host
        assert q._affinity_slot(13) is None
        q._lanes_cache = tuple(f"dev{i}" for i in range(8))
        assert q._affinity_slot(13) == 13 % 8
        assert q._affinity_slot(13 + 8) == 13 % 8  # shared-lane coalesce
        monkeypatch.setattr(dp, "DISPATCH_LANES", "1")
        assert q._affinity_slot(13) is None
        monkeypatch.setattr(dp, "DISPATCH_LANES", "4")
        assert q._affinity_slot(13) == 1
    finally:
        q.stop()
