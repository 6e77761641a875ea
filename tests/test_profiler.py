"""Continuous profiling plane (ISSUE 14, docs/observability.md
"Continuous profiling"): deterministic hot-spin attribution (role /
subsystem / QoS tag), folded + speedscope schema pins, capped-memory
drop counting, lock-wait histogram + contended-site report,
SLO-breach-triggered capture retrievable from the admin endpoint, and
the <2% default-rate overhead gate."""
import io
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from minio_tpu.madmin import AdminClient, AdminError  # noqa: E402
from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.obs import lockrank, profiler, slo  # noqa: E402
from minio_tpu.server import S3Server  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402

AK, SK = "profak", "profsk"


@pytest.fixture()
def prof():
    """Running sampler with fresh aggregates (and fresh again on the
    way out, so samples from one test never bleed into the next)."""
    profiler.ensure_started()
    profiler.reset()
    yield profiler
    profiler.reset()


@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    root = tmp_path_factory.mktemp("profsrv")
    obj = ErasureObjects([XLStorage(str(root / f"d{i}"))
                          for i in range(4)], default_parity=1)
    s = S3Server(obj, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    s.start_background()
    yield s
    s.shutdown()


def _spin_threads(n: int, stop: threading.Event,
                  cls: str = "interactive",
                  op: str = "s3.put-test") -> list[threading.Thread]:
    def spin():
        profiler.set_task_tag(cls, op)
        try:
            profiler.calibrate_spin(10.0, stop)
        finally:
            profiler.clear_task_tag()

    ths = [threading.Thread(target=spin, daemon=True,
                            name=f"minio-tpu-test-spin-{i}")
           for i in range(n)]
    for t in ths:
        t.start()
    return ths


def test_hot_spin_attribution(prof):
    """THE attribution proof: an injected busy loop in tagged worker
    threads surfaces as the top folded frame OF THE TAGGED SAMPLES,
    with the correct subsystem (obs — calibrate_spin lives in
    minio_tpu/obs) and the QoS class + op joined cross-thread via the
    tag registry. A unique tag keys the assertion: whatever thread zoo
    the rest of the suite left running, only the injected workers
    carry it, so the verdict is deterministic (in a quiet process the
    spin is also the GLOBAL top frame — not pinned here)."""
    stop = threading.Event()
    ths = _spin_threads(6, stop, cls="qos-test-hotspin",
                        op="op-test-hotspin")
    try:
        agg = profiler.capture_window(1.2, hz=97)
    finally:
        stop.set()
        for t in ths:
            t.join(timeout=10)
    rep = profiler.report_top(agg)
    assert rep["samples"] > 0
    tagged = {s: c for s, c in agg.stacks.items()
              if "class:qos-test-hotspin;" in s}
    assert tagged, agg.stacks.most_common(5)
    # top folded frame of the tagged worker = the injected busy loop
    top_sig = max(tagged, key=tagged.get)
    assert top_sig.endswith("profiler.py:calibrate_spin"), top_sig
    # ... with the correct subsystem
    assert ";subsys:obs;" in top_sig, top_sig
    # ... and it DOMINATES the worker's samples (the loop body is
    # pure arithmetic, so nothing else in the thread can own share)
    spin = sum(c for s, c in tagged.items()
               if s.endswith("profiler.py:calibrate_spin"))
    assert spin / sum(tagged.values()) > 0.7, tagged
    # the class/op joins surface in the report counters too
    assert rep["classes"].get("qos-test-hotspin", 0) > 0, \
        rep["classes"]
    assert rep["ops"].get("op-test-hotspin", 0) > 0, rep["ops"]
    assert rep["subsystems"].get("obs", 0) > 0, rep["subsystems"]
    # the folded export carries the classification prefix
    folded = profiler.render_folded(agg).decode()
    assert "class:qos-test-hotspin" in folded
    assert "subsys:obs" in folded


def test_folded_and_speedscope_schema(prof):
    """Schema pins: every folded line is `<role:...;...;frames> count`,
    and the speedscope document is a valid 'sampled' profile (frame
    indices in range, endValue == sum of weights)."""
    stop = threading.Event()
    ths = _spin_threads(2, stop)
    try:
        agg = profiler.capture_window(0.5, hz=200)
    finally:
        stop.set()
        for t in ths:
            t.join(timeout=10)
    folded = profiler.render_folded(agg).decode()
    lines = [ln for ln in folded.splitlines()
             if ln and not ln.startswith("#")]
    assert lines
    for ln in lines:
        stack, _, count = ln.rpartition(" ")
        assert count.isdigit() and int(count) > 0, ln
        head = stack.split(";")
        assert head[0].startswith("role:"), ln
        assert head[1].startswith("class:"), ln
        assert head[2].startswith("subsys:"), ln
    doc = json.loads(profiler.render_speedscope(agg))
    assert doc["$schema"] == profiler.SPEEDSCOPE_SCHEMA
    p = doc["profiles"][doc["activeProfileIndex"]]
    assert p["type"] == "sampled"
    assert len(p["samples"]) == len(p["weights"]) > 0
    nframes = len(doc["shared"]["frames"])
    assert all(0 <= i < nframes for s in p["samples"] for i in s)
    assert p["endValue"] == sum(p["weights"])
    assert all(isinstance(f["name"], str)
               for f in doc["shared"]["frames"])


def test_capped_memory_counts_drops():
    """The bounded-memory contract: past `cap` distinct stacks, new
    signatures are dropped AND counted; classification side counters
    still see every sample."""
    agg = profiler._Agg(cap=4, hz=50)
    for i in range(100):
        agg.feed(f"role:other;class:-;subsys:t;f{i}", f"f{i}",
                 "other", "t", None, False)
    assert len(agg.stacks) == 4
    assert agg.drops == 96
    assert agg.samples == 100  # side counters never drop
    assert agg.subsystems["t"] == 100


def test_lock_wait_histogram_and_contended_report(prof):
    """TrackedLock acquire waits land in the per-site lock-wait stats,
    the top-contended report names the site, profiler samples taken
    while blocked carry the lockwait mark, and the metrics group
    renders the histogram family."""
    if not lockrank.enabled():
        pytest.skip("lockrank disabled")
    lk = lockrank.tracked("profiler-test-site")
    hold = threading.Event()
    held = threading.Event()

    def holder():
        with lk:
            held.set()
            hold.wait(10)

    t = threading.Thread(target=holder, daemon=True,
                         name="minio-tpu-test-holder")
    t.start()
    assert held.wait(10)

    def contender():
        with lk:
            pass

    c = threading.Thread(target=contender, daemon=True,
                         name="minio-tpu-test-contender")
    c.start()
    time.sleep(0.15)  # contender is parked inside acquire
    agg = profiler.capture_window(0.3, hz=200)
    hold.set()
    c.join(10)
    t.join(10)
    assert agg.lockwait > 0, "no sample observed the blocked thread"
    rows = profiler.lock_report(10_000)
    row = next((r for r in rows if r["site"] == "profiler-test-site"),
               None)
    assert row is not None, rows[:5]
    assert row["waits"] >= 1
    assert row["wait_seconds_total"] >= 0.2
    snap = profiler.lock_wait_snapshot()["profiler-test-site"]
    assert snap["count"] >= 1
    assert sum(snap["buckets"]) == snap["count"]
    # exposition: the histogram family renders with the site label
    from minio_tpu.obs.metrics import _g_profiler
    text = "\n".join(_g_profiler(None))
    assert "# TYPE minio_tpu_lock_wait_seconds histogram" in text
    assert 'site="profiler-test-site"' in text
    assert "minio_tpu_profiler_samples_total" in text


def test_breach_triggers_capture_and_admin_fetch(prof, srv,
                                                 monkeypatch):
    """An SLO burn-rate breach auto-captures a high-rate profile
    window keyed by the breaching class (ISSUE 14 acceptance): the
    report links it, and `profile?breach=<class>` serves it."""
    monkeypatch.setenv("MINIO_TPU_PROFILER_BURST_S", "0.3")
    slo.reset()
    try:
        for _ in range(30):  # errors burn availability in BOTH windows
            slo.record("interactive", 0.01, status=500)
        rep = slo.report()
        assert rep["classes"]["interactive"]["breach"][
            "availability"] is True
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and \
                profiler.breach_profile("interactive") is None:
            time.sleep(0.05)
        stored = profiler.breach_profile("interactive")
        assert stored is not None, "breach did not store a capture"
        assert stored["class"] == "interactive"
        assert stored["samples"] >= 0 and "subsystems" in stored
        # linked from the SLO report
        link = slo.report()["classes"]["interactive"]["breach_profile"]
        assert link.get("captured") is True and "samples" in link
        # retrievable from the admin endpoint
        adm = AdminClient(f"http://127.0.0.1:{srv.port}", AK, SK)
        got = adm.profile(breach="interactive")
        assert got["class"] == "interactive"
        assert got["samples"] == stored["samples"]
    finally:
        slo.reset()


def test_admin_profile_endpoint_formats(prof, srv):
    """GET /minio/admin/v3/profile: top (default JSON), folded,
    speedscope, a fresh `seconds=` window, and a 400 on unknown fmt."""
    adm = AdminClient(f"http://127.0.0.1:{srv.port}", AK, SK)
    rep = adm.profile()
    assert "samples" in rep and "subsystems" in rep
    assert "lock_contention" in rep and rep.get("endpoint")
    fresh = adm.profile(seconds=0.3)
    assert fresh["duration_s"] < 5.0
    folded = adm.profile(fmt="folded")
    assert folded.startswith(b"# samples:")
    scope = adm.profile(fmt="speedscope")
    assert scope["$schema"] == profiler.SPEEDSCOPE_SCHEMA
    with pytest.raises(AdminError) as ei:
        adm.profile(fmt="bogus")
    assert ei.value.status == 400
    with pytest.raises(AdminError) as ei:
        adm.profile(breach="nothing-stored-here")
    assert ei.value.status == 404


def test_thread_role_classification():
    assert profiler.thread_role(0, "minio-tpu-dispatch") == "dispatcher"
    assert profiler.thread_role(0, "minio-tpu-dispatch-ia") == \
        "dispatcher"
    assert profiler.thread_role(0, "minio-tpu-complete_3") == \
        "completer"
    assert profiler.thread_role(
        0, "Thread-7 (process_request_thread)") == "http-worker"
    assert profiler.thread_role(0, "data-scanner") == "scanner"
    assert profiler.thread_role(0, "lock-maintenance") == \
        "lock-maintenance"
    assert profiler.thread_role(0, "mystery") == "other"
    profiler.register_role("custom-role")
    try:
        assert profiler.thread_role(
            threading.get_ident(),
            threading.current_thread().name) == "custom-role"
    finally:
        profiler._roles.pop(threading.get_ident(), None)


def test_overhead_under_two_percent(prof, tmp_path):
    """The <2% overhead gate (ISSUE 14 acceptance): the default-rate
    profiler's wall tax on a PUT microbench stays small (generous CI
    margin), and the sampler's own duty-cycle self-measure — the
    number the metric group exports — stays under 2%."""
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=1)
    obj.make_bucket("ovh")
    body = np.random.default_rng(3).integers(
        0, 256, 256 << 10, dtype=np.uint8).tobytes()

    def put_bench(tag: str, n: int = 20) -> float:
        t0 = time.perf_counter()
        for i in range(n):
            obj.put_object("ovh", f"{tag}{i}", io.BytesIO(body),
                           len(body))
        return time.perf_counter() - t0

    put_bench("warm")
    profiler.stop()
    off = min(put_bench("off-a"), put_bench("off-b"))
    profiler.ensure_started()
    time.sleep(0.3)  # a few base passes so the self-measure is live
    on = min(put_bench("on-a"), put_bench("on-b"))
    # generous margin: scheduler noise on a shared 1-core CI host
    # dwarfs a 19 Hz sampler; the hard 2% claim rides the self-measure
    assert on <= off * 1.5 + 0.25, (on, off)
    st = profiler.status()
    assert st["running"] and st["samples_total"] > 0
    assert st["overhead_ratio"] < 0.02, st


def test_mixed_load_with_scanner_cycle_profile_and_lock_order(prof, srv):
    """Concurrent mixed PUT/GET/LIST/DELETE against a live server with
    one scanner cycle forced mid-run: every request is admitted under
    its class, the lock-order detector (on for the whole suite,
    tests/conftest.py) reports nothing new, and the always-on profile
    of the run is well-formed: samples taken, subsystems and roles
    named, every share (the scanner's among them) a fraction."""
    from minio_tpu.scanner.scanner import DataScanner
    from s3client import S3Client
    assert lockrank.enabled()
    reports0 = len(lockrank.reports())
    admitted0 = dict(srv.qos_admission.stats()["admitted"])
    S3Client(srv.endpoint(), AK, SK).put_bucket("mixed")
    stop = threading.Event()
    codes: set[int] = set()

    def client(wid: int) -> None:
        c = S3Client(srv.endpoint(), AK, SK)
        seq = 0
        while not stop.is_set():
            key = f"w{wid}/o{seq % 8}"
            seq += 1
            codes.add(c.put_object("mixed", key, b"m" * 2048).status_code)
            codes.add(c.get_object("mixed", key).status_code)
            codes.add(c.request("GET", "/mixed",
                                query={"list-type": "2",
                                       "prefix": f"w{wid}/"}).status_code)
            if seq % 3 == 0:
                codes.add(c.delete_object("mixed", key).status_code)

    cycle: list[dict] = []
    scan = threading.Thread(
        target=lambda: cycle.append(
            DataScanner(srv.obj, sleep_per_object=0).scan_cycle()),
        daemon=True, name="data-scanner-mixed-load")
    ths = [threading.Thread(target=client, args=(w,), daemon=True,
                            name=f"mixed-client-{w}") for w in range(4)]
    for t in ths:
        t.start()
    time.sleep(0.5)
    scan.start()
    scan.join(timeout=60)
    time.sleep(0.5)
    stop.set()
    for t in ths:
        t.join(timeout=60)
    assert cycle and cycle[0]["buckets"].get("mixed"), cycle
    assert codes <= {200, 204}, codes
    # object requests were admitted as interactive, listings as control
    admitted = srv.qos_admission.stats()["admitted"]
    for cls in ("interactive", "control"):
        assert admitted.get(cls, 0) > admitted0.get(cls, 0), admitted
    assert len(lockrank.reports()) == reports0, \
        lockrank.reports()[reports0:]
    rep = profiler.snapshot_report(n=8)
    assert rep["samples"] > 0, rep
    for family in ("subsystems", "roles"):
        assert rep[family], rep
        assert all(isinstance(k, str) and k and 0.0 <= v <= 1.0
                   for k, v in rep[family].items()), rep[family]
    assert "http-worker" in rep["roles"], rep["roles"]
    assert isinstance(rep["lock_contention"], list)
