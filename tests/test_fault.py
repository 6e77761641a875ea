"""Chaos matrix (ISSUE 4 tentpole): for each armed fault class assert
GET/PUT/heal still return correct data or the correct typed error, disks
trip and recover, hedged reads beat the injected straggler delay, and —
because ``flaky`` draws from a per-rule seeded RNG — the whole matrix is
deterministic under ``pytest -m 'not slow'``."""
import io
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from minio_tpu import fault  # noqa: E402
from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.objectlayer.metadata import hash_order  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402
from minio_tpu.utils import errors  # noqa: E402

MB = 1 << 20


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()


def _layer(tmp_path, n=20, parity=4, **monkeyenv):
    disks = [XLStorage(os.path.join(tmp_path, f"d{i}")) for i in range(n)]
    ol = ErasureObjects(disks, default_parity=parity)
    ol.make_bucket("b")
    return ol


def _body(nbytes=MB, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


def _shard_disk(ol, obj, shard_idx=1, n=20):
    """The wrapped disk holding ``shard_idx`` for object ``obj`` (the
    PUT distribution is hash_order, so this is deterministic)."""
    dist = hash_order(f"b/{obj}", n)
    return ol.disks[dist.index(shard_idx)]


# --- registry ---------------------------------------------------------------


def test_rule_grammar_roundtrip():
    r = fault.parse_rule("disk:/d/3:read_at:delay(200,50)@ttl=30")
    assert (r.layer, r.target, r.op) == ("disk", "/d/3", "read_at")
    assert (r.delay_ms, r.jitter_ms, r.ttl_s) == (200.0, 50.0, 30.0)
    r = fault.parse_rule("rpc:http://peer:9000:readversion:flaky(0.3,42)")
    assert r.target == "http://peer:9000" and r.prob == 0.3 and r.seed == 42
    r = fault.parse_rule("kernel:*::error(FaultyDisk)@count=2")
    assert r.op == "*" and r.count == 2
    with pytest.raises(ValueError):
        fault.parse_rule("disk:*:x:explode")
    with pytest.raises(ValueError):
        fault.parse_rule("disk:*:x:error(NoSuchError)")


def test_hit_count_and_ttl_disarm():
    fault.arm("disk:*:stat:error(FaultyDisk)@count=2")
    for _ in range(2):
        with pytest.raises(errors.FaultyDisk):
            fault.inject("disk", "/d0", "stat")
    assert fault.inject("disk", "/d0", "stat") is None  # budget spent
    assert fault.rules() == []  # swept
    fault.arm("disk:*:stat:error(FaultyDisk)@ttl=0.05")
    time.sleep(0.08)
    assert fault.inject("disk", "/d0", "stat") is None  # expired
    assert fault.rules() == []


def test_flaky_is_seed_deterministic():
    def run():
        fault.clear()
        fault.arm("disk:*:stat:flaky(0.5,1234)")
        out = []
        for _ in range(16):
            try:
                fault.inject("disk", "/d0", "stat")
                out.append(0)
            except errors.FaultyDisk:
                out.append(1)
        return out

    a, b = run(), run()
    assert a == b and 0 < sum(a) < 16


# --- disk-layer chaos -------------------------------------------------------


def test_error_fault_put_get_survive_quorum(tmp_path):
    """Typed errors on two endpoints: PUT and GET still succeed at 16+4
    (quorum absorbs 2 bad disks), the faults actually fired, and MRF
    heard about the partial write."""
    ol = _layer(tmp_path)
    calls = []
    ol.on_partial = \
        lambda b, o, v, scan_mode="normal": calls.append((b, o, scan_mode))
    body = _body()
    ol.put_object("b", "seed", io.BytesIO(body), len(body))
    d1 = _shard_disk(ol, "seed", 1)
    d2 = _shard_disk(ol, "seed", 2)
    fault.arm(f"disk:{d1.endpoint()}:*:error(FaultyDisk)")
    fault.arm(f"disk:{d2.endpoint()}:*:error(DiskNotFound)")
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    assert ol.get_object_bytes("b", "o") == body
    assert ol.get_object_bytes("b", "seed") == body
    assert calls  # degraded paths reported to MRF
    from minio_tpu.obs.metrics import counters_snapshot
    snap = counters_snapshot()
    assert any("minio_tpu_fault_injected_total" in k and 'layer="disk"' in k
               for k in snap)


def test_bitrot_fault_detected_and_deep_healed(tmp_path):
    """A bitrot-corrupted shard read is caught by the bitrot reader,
    reconstructed around, and the object lands in MRF with
    scan_mode='deep' — then a deep heal actually repairs on-disk rot."""
    ol = _layer(tmp_path)
    calls = []
    ol.on_partial = \
        lambda b, o, v, scan_mode="normal": calls.append(scan_mode)
    body = _body()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    d = _shard_disk(ol, "o", 1)
    fault.arm(f"disk:{d.endpoint()}:read_at:bitrot@count=1")
    assert ol.get_object_bytes("b", "o") == body
    assert "deep" in calls
    # now REAL on-disk rot: deep heal must classify + rewrite the shard
    fault.clear()
    fi = d.read_version("b", "o")
    part = f"o/{fi.data_dir}/part.1"
    blob = bytearray(d.read_all("b", part))
    blob[len(blob) // 2] ^= 0xFF
    d.write_all("b", part, bytes(blob))
    res = ol.heal_object("b", "o", scan_mode="deep")
    assert "corrupt" in res.before_state
    assert res.after_state.count("ok") == len(ol.disks)
    assert ol.get_object_bytes("b", "o") == body


def test_hang_fault_is_hedged_around(tmp_path, monkeypatch):
    """A hung shard read (the worst straggler) does not hang the GET:
    the hedge fires a parity read and the request completes fast."""
    monkeypatch.setenv("MINIO_TPU_HEDGE_MS", "15")
    ol = _layer(tmp_path)
    body = _body()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    d = _shard_disk(ol, "o", 1)
    fault.arm(f"disk:{d.endpoint()}:read_at:hang(5)@count=1")
    t0 = time.perf_counter()
    assert ol.get_object_bytes("b", "o") == body
    assert time.perf_counter() - t0 < 2.0
    fault.clear()  # releases the sleeping io_pool thread immediately


def test_flaky_disk_reads_stay_correct(tmp_path):
    ol = _layer(tmp_path)
    body = _body()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    d = _shard_disk(ol, "o", 1)
    fault.arm(f"disk:{d.endpoint()}:read_at:flaky(0.5,7)")
    for _ in range(4):  # replacement reads absorb every coin flip
        assert ol.get_object_bytes("b", "o") == body


# --- hedged reads beat the injected straggler (acceptance criterion) --------


def test_hedged_get_fires_deterministic(tmp_path, monkeypatch):
    """Load-insensitive tier-1 hedging gate (ISSUE 10 satellite: the
    3x-statistics variant below flaked under suite load since PR 9 —
    its run-to-run medians swing 2x on a saturated host). This variant
    is deterministic: with a FIVE-second delay injected on one data
    shard and a 15 ms hedge threshold, the GET returning correct bytes
    in under 4 s is only possible when the hedged parity read rescued
    it — no distribution comparison, just an outcome the scheduler
    cannot fake. The timing margin is 300x the hedge threshold, so CI
    noise cannot flip it; a broken hedge path waits out the full 5 s
    and fails both asserts."""
    from minio_tpu.obs.metrics import counters_snapshot
    ol = _layer(tmp_path)
    body = _body()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    d = _shard_disk(ol, "o", 1)
    # warm the GET path (jit/pool costs stay out of the gated read)
    assert ol.get_object_bytes("b", "o") == body
    monkeypatch.setenv("MINIO_TPU_HEDGE_MS", "15")

    def fired() -> float:
        return sum(v for k, v in counters_snapshot().items()
                   if "minio_tpu_hedged_reads_total" in k
                   and "fired" in k)

    before = fired()
    fault.arm(f"disk:{d.endpoint()}:read_at:delay(5000)")
    try:
        t0 = time.perf_counter()
        assert ol.get_object_bytes("b", "o") == body
        wall = time.perf_counter() - t0
    finally:
        fault.clear()
    assert wall < 4.0, \
        f"GET took {wall:.2f}s: the hedge did not rescue the read"
    assert fired() > before


def test_hedged_get_p99_beats_straggler_3x(tmp_path, monkeypatch):
    """delay(200ms) on ONE data shard: 1 MiB GET p99 with hedging is
    >= 3x better than without (the unhedged path must wait out the
    injected delay every time; the hedged path pays ~threshold +
    reconstruct). Judged by its best and median samples (below), which
    held 10 of 10 times beside a whole `-n 6` run (PR 24); the
    deterministic variant above does not depend on timing at all."""
    ol = _layer(tmp_path)
    body = _body()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    d = _shard_disk(ol, "o", 1)
    # warm the python GET path and the degraded-reconstruct kernel so
    # neither measured distribution pays first-use jit/compile costs
    monkeypatch.setenv("MINIO_TPU_GET_PATH", "dispatch")
    fault.arm(f"disk:{d.endpoint()}:read_at:error(FaultyDisk)@count=3")
    for _ in range(3):
        assert ol.get_object_bytes("b", "o") == body
    fault.clear()
    for _ in range(2):
        assert ol.get_object_bytes("b", "o") == body

    fault.arm(f"disk:{d.endpoint()}:read_at:delay(200)")
    monkeypatch.setenv("MINIO_TPU_HEDGE_MS", "15")
    hedged = []
    for _ in range(8):
        t0 = time.perf_counter()
        assert ol.get_object_bytes("b", "o") == body
        hedged.append(time.perf_counter() - t0)
    monkeypatch.setenv("MINIO_TPU_HEDGE", "0")
    unhedged = []
    for _ in range(4):
        t0 = time.perf_counter()
        assert ol.get_object_bytes("b", "o") == body
        unhedged.append(time.perf_counter() - t0)
    # every unhedged sample carries the full 200ms delay. The whole
    # hedged distribution shifts 2x run-to-run on this 1-core host
    # (median 55-100ms), so judge with noise-robust statistics: the
    # BEST hedged sample shows the >=3x win hedging achieves, and the
    # MEDIAN must beat every straggler-bound GET outright — a hedged
    # path that stopped working would sit at ~215ms across the board
    # and fail both.
    hedged.sort()
    hedged_median = hedged[len(hedged) // 2]
    assert min(unhedged) >= 0.2
    assert min(unhedged) >= 3.0 * min(hedged), \
        f"hedged={hedged} unhedged={unhedged}"
    assert hedged_median < min(unhedged), \
        f"hedged={hedged} unhedged={unhedged}"
    from minio_tpu.obs.metrics import counters_snapshot
    snap = counters_snapshot()
    assert any("minio_tpu_hedged_reads_total" in k and "fired" in k
               for k in snap)


# --- health tracker: trip fast-fail + recovery (acceptance criterion) -------


def test_disk_trips_fast_fails_and_recovers(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_HEALTH_TRIP", "3")
    monkeypatch.setenv("MINIO_TPU_HEALTH_COOLDOWN_S", "0.2")
    ol = _layer(tmp_path)
    body = _body()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    w1 = _shard_disk(ol, "o", 1)
    w2 = _shard_disk(ol, "o", 2)
    events = []
    w1.state_listeners.append(lambda d, s: events.append(s))
    for w in (w1, w2):
        fault.arm(f"disk:{w.endpoint()}:*:error(FaultyDisk)")
    for _ in range(4):  # every GET's meta fan-out scores both disks
        assert ol.get_object_bytes("b", "o") == body
    assert w1.health_state() == "faulty" and w2.health_state() == "faulty"
    # tripped disk answers DiskNotFound in < 10ms, without inner I/O
    t0 = time.perf_counter()
    with pytest.raises(errors.DiskNotFound):
        w1.read_version("b", "o")
    assert time.perf_counter() - t0 < 0.010
    assert not w1.is_online()
    # quorum reads AND writes still succeed at 16+4 with 2 disks down
    assert ol.get_object_bytes("b", "o") == body
    ol.put_object("b", "o2", io.BytesIO(body), len(body))
    assert ol.get_object_bytes("b", "o2") == body
    # clear the faults: the cooldown probe re-onlines both disks
    fault.clear()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and not (
            w1.healthy() and w2.healthy()):
        time.sleep(0.05)
    assert w1.healthy() and w2.healthy()
    assert events[0] == "faulty" and events[-1] == "ok"
    assert w1.health_stats()["trips"] == 1


# --- kernel layer: CPU-salvage path -----------------------------------------


def test_kernel_fault_salvages_flush_on_cpu():
    """An injected device fault on a dispatch flush re-routes the whole
    flush to the CPU executor; results stay correct."""
    from minio_tpu.erasure.codec import Erasure
    er = Erasure(4, 2, 1 << 20)
    data = _body(256 << 10, seed=3)
    want = [s.tobytes() for s in er.encode_data(data)]
    fault.arm("kernel:*:encode:error(FaultyDisk)@count=4")
    got = [s.tobytes() for s in er.encode_data_async(data).result()]
    assert got == want
    from minio_tpu.obs.metrics import counters_snapshot
    assert any("minio_tpu_fault_injected_total" in k and 'layer="kernel"' in k
               for k in counters_snapshot())


def test_kernel_delay_fault_slows_but_correct():
    from minio_tpu.erasure.codec import Erasure
    er = Erasure(4, 2, 1 << 20)
    data = _body(64 << 10, seed=4)
    want = [s.tobytes() for s in er.encode_data(data)]
    fault.arm("kernel:*:encode:delay(30)@count=2")
    got = [s.tobytes() for s in er.encode_data_async(data).result()]
    assert got == want


# --- rpc layer: retry budget + ping backoff ---------------------------------


def test_rpc_idempotent_retry_budget(monkeypatch):
    import requests as _rq

    from minio_tpu.dist.rpc import RPCClient
    c = RPCClient("http://127.0.0.1:1", "storage", "s3cr3t")
    calls = {"n": 0}

    class _R:
        status_code = 200
        content = b"ok"
        headers: dict = {}

    def post(url, **kw):
        calls["n"] += 1
        if calls["n"] < 3:
            raise _rq.ConnectionError("boom")
        return _R()

    monkeypatch.setattr(c._session, "post", post)
    # idempotent: 2 transport failures burn the retry budget, 3rd wins
    assert c.call("readall", idempotent=True) == b"ok"
    assert calls["n"] == 3 and c.is_online()
    # non-idempotent: first transport failure marks offline immediately
    calls["n"] = -10**9  # always raise
    with pytest.raises(errors.DiskNotFound):
        c.call("writeall")
    assert not c.is_online()
    c.close()


def test_rpc_ping_backoff_and_reconnect_hook(monkeypatch):
    from minio_tpu.dist import rpc as rpc_mod
    monkeypatch.setattr(rpc_mod, "HEALTH_INTERVAL_S", 0.02)
    c = rpc_mod.RPCClient("http://127.0.0.1:1", "storage", "s3cr3t")
    pings = {"n": 0}

    class _R:
        status_code = 200

    def get(url, **kw):
        pings["n"] += 1
        if pings["n"] < 3:
            import requests as _rq
            raise _rq.ConnectionError("still down")
        return _R()

    monkeypatch.setattr(c._session, "get", get)
    hook = {"called": 0}

    def bad_hook(_c):
        hook["called"] += 1
        raise RuntimeError("hook explodes")

    c.on_reconnect = bad_hook
    c._mark_offline()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and not c.is_online():
        time.sleep(0.02)
    # exploding on_reconnect did not kill the flip back online
    assert c.is_online() and hook["called"] == 1 and pings["n"] == 3
    c.close()


def test_rpc_fault_injection_layer(monkeypatch):
    from minio_tpu.dist.rpc import RPCClient
    c = RPCClient("http://127.0.0.1:1", "storage", "s3cr3t")

    class _R:
        status_code = 200
        content = b"ok"
        headers: dict = {}

    monkeypatch.setattr(c._session, "post", lambda *a, **k: _R())
    fault.arm("rpc:127.0.0.1:readall:error(FileNotFound)@count=1")
    with pytest.raises(errors.FileNotFound):
        c.call("readall")
    assert c.call("readall") == b"ok"  # budget spent
    c.close()


# --- MRF drop accounting (satellite) ----------------------------------------


def test_mrf_drop_oldest_keeps_newest_and_counts():
    from minio_tpu.scanner.mrf import MRFHealer
    mrf = MRFHealer(None, max_queue=2)  # not started: queue fills
    for i in range(5):
        mrf.add_partial("b", f"o{i}")
    st = mrf.stats()
    assert st["queued"] == 2 and st["dropped"] == 3
    # the NEWEST entries survived the drop-oldest policy
    held = [mrf.q.get_nowait()[1] for _ in range(2)]
    assert held == ["o3", "o4"]
    from minio_tpu.obs.metrics import counters_snapshot
    assert counters_snapshot().get("minio_tpu_mrf_dropped_total", 0) >= 3


# --- heal under chaos -------------------------------------------------------


def test_heal_under_delay_fault(tmp_path, monkeypatch):
    """Heal of a missing shard completes correctly while a delay fault
    makes one SOURCE disk a straggler."""
    import shutil
    monkeypatch.setenv("MINIO_TPU_HEDGE_MS", "15")
    ol = _layer(tmp_path)
    body = _body()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    # destroy one disk's copy entirely
    victim = _shard_disk(ol, "o", 3)
    shutil.rmtree(os.path.join(victim.base, "b", "o"))
    src = _shard_disk(ol, "o", 2)
    fault.arm(f"disk:{src.endpoint()}:read_at:delay(50)")
    res = ol.heal_object("b", "o")
    assert "missing" in res.before_state
    assert res.after_state.count("ok") == len(ol.disks)
    fault.clear()
    assert ol.get_object_bytes("b", "o") == body


# --- admin API + exposition -------------------------------------------------


def test_admin_fault_api_and_metrics(tmp_path):
    from s3client import S3Client

    from minio_tpu.madmin import AdminClient
    from minio_tpu.obs.metrics import render_prometheus
    from minio_tpu.server import S3Server
    obj = ErasureObjects(
        [XLStorage(os.path.join(tmp_path, f"d{i}")) for i in range(4)],
        default_parity=2)
    srv = S3Server(obj, "127.0.0.1", 0, access_key="fak",
                   secret_key="fsecret1")
    srv.start_background()
    try:
        adm = AdminClient(srv.endpoint(), "fak", "fsecret1")
        rid = adm.fault_arm("disk:*:read_at:delay(1)@ttl=60")
        st = adm.fault_status()
        assert [r["id"] for r in st["rules"]] == [rid]
        assert st["disks"] and st["disks"][0]["state"] == "ok"
        adm.fault_disarm(rid)
        rid2 = adm.fault_arm({"layer": "kernel", "op": "encode",
                              "action": "error", "count": 1})
        assert adm.fault_status()["rules"][0]["id"] == rid2
        adm.fault_clear()
        assert adm.fault_status()["rules"] == []
        # exposition carries the health families
        c = S3Client(srv.endpoint(), "fak", "fsecret1")
        c.request("PUT", "/fb")
        c.request("PUT", "/fb/o", body=b"y" * 1024)
        c.request("GET", "/fb/o")
        text = render_prometheus(srv).decode()
        assert "# TYPE minio_tpu_disk_state gauge" in text
        assert 'minio_tpu_disk_state{' in text
        assert "# TYPE minio_tpu_hedge_threshold_seconds gauge" in text
    finally:
        srv.shutdown()
