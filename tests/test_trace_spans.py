"""Request-scoped span trees (obs/spans.py): x-amz-request-id stamping,
http -> objectlayer -> kernel(link) -> storage trees assembled from a
real degraded GET, truthful span links when one dispatch flush serves
two requests, tail-sampled slow-trace capture with NO live trace
subscriber, audit/trace joins, and the profiling session lifecycle."""
import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from s3client import S3Client  # noqa: E402

from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.obs import spans as sp  # noqa: E402
from minio_tpu.server import S3Server  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402

AK, SK = "spak", "spsecret123"


@pytest.fixture
def srv(tmp_path, monkeypatch):
    # a sub-millisecond interactive budget makes every request breach it:
    # tail sampling keeps everything, so trees are queryable by id
    monkeypatch.setenv("MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS", "0.0001")
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=2)
    server = S3Server(obj, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    server.start_background()
    yield server
    server.shutdown()


@pytest.fixture
def c(srv):
    return S3Client(srv.endpoint(), AK, SK)


def test_traceparent_roundtrip():
    ctx = sp.SpanContext(sp.new_trace_id(), sp.new_span_id(), sampled=True)
    back = sp.parse_traceparent(sp.to_traceparent(ctx))
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled
    unsampled = sp.SpanContext(sp.new_trace_id(), sp.new_span_id(),
                               sampled=False)
    assert not sp.parse_traceparent(sp.to_traceparent(unsampled)).sampled
    # malformed headers must parse to None, never raise
    for bad in ("", "junk", "00-short-1234-01", "zz" * 40,
                "00-" + "g" * 32 + "-" + "1" * 16 + "-01"):
        assert sp.parse_traceparent(bad) is None


def test_request_id_on_every_response_and_error_xml(c, tmp_path):
    r = c.put_bucket("spb")
    assert r.status_code == 200
    rid = r.headers.get("x-amz-request-id", "")
    assert len(rid) == 32 and int(rid, 16) >= 0
    assert r.headers.get("x-amz-id-2")
    # every response gets a FRESH id
    r2 = c.put_object("spb", "o", b"data")
    assert r2.headers["x-amz-request-id"] != rid
    # error XML names the request and host so client reports join
    # server-side evidence
    r3 = c.get_object("spb", "missing")
    assert r3.status_code == 404
    erid = r3.headers["x-amz-request-id"]
    assert f"<RequestId>{erid}</RequestId>" in r3.text
    assert "<HostId>" in r3.text and "<HostId></HostId>" not in r3.text


def test_degraded_get_yields_full_span_tree(c, srv, tmp_path, monkeypatch):
    """The acceptance tree: a GetObject served through the device
    dispatch path (degraded read -> masked rebuild flush) assembles
    http -> objectlayer -> kernel(link) -> storage spans sharing one
    trace_id, retrievable by ?trace_id= — and the request shows up in
    ?slow=1 without any live trace subscriber attached."""
    # a degraded read of LOCAL shard files is one native call a block
    # and launches no kernel; the queue is the route of sources without
    # an fd (RPC), forced here as chip_smoke.py's device leg forces it
    monkeypatch.setenv("MINIO_TPU_GET_PATH", "dispatch")
    c.put_bucket("spb")
    assert c.put_object("spb", "o", b"q" * 300_000).status_code == 200
    # degrade one DATA shard (erasure index <= k) so the GET must
    # rebuild — losing a parity shard would serve the read as a healthy
    # one whatever the route
    k = len(srv.obj.disks) - 2
    victim = next(d for d in srv.obj.disks
                  if d.read_version("spb", "o", "").erasure.index <= k)
    os.unlink(glob.glob(os.path.join(victim.base, "spb", "o", "*",
                                     "part.1"))[0])
    r = c.get_object("spb", "o")
    assert r.status_code == 200 and len(r.content) == 300_000
    rid = r.headers["x-amz-request-id"]

    # tail-sampled WITHOUT any subscriber: listed by ?slow=1
    slow = c.request("GET", "/minio/admin/v3/trace",
                     query={"slow": "1", "count": "100"}).json()
    entry = next(e for e in slow if e["trace_id"] == rid)
    assert entry["reason"] == "budget" and entry["span_count"] >= 3

    out = c.request("GET", "/minio/admin/v3/trace",
                    query={"trace_id": rid}).json()
    spans = out["spans"]
    assert spans and all(s["trace_id"] == rid for s in spans)
    names = [s["name"] for s in spans]
    assert "objectlayer.get_object" in names
    assert any(n.startswith("kernel.") for n in names)
    assert any(n.startswith("storage.") for n in names)
    by_id = {s["span_id"]: s for s in spans}
    root = out["tree"][0]
    assert root["name"] == "s3.getobject" and len(out["tree"]) == 1
    ol = next(s for s in spans if s["name"] == "objectlayer.get_object")
    assert by_id[ol["parent_span_id"]]["name"] == "s3.getobject"
    kern = next(s for s in spans if s["name"].startswith("kernel."))
    # the flush span links back to the submitting item's context and
    # records its queue wait + batch id
    assert {"trace_id": rid,
            "span_id": by_id[kern["parent_span_id"]]["span_id"]} in \
        kern["links"]
    assert "queue_wait_s" in kern["attrs"]
    assert "batch_id" in kern["attrs"]
    # unknown ids 404 instead of an empty 200
    r = c.request("GET", "/minio/admin/v3/trace",
                  query={"trace_id": "f" * 32})
    assert r.status_code == 404


def test_fast_request_is_not_kept(c, monkeypatch):
    """Tail sampling: within budget -> tracked cheaply, then discarded."""
    monkeypatch.setenv("MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS", "60000")
    c.put_bucket("fastb")
    r = c.put_object("fastb", "o", b"ok")
    rid = r.headers["x-amz-request-id"]
    r = c.request("GET", "/minio/admin/v3/trace", query={"trace_id": rid})
    assert r.status_code == 404


def test_concurrent_requests_share_one_kernel_span():
    """Two traces batched into ONE dispatch flush yield two distinct
    span trees that both contain the SAME kernel span_id, each linking
    every coalesced item's context — per-request trees stay truthful
    under batching."""
    from minio_tpu.ops.rs_jax import get_codec, pack_shards
    from minio_tpu.runtime.dispatch import DispatchQueue
    q = DispatchQueue(max_batch=8, max_delay=0.2)  # long delay: coalesce
    codec = get_codec(4, 2)
    try:
        opened = []
        futs = []
        for i in range(2):
            root, tok = sp.begin_request(sp.new_trace_id())
            d = np.random.default_rng(i).integers(
                0, 256, size=(4, 1024), dtype=np.uint8)
            futs.append(q.encode(codec, pack_shards(d)))
            opened.append((root, tok))
        for f in futs:
            f.result(timeout=30)

        def buffered_kernels():
            with sp._lock:
                return {root.trace_id: [dict(s) for s in
                                        sp._active[root.trace_id]["spans"]
                                        if s["name"].startswith("kernel.")]
                        for root, _ in opened}

        # the flush callback records from a completer thread — wait for
        # both copies to land before closing the traces
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                not all(buffered_kernels().values()):
            time.sleep(0.02)
        os.environ["MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS"] = "0.0001"
        try:
            for root, tok in opened:
                sp.finish_request(root, tok, name="s3.putobject",
                                  duration_s=1.0, cls="interactive",
                                  status=200)
        finally:
            os.environ.pop("MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS", None)
        kernels = {}
        for root, _ in opened:
            ent = sp.store().get(root.trace_id)
            ks = [s for s in (ent or {}).get("spans", ())
                  if s["name"].startswith("kernel.")]
            if ks:
                kernels[root.trace_id] = ks[0]
        assert len(kernels) == 2, "kernel span missing from a trace"
        (ka, kb) = kernels.values()
        assert ka["span_id"] == kb["span_id"], "flush span must be shared"
        assert ka["attrs"]["batch"] == 2
        assert ka["attrs"]["batch_id"] == kb["attrs"]["batch_id"]
        linked = {lk["trace_id"] for lk in ka["links"]}
        assert linked == set(kernels), \
            "kernel span must link every coalesced item's context"
        assert ka["trace_id"] != kb["trace_id"]
    finally:
        q.stop()


def test_pipelined_items_collapse_into_one_kernel_record():
    """A request contributing SEVERAL items to one flush (pipelined PUT
    windows) gets ONE kernel span record carrying its item count and
    oldest queue wait — not one duplicate per item."""
    from minio_tpu.ops.rs_jax import get_codec, pack_shards
    from minio_tpu.runtime.dispatch import DispatchQueue
    q = DispatchQueue(max_batch=8, max_delay=0.2)
    codec = get_codec(4, 2)
    root, tok = sp.begin_request(sp.new_trace_id())
    try:
        futs = [q.encode(codec, pack_shards(
            np.random.default_rng(i).integers(0, 256, size=(4, 1024),
                                              dtype=np.uint8)))
                for i in range(3)]
        for f in futs:
            f.result(timeout=30)
        deadline = time.monotonic() + 10
        ks = []
        while time.monotonic() < deadline and not ks:
            with sp._lock:
                ks = [dict(s) for s in
                      sp._active[root.trace_id]["spans"]
                      if s["name"].startswith("kernel.")]
            time.sleep(0.02)
        assert len(ks) == 1, ks
        assert ks[0]["attrs"]["items"] == 3
        assert ks[0]["attrs"]["batch"] == 3
        assert len(ks[0]["links"]) == 1  # one submitting context
    finally:
        sp.finish_request(root, tok, name="s3.putobject",
                          duration_s=0.0, status=200)
        q.stop()


def test_audit_entries_join_traces(c):
    """Audit entries carry trace_id/request_id + status/duration and
    mirror into the admin console plane on their own ring (flood-
    isolated from error-log history)."""
    import time as _t

    from minio_tpu.obs.logger import log_sys
    c.put_bucket("audb")
    rid = c.put_object("audb", "o", b"z").headers["x-amz-request-id"]
    # the audit entry lands in the handler's finally AFTER the response
    # is on the wire — poll briefly instead of racing the server thread
    # (loses only on a saturated suite host, but loses for real)
    ent = None
    deadline = _t.monotonic() + 5.0
    while ent is None and _t.monotonic() < deadline:
        ent = next((e for e in list(log_sys().audit_ring)
                    if e.get("trace_id") == rid), None)
        if ent is None:
            _t.sleep(0.02)
    assert ent is not None, "audit entry never appeared"
    assert ent["type"] == "audit"
    assert ent["request_id"] == rid
    assert ent["status"] == 200
    assert ent["duration_s"] > 0
    assert ent["api"] == "s3.putobject"
    # served by the admin logs endpoint under ?type=audit — and NOT
    # mixed into the error-log ring it would flood
    logs = c.request("GET", "/minio/admin/v3/logs",
                     query={"n": "500", "type": "audit"}).json()
    assert any(e.get("trace_id") == rid for e in logs)
    assert not any(e.get("type") == "audit" for e in list(log_sys().ring))


def test_top_api_links_worst_sample_to_trace(c, tmp_path):
    c.put_bucket("topb")
    rid = c.get_object("topb", "nope").headers["x-amz-request-id"]
    top = c.request("GET", "/minio/admin/v3/top/api").json()
    row = top.get("getobject", {})
    assert row.get("worst_trace_id"), top
    assert len(row["worst_trace_id"]) == 32
    assert row.get("worst_ms", 0) > 0
    assert rid  # the link target is fetchable by the same admin route


def test_profiling_reaps_auto_halted_session(monkeypatch):
    """Unified session lifecycle (ISSUE 14 satellite): cpu sessions
    ride obs/profiler's session machinery — the busy error reports the
    session age, and an abandoned session past MAX_SESSION_S is reaped
    by the next start() instead of wedging the profiler."""
    from minio_tpu.obs import profiler
    from minio_tpu.obs import profiling as pf
    # ensure a clean slate whatever earlier tests did
    try:
        pf.stop_and_dump()
    except ValueError:
        pass
    pf.start("cpu")
    # a second start while RUNNING still refuses, naming the state/age
    # (asserted under the REAL 300s threshold — shrinking it first
    # would race this very assertion on a slow host)
    with pytest.raises(ValueError, match="running .*cpu.*started"):
        pf.start("cpu")
    monkeypatch.setattr(profiler, "MAX_SESSION_S", 0.05)
    time.sleep(0.1)  # abandoned past (the now-shrunk) MAX_SESSION_S
    # the stale session is reaped by a fresh start()
    info = pf.start("cpu")
    assert info["kind"] == "cpu"
    kind, data = pf.stop_and_dump()
    assert kind == "cpu" and data.startswith(b"# samples:")


def test_span_buffers_are_bounded(monkeypatch):
    """The active-trace registry refuses tracking past its cap instead
    of growing without bound; the overflowing request runs unsampled."""
    monkeypatch.setattr(sp, "MAX_ACTIVE_TRACES", 4)
    with sp._lock:  # leftovers from earlier tests must not eat the cap
        sp._active.clear()
    opened = []
    try:
        for _ in range(6):
            opened.append(sp.begin_request(sp.new_trace_id()))
        sampled = [ctx for ctx, _ in opened if ctx.sampled]
        unsampled = [ctx for ctx, _ in opened if not ctx.sampled]
        assert unsampled, "cap did not engage"
        assert sampled, "cap engaged too early"
    finally:
        for ctx, tok in reversed(opened):
            sp.finish_request(ctx, tok, name="t", duration_s=0.0,
                              status=200)
