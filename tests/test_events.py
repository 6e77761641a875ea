"""Event notification: ARN routing, webhook delivery to a live HTTP
target, and crash-safe retry from the on-disk queue store (reference
pkg/event/target/webhook.go + queuestore.go)."""
import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from s3client import S3Client  # noqa: E402

from minio_tpu.event import (EventNotifier, QueueStore, WebhookTarget,
                             parse_notification_xml)  # noqa: E402
from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.server import S3Server  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402

AK, SK = "evak", "evsk"


class _Sink(BaseHTTPRequestHandler):
    received: list = []
    fail = False

    def do_POST(self):  # noqa: N802
        body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        if type(self).fail:
            self.send_response(503)
            self.end_headers()
            return
        type(self).received.append(
            (self.headers.get("Authorization", ""), json.loads(body)))
        self.send_response(200)
        self.end_headers()

    def log_message(self, *a):  # silence
        pass


@pytest.fixture
def sink():
    class Snk(_Sink):
        received = []
        fail = False
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Snk)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield Snk, f"http://127.0.0.1:{httpd.server_address[1]}/hook"
    httpd.shutdown()


NOTIF_XML = """<NotificationConfiguration>
  <QueueConfiguration>
    <Id>1</Id>
    <Queue>arn:minio:sqs:us-east-1:t1:webhook</Queue>
    <Event>s3:ObjectCreated:*</Event>
    <Filter><S3Key>
      <FilterRule><Name>prefix</Name><Value>docs/</Value></FilterRule>
      <FilterRule><Name>suffix</Name><Value>.txt</Value></FilterRule>
    </S3Key></Filter>
  </QueueConfiguration>
  <QueueConfiguration>
    <Id>2</Id>
    <Queue>arn:minio:sqs:us-east-1:t1:webhook</Queue>
    <Event>s3:ObjectRemoved:*</Event>
  </QueueConfiguration>
</NotificationConfiguration>"""


def test_rule_parsing_and_routing():
    rules = parse_notification_xml(NOTIF_XML.encode())
    assert len(rules.rules) == 2
    assert rules.route("s3:ObjectCreated:Put", "docs/a.txt") == \
        ["arn:minio:sqs:us-east-1:t1:webhook"]
    assert rules.route("s3:ObjectCreated:Put", "docs/a.pdf") == []
    assert rules.route("s3:ObjectCreated:Put", "other/a.txt") == []
    assert rules.route("s3:ObjectRemoved:Delete", "anything") == \
        ["arn:minio:sqs:us-east-1:t1:webhook"]


def _server(tmp_path, sink_url):
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=2)
    srv = S3Server(obj, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    target = WebhookTarget("t1", sink_url, auth_token="sekrit")
    srv.enable_events([target], queue_root=str(tmp_path / "queue"))
    srv.start_background()
    return srv


def _wait(cond, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(0.05)
    return False


def test_put_delivers_s3_shaped_event(tmp_path, sink):
    Snk, url = sink
    srv = _server(tmp_path, url)
    try:
        c = S3Client(srv.endpoint(), AK, SK)
        assert c.request("PUT", "/evb").status_code == 200
        r = c.request("PUT", "/evb", query={"notification": ""},
                      body=NOTIF_XML.encode())
        assert r.status_code == 200, r.text
        c.request("PUT", "/evb/docs/hello.txt", body=b"hi there")
        c.request("PUT", "/evb/docs/skip.pdf", body=b"nope")
        assert _wait(lambda: len(Snk.received) >= 1)
        auth, env = Snk.received[0]
        assert auth == "Bearer sekrit"
        assert env["EventName"] == "s3:ObjectCreated:Put"
        rec = env["Records"][0]
        assert rec["eventVersion"] == "2.0"
        assert rec["s3"]["bucket"]["name"] == "evb"
        assert rec["s3"]["object"]["key"] == "docs/hello.txt"
        assert rec["s3"]["object"]["size"] == 8
        # the .pdf must NOT arrive
        time.sleep(0.3)
        keys = [e["Records"][0]["s3"]["object"]["key"]
                for _, e in Snk.received]
        assert "docs/skip.pdf" not in keys
        # delete event (rule 2: no filter)
        c.request("DELETE", "/evb/docs/hello.txt")
        assert _wait(lambda: any(
            e["EventName"].startswith("s3:ObjectRemoved")
            for _, e in Snk.received))
    finally:
        srv.shutdown()


def test_unknown_arn_rejected(tmp_path, sink):
    Snk, url = sink
    srv = _server(tmp_path, url)
    try:
        c = S3Client(srv.endpoint(), AK, SK)
        c.request("PUT", "/evb2")
        bad = NOTIF_XML.replace("t1:webhook", "nope:webhook")
        r = c.request("PUT", "/evb2", query={"notification": ""},
                      body=bad.encode())
        assert r.status_code == 400
        assert "unknown notification target" in r.text
    finally:
        srv.shutdown()


def test_queue_survives_restart(tmp_path):
    """Events enqueued while the target is down are delivered by a NEW
    store instance pointed at the same directory (restart semantics)."""
    calls = []
    fails = {"n": 3}

    def flaky(record):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise RuntimeError("target down")
        calls.append(record)

    qdir = str(tmp_path / "q")
    store = QueueStore(qdir, lambda r: (_ for _ in ()).throw(
        RuntimeError("always down")), retry_base_s=0.05)
    store.start()
    for i in range(5):
        assert store.put({"i": i})
    time.sleep(0.3)
    store.stop()
    assert calls == []
    assert len(os.listdir(qdir)) == 5  # persisted, undelivered
    # "restart": new store over the same dir with a working sender
    store2 = QueueStore(qdir, flaky, retry_base_s=0.05).start()
    assert _wait(lambda: len(calls) == 5)
    assert [r["i"] for r in calls] == [0, 1, 2, 3, 4]  # oldest first
    store2.stop()
    assert os.listdir(qdir) == []


def test_queue_limit(tmp_path):
    store = QueueStore(str(tmp_path / "q"), lambda r: None, limit=3)
    assert all(store.put({"i": i}) for i in range(3))
    assert not store.put({"i": 99})
    assert store.failed_puts == 1


def test_listen_bucket_notification(tmp_path):
    """Live event stream (minio ListenBucketNotification extension):
    events stream as JSON lines with prefix/suffix/event filtering and
    no stored notification config."""
    import json
    import threading

    import requests

    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.server import S3Server
    from minio_tpu.storage import XLStorage
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=1)
    srv = S3Server(obj, "127.0.0.1", 0, access_key="lk", secret_key="lsec")
    srv.start_background()
    try:
        from s3client import S3Client
        c = S3Client(srv.endpoint(), "lk", "lsec")
        assert c.request("PUT", "/lb").status_code == 200
        got: list = []
        ready = threading.Event()

        def listener():
            r = c.request("GET", "/lb", query={
                "events": "s3:ObjectCreated:*", "prefix": "logs/",
                "timeout": "15"})
            ready.set()  # headers received implies subscription is live
            for ln in r.iter_lines():
                if ln and ln.strip():
                    got.append(json.loads(ln))
                    if len(got) >= 2:
                        break
            r.close()

        t = threading.Thread(target=listener, daemon=True)
        t.start()
        # the subscription registers before the body streams; give the
        # request a moment to reach the handler
        deadline = time.time() + 10
        while not srv._notifier or not srv._notifier._listeners:
            assert time.time() < deadline, "listener never registered"
            time.sleep(0.05)
        c.request("PUT", "/lb/other/skip.txt", body=b"x")   # filtered out
        c.request("PUT", "/lb/logs/a.txt", body=b"1")
        c.request("DELETE", "/lb/logs/a.txt")               # wrong event
        c.request("PUT", "/lb/logs/b.txt", body=b"2")
        t.join(timeout=20)
        assert len(got) == 2, got
        keys = [g["Records"][0]["s3"]["object"]["key"] for g in got]
        assert keys == ["logs/a.txt", "logs/b.txt"]
        assert got[0]["Records"][0]["eventName"].startswith(
            "ObjectCreated")
    finally:
        srv.shutdown()


def test_listen_preserves_replication_chain(tmp_path):
    """Lazily attaching the listen notifier must CHAIN with an existing
    notify hook (replication), not replace it."""
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.server import S3Server
    from minio_tpu.storage import XLStorage
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=1)
    srv = S3Server(obj, "127.0.0.1", 0, access_key="ck", secret_key="csec")
    seen = []

    class _FakePool:
        def on_event(self, event, bucket, oi):
            seen.append((event, getattr(oi, "name", "")))

    srv.enable_replication(_FakePool())
    srv.start_background()
    try:
        from s3client import S3Client
        c = S3Client(srv.endpoint(), "ck", "csec")
        c.request("PUT", "/rb")
        notifier = srv.ensure_notifier()  # what a listen request does
        sub = notifier.listen("rb")
        c.request("PUT", "/rb/o", body=b"x")
        deadline = time.time() + 10
        while not seen and time.time() < deadline:
            time.sleep(0.05)
        # the replication hook STILL fires...
        assert ("s3:ObjectCreated:Put", "o") in seen
        # ...and the listener got the same event
        rec = sub.q.get(timeout=5)
        assert rec["s3"]["object"]["key"] == "o"
        notifier.unlisten(sub)
    finally:
        srv.shutdown()


def test_notifier_attach_serialized_and_chained(tmp_path):
    """enable_replication / enable_cross_replication read-chain-store
    self.notify under _notifier_lock (graftlint GL020 regression: an
    unguarded attach racing another notifier hookup silently drops one
    link). The attach must wait for the lock, and afterwards BOTH links
    fire on one notify."""
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=1)
    srv = S3Server(obj, "127.0.0.1", 0, access_key="ak", secret_key="sk")
    seen = []

    class _Pool:
        def on_event(self, event, bucket, oi):
            seen.append(("pool", event))

    class _Rs:
        def charge(self, event, bucket, oi):
            seen.append(("rs", event))

        def lag_report(self):
            return {}

    attached = threading.Event()
    t = threading.Thread(
        target=lambda: (srv.enable_replication(_Pool()), attached.set()))
    with srv._notifier_lock:
        t.start()
        time.sleep(0.2)
        assert not attached.is_set()   # attach serialized behind the lock
    t.join(10)
    assert attached.is_set()
    srv.enable_cross_replication(_Rs())
    oi = type("OI", (), {"name": "o"})()
    srv.notify("s3:ObjectCreated:Put", "b", oi)
    assert ("pool", "s3:ObjectCreated:Put") in seen
    assert ("rs", "s3:ObjectCreated:Put") in seen


def test_failed_put_rollback_consistent(tmp_path, monkeypatch):
    """A put that fails at the durable-write step rolls back _count and
    bumps failed_puts in ONE _count_lock section (graftlint GL020
    regression: the counter write used to sit outside the lock)."""
    from minio_tpu.storage import durability as dur

    def boom(path, data):
        raise OSError("disk full")

    monkeypatch.setattr(dur, "durable_write", boom)
    store = QueueStore(str(tmp_path / "q"), lambda r: None, limit=3)
    assert store.put({"i": 0}) is False
    assert store.failed_puts == 1
    with store._count_lock:
        assert store._count == 0       # the reservation was rolled back


def test_dead_target_queue_caps_and_puts_never_stall(tmp_path):
    """The target is down for good and its queue is small: concurrent
    PUTs all answer 200 without waiting for it, the queue never holds
    more than its limit, and every event past it is counted, by the
    store and by the exported drop counter."""
    from minio_tpu.obs import metrics as mx
    limit, clients, puts = 8, 4, 10
    srv = _server(tmp_path, "http://127.0.0.1:9/nobody-listens")
    arn = "arn:minio:sqs:us-east-1:t1:webhook"
    n = srv.ensure_notifier()
    n.stores[arn].stop()
    # built directly for the small limit; a long retry base keeps the
    # doomed sender quiet during the test
    store = n.stores[arn] = QueueStore(
        str(tmp_path / "small-queue"), n.targets[arn].send, limit=limit,
        retry_base_s=5.0).start()
    counter = f'minio_tpu_notify_events_dropped_total{{target="{arn}"}}'
    dropped0 = mx.counters_snapshot().get(counter, 0)
    slowest: list[float] = []
    codes: list[int] = []
    over: list[int] = []

    def client(wid: int) -> None:
        c = S3Client(srv.endpoint(), AK, SK)
        for j in range(puts):
            t0 = time.monotonic()
            codes.append(c.put_object("evq", f"w{wid}-{j}", b"body")
                         .status_code)
            slowest.append(time.monotonic() - t0)
            queued = len(store._pending())
            if queued > limit:
                over.append(queued)
    try:
        c = S3Client(srv.endpoint(), AK, SK)
        assert c.put_bucket("evq").status_code == 200
        xml = ("<NotificationConfiguration><QueueConfiguration>"
               f"<Queue>{arn}</Queue><Event>s3:ObjectCreated:*</Event>"
               "</QueueConfiguration></NotificationConfiguration>")
        assert c.request("PUT", "/evq", query={"notification": ""},
                         body=xml.encode()).status_code == 200
        ths = [threading.Thread(target=client, args=(w,), daemon=True,
                                name=f"event-client-{w}")
               for w in range(clients)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        assert codes == [200] * (clients * puts), codes
        assert max(slowest) < 30.0, max(slowest)   # no PUT waits on it
        assert not over, over
        # an event is routed after its PUT has been answered
        assert _wait(lambda: store.failed_puts >= clients * puts - limit)
        assert store.delivered == 0
        assert len(store._pending()) == limit
        assert store.failed_puts == clients * puts - limit
        assert mx.counters_snapshot().get(counter, 0) - dropped0 == \
            store.failed_puts
    finally:
        store.stop()
        srv.shutdown()
