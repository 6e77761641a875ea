"""Async cross-node replication (ISSUE 19): the chaos matrix over a
real in-process topology (dist.harness.LocalCluster), plus the rule
grammar, the per-object status lifecycle, and the journal recovery
semantics the plane's durability claims rest on.

Matrix (one module-scoped 4-node cluster; tests restore what they
break):

* config surface — PUT/GET/DELETE ``?replication`` round-trip with
  validation (malformed XML and destination-less rules 400),
* status lifecycle — PENDING stamped at PUT, flipped COMPLETED by the
  worker, the target copy bit-exact and REPLICA-marked (loop guard),
  deletes propagating when the rule opts in,
* kill TARGET mid-multipart — the multipart-complete charge parks in
  the retry journal while the target is dead and ships after rejoin,
* partition TARGET mid-stream — same proof through an RPC-layer
  blackhole instead of a process kill,
* restart SOURCE mid-backlog-drain — obligations recorded in the
  journal replay into the fresh process and still drain,
* torn journal — a crash mid-rename loads as empty (sweep re-finds the
  debt), never a startup crash,
* resync — a rebuilt (wiped) target repopulates from the source's
  namespace via the admin resync surface.
"""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from s3client import S3Client  # noqa: E402

from minio_tpu.bucket import replicate as repl  # noqa: E402
from minio_tpu.dist.harness import LocalCluster  # noqa: E402
from minio_tpu.fault import node as fnode  # noqa: E402
from minio_tpu.madmin import AdminClient  # noqa: E402

AK = SK = "minioadmin"


def wait_until(fn, timeout=20.0, step=0.1, msg="condition"):
    """Poll ``fn`` to True. A raised exception counts as 'not yet':
    chaos polls race mid-flight writes and node restarts, and a
    transient broken read must re-poll, not fail the proof — the final
    successful poll is always a clean bit-exact read."""
    deadline = time.monotonic() + timeout
    err = None
    while time.monotonic() < deadline:
        try:
            if fn():
                return
            err = None
        except Exception as e:  # noqa: BLE001 — retried until deadline
            err = e
        time.sleep(step)
    raise AssertionError(f"timed out waiting for {msg} (last: {err!r})")


def rule_xml(dst_bucket: str, endpoint: str, prefix: str = "",
             deletes: bool = True, priority: int = 1) -> bytes:
    dmr = "Enabled" if deletes else "Disabled"
    pfx = f"<Filter><Prefix>{prefix}</Prefix></Filter>" if prefix else ""
    return (
        "<ReplicationConfiguration><Rule><ID>t</ID>"
        f"<Status>Enabled</Status><Priority>{priority}</Priority>{pfx}"
        f"<DeleteMarkerReplication><Status>{dmr}</Status>"
        "</DeleteMarkerReplication><Destination>"
        f"<Bucket>{dst_bucket}</Bucket><Endpoint>{endpoint}</Endpoint>"
        "</Destination></Rule></ReplicationConfiguration>").encode()


# --- grammar + journal units (no cluster) ------------------------------------


def test_rule_parse_grammar():
    rules = repl.parse_replication(rule_xml("dstb", "http://n2:9000/",
                                            prefix="logs/"))
    assert len(rules) == 1
    r = rules[0]
    assert r.enabled and r.priority == 1 and r.prefix == "logs/"
    assert r.target_bucket == "dstb"
    assert r.endpoint == "http://n2:9000"          # trailing / stripped
    assert r.delete_replication
    # arn-style destination bucket resolves to the bare name
    arn = rule_xml("arn:aws:s3:::dstb", "http://n2:9000")
    assert repl.parse_replication(arn)[0].target_bucket == "dstb"
    # namespaced S3 schema parses too
    ns = (b'<ReplicationConfiguration xmlns="http://s3.amazonaws.com/'
          b'doc/2006-03-01/"><Rule><Status>Enabled</Status>'
          b'<Destination><Bucket>d</Bucket>'
          b'<Endpoint>http://x:1</Endpoint></Destination></Rule>'
          b'</ReplicationConfiguration>')
    assert repl.parse_replication(ns)[0].target_bucket == "d"
    # an enabled rule without a destination fails validation
    bad = (b"<ReplicationConfiguration><Rule><Status>Enabled</Status>"
           b"</Rule></ReplicationConfiguration>")
    with pytest.raises(ValueError):
        repl.validate_replication(bad)
    assert repl.parse_replication(b"") == []


def test_torn_journal_loads_empty(tmp_path):
    """A torn journal (crash mid-rename left invalid JSON) must load
    as zero recovered entries — the scanner sweep re-finds the debt —
    and a healthy journal must replay every obligation, delete ops
    surviving dedupe collisions (sticky)."""
    rs = repl.ReplicationSys(None, None)
    path = str(tmp_path / "replication.json")
    rs.attach_persistence(path)
    rs.dq.add("b", "o1", "", mode="put")
    rs.dq.add("b", "o2", "", mode="delete")
    rs.flush_journal()
    # healthy replay: both entries come back, the delete stays a delete
    rs2 = repl.ReplicationSys(None, None)
    assert rs2.attach_persistence(path) == 2
    modes = {e[1]: e[3] for e in list(rs2.dq.q.queue)}
    assert modes == {"o1": "put", "o2": "delete"}
    # torn: truncate mid-document
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])
    rs3 = repl.ReplicationSys(None, None)
    assert rs3.attach_persistence(path) == 0
    assert rs3.stats()["queued"] == 0


# --- the cluster matrix ------------------------------------------------------


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    # chaos-speed knobs: fast replication retry backoff + RPC timeout,
    # fast peer reconnect probing (the rejoin kick path)
    mp.setenv("MINIO_TPU_REPLICATION_RETRY_BASE_S", "0.2")
    mp.setenv("MINIO_TPU_REPLICATION_TIMEOUT_S", "5")
    # a tripped remote-disk wrapper re-onlines on this cadence; the
    # default 5 s stretches the between-test health barrier
    mp.setenv("MINIO_TPU_HEALTH_COOLDOWN_S", "1")
    from minio_tpu.dist import rpc as rpc_mod
    mp.setattr(rpc_mod, "HEALTH_MAX_INTERVAL_S", 2.0)
    root = tmp_path_factory.mktemp("replchaos")
    lc = LocalCluster(str(root), nodes=4, disks_per_node=2, parity=2)
    yield lc
    lc.shutdown()
    mp.undo()


@pytest.fixture
def c(cluster):
    return S3Client(cluster.urls[0], AK, SK)


def _rs(cluster, i=0):
    return cluster.nodes[i].server.replication_sys


def _internal(cluster, bucket, key, i=0):
    return cluster.nodes[i].obj.get_object_info(bucket, key).internal


def _wait_cluster_healthy(cluster, timeout=30.0):
    """Chaos-leg barrier: every node must see every drive online before
    the next fault goes in. A tripped remote-disk wrapper
    (storage.health) fast-fails DiskNotFound until its cooldown probe
    re-onlines it; stacking a fresh kill/partition on top of that
    window drops writes below quorum and 503s the whole leg."""
    wait_until(lambda: all(
        n.obj is not None and
        n.obj.storage_info()["disks_offline"] == 0
        for n in cluster.nodes), timeout=timeout,
        msg="all drives back online")


def _set_rule(c, cluster, src, dst, target=1, **kw):
    _wait_cluster_healthy(cluster)
    assert c.put_bucket(src).status_code in (200, 409)
    r = c.request("PUT", f"/{src}", query={"replication": ""},
                  body=rule_xml(dst, cluster.urls[target], **kw))
    assert r.status_code == 200, r.text


def test_config_surface_roundtrip(c, cluster):
    src = "cfg-src"
    assert c.put_bucket(src).status_code == 200
    # no config yet -> 404
    r = c.request("GET", f"/{src}", query={"replication": ""})
    assert r.status_code == 404
    # malformed XML and destination-less rules are rejected
    r = c.request("PUT", f"/{src}", query={"replication": ""},
                  body=b"<not xml")
    assert r.status_code == 400
    r = c.request("PUT", f"/{src}", query={"replication": ""},
                  body=b"<ReplicationConfiguration><Rule>"
                       b"<Status>Enabled</Status></Rule>"
                       b"</ReplicationConfiguration>")
    assert r.status_code == 400
    xml = rule_xml("cfg-dst", cluster.urls[1])
    r = c.request("PUT", f"/{src}", query={"replication": ""}, body=xml)
    assert r.status_code == 200
    r = c.request("GET", f"/{src}", query={"replication": ""})
    assert r.status_code == 200 and r.content == xml
    r = c.request("DELETE", f"/{src}", query={"replication": ""})
    assert r.status_code == 204
    r = c.request("GET", f"/{src}", query={"replication": ""})
    assert r.status_code == 404


def test_status_lifecycle_ship_and_delete(c, cluster):
    """PENDING at PUT -> worker ships -> COMPLETED on the source, the
    replica bit-exact and REPLICA-marked on the target; a later delete
    propagates (the rule opted in)."""
    src, dst = "life-src", "life-dst"
    _set_rule(c, cluster, src, dst)
    body = b"replicate me " * 997
    assert c.put_object(src, "a/k1", body).status_code == 200
    # charged PENDING on the request path, before the worker ships
    assert _internal(cluster, src, "a/k1")[repl.META_REP_STATUS] \
        in (repl.PENDING, repl.COMPLETED)

    def replicated():
        r = S3Client(cluster.urls[1], AK, SK).get_object(dst, "a/k1")
        return r.status_code == 200 and r.content == body
    wait_until(replicated, msg="replica on target")
    wait_until(lambda: _internal(cluster, src, "a/k1")
               [repl.META_REP_STATUS] == repl.COMPLETED,
               msg="COMPLETED status")
    # the target copy is marked REPLICA so it can never re-replicate
    assert _internal(cluster, dst, "a/k1", i=1)[repl.META_REPLICA] == \
        repl.REPLICA
    # lag was observed through the Window -> SLO probe shape. The worker
    # flips the status first and records the lag after it, so COMPLETED
    # above does not yet say that the sample is in
    wait_until(lambda: _rs(cluster).lag_report()["samples"] >= 1,
               msg="lag sample")
    assert _rs(cluster).lag_report()["ok"]
    # delete propagates
    assert c.delete_object(src, "a/k1").status_code == 204
    wait_until(lambda: S3Client(cluster.urls[1], AK, SK).get_object(
        dst, "a/k1").status_code == 404, msg="replica delete")


def test_slo_async_probe_carries_replication(cluster):
    from minio_tpu.obs import slo
    rep = slo.report()
    probe = rep.get("async", {}).get("replication")
    assert probe is not None and "lag_p99_s" in probe and "ok" in probe


def test_kill_target_mid_multipart(c, cluster):
    """The target dies between upload start and complete: the
    multipart-complete charge parks in the retry journal (never
    dropped) and the full object ships bit-exact after rejoin."""
    src, dst = "mp-src", "mp-dst"
    _set_rule(c, cluster, src, dst)
    r = c.request("POST", f"/{src}/big", query={"uploads": ""})
    assert r.status_code == 200
    uid = r.text.split("<UploadId>")[1].split("</UploadId>")[0]
    p1, p2 = os.urandom(5 << 20), os.urandom(64 << 10)
    etags = []
    for n, part in ((1, p1), (2, p2)):
        r = c.request("PUT", f"/{src}/big",
                      query={"partNumber": str(n), "uploadId": uid},
                      body=part)
        assert r.status_code == 200
        etags.append(r.headers["ETag"])
    cluster.kill(1)                      # TARGET dies before complete
    try:
        parts = "".join(
            f"<Part><PartNumber>{i + 1}</PartNumber>"
            f"<ETag>{e}</ETag></Part>" for i, e in enumerate(etags))
        r = c.request("POST", f"/{src}/big", query={"uploadId": uid},
                      body=f"<CompleteMultipartUpload>{parts}"
                           "</CompleteMultipartUpload>".encode())
        assert r.status_code == 200
        # the obligation is parked (queued or in retry), not lost
        rs = _rs(cluster)
        wait_until(lambda: rs.dq.queued((src, "big", "")),
                   msg="obligation parked while target down")
        assert _internal(cluster, src, "big")[repl.META_REP_STATUS] \
            == repl.PENDING
    finally:
        cluster.restart(1)

    def replicated():
        r = S3Client(cluster.urls[1], AK, SK).get_object(dst, "big")
        return r.status_code == 200 and r.content == p1 + p2
    wait_until(replicated, timeout=40, msg="multipart replica after "
               "rejoin")
    wait_until(lambda: not rs.dq.queued((src, "big", "")),
               msg="obligation settled")


def test_partition_target_mid_stream(c, cluster):
    """Same proof through an asymmetric RPC blackhole: obligations park
    while the target is unreachable and drain after the partition
    heals — the process never died, only the wire."""
    src, dst = "part-src", "part-dst"
    _set_rule(c, cluster, src, dst, target=2)
    bodies = {f"s/k{i}": os.urandom(4096) for i in range(4)}
    rid = fnode.partition(cluster.urls[2])
    try:
        for k, b in bodies.items():
            assert c.put_object(src, k, b).status_code == 200
        rs = _rs(cluster)
        wait_until(lambda: any(
            rs.dq.queued((src, k, "")) for k in bodies),
            msg="obligations parked under partition")
    finally:
        from minio_tpu import fault
        fault.disarm(rid)
    tcl = S3Client(cluster.urls[2], AK, SK)

    def all_replicated():
        return all(tcl.get_object(dst, k).status_code == 200 and
                   tcl.get_object(dst, k).content == b
                   for k, b in bodies.items())
    wait_until(all_replicated, timeout=40,
               msg="backlog drained after partition heal")
    st = rs.stats()
    assert st["queued"] == 0 and st["dropped"] == 0


def test_source_restart_mid_backlog_drain(c, cluster):
    """Obligations charged while the target is down survive a SOURCE
    process restart through the journal: the fresh node replays them
    and the backlog still drains to zero after the target rejoins."""
    import json as _json
    src, dst = "jrn-src", "jrn-dst"
    _set_rule(c, cluster, src, dst)
    bodies = {f"j/k{i}": os.urandom(2048) for i in range(3)}
    cluster.kill(1)
    try:
        for k, b in bodies.items():
            assert c.put_object(src, k, b).status_code == 200
        rs = _rs(cluster)
        wait_until(lambda: all(
            rs.dq.queued((src, k, "")) for k in bodies),
            msg="backlog parked while target down")
        rs.flush_journal()              # deterministic journal state
        jpath = rs.dq._persist_path
        cluster.kill(0)                 # SOURCE dies mid-drain
        # the obligations are durably on disk, not only in the dead
        # process's memory
        with open(jpath, encoding="utf-8") as f:
            recorded = {e["object"] for e in _json.load(f)["entries"]}
        assert set(bodies) <= recorded
    finally:
        # both ends are down and a booting node retries format until
        # every peer answers — the restarts must overlap (the cold-boot
        # shape), or each would wait out the other's format forever
        import threading
        t = threading.Thread(target=cluster.restart, args=(1,),
                             daemon=True, name="restart-target")
        t.start()
        cluster.restart(0)              # source reboots over the port
        t.join(timeout=90)
        assert cluster.nodes[1].obj is not None, "target failed to boot"
    # the fresh source process replays the journal and drains it
    tcl = S3Client(cluster.urls[1], AK, SK)

    def all_replicated():
        return all(tcl.get_object(dst, k).status_code == 200 and
                   tcl.get_object(dst, k).content == b
                   for k, b in bodies.items())
    wait_until(all_replicated, timeout=40,
               msg="journal-replayed backlog drained")


def test_resync_rebuilt_target(c, cluster):
    """Wipe the target's replica bucket (a rebuilt target) and replay
    the source namespace through the admin resync surface."""
    src, dst = "rsyn-src", "rsyn-dst"
    _set_rule(c, cluster, src, dst)
    bodies = {f"r/k{i}": os.urandom(1024) for i in range(3)}
    tcl = S3Client(cluster.urls[1], AK, SK)
    for k, b in bodies.items():
        assert c.put_object(src, k, b).status_code == 200
    wait_until(lambda: all(tcl.get_object(dst, k).status_code == 200
                           for k in bodies), msg="initial replication")
    for k in bodies:                     # the target loses everything
        assert tcl.delete_object(dst, k).status_code == 204
    adm = AdminClient(cluster.urls[0], AK, SK)
    out = adm.replication_resync(src, force=True)
    assert out["scheduled"] == len(bodies)

    def restored():
        return all(tcl.get_object(dst, k).status_code == 200 and
                   tcl.get_object(dst, k).content == b
                   for k, b in bodies.items())
    wait_until(restored, timeout=30, msg="resync repopulated target")
    st = adm.replication_status(peers=True)
    assert st["resynced"] >= len(bodies)
    assert st["lag"]["backlog"] == 0
    assert any(p.get("endpoint") for p in st.get("peers", []))


def test_metrics_exposition_families(c, cluster):
    import requests
    text = requests.get(cluster.urls[0] + "/minio/v2/metrics",
                        timeout=10).text
    for fam in ("minio_tpu_replication_completed_total",
                "minio_tpu_replication_backlog",
                "minio_tpu_replication_retry_pending",
                "minio_tpu_replication_lag_seconds"):
        assert fam in text, fam
