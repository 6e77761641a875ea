"""Distributed-plane tests: dsync quorum locks, storage REST round trips,
multi-node clusters on localhost ports (the in-process analogue of
buildscripts/verify-build.sh dist-erasure + verify-healing.sh)."""
import io
import os
import shutil
import socket
import threading
import time

import numpy as np
import pytest

from minio_tpu.dist.dsync import DRWMutex, LocalLocker, NSLockMap
from minio_tpu.dist.ellipses import expand
from minio_tpu.dist.format import (find_disk_slot, init_format_erasure,
                                   load_format)
from minio_tpu.dist.node import Node
from minio_tpu.dist.topology import pick_set_layout
from minio_tpu.storage import XLStorage
from minio_tpu.utils import errors
from s3client import S3Client

AK, SK = "minioadmin", "minioadmin"


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def rng_bytes(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


# --- ellipses / topology -----------------------------------------------------


def test_ellipses_expansion():
    assert expand("/data/disk{1...4}") == [
        "/data/disk1", "/data/disk2", "/data/disk3", "/data/disk4"]
    assert expand("http://h{1...2}/d{1...2}") == [
        "http://h1/d1", "http://h1/d2", "http://h2/d1", "http://h2/d2"]
    assert expand("/plain") == ["/plain"]
    assert expand("/d{01...03}") == ["/d01", "/d02", "/d03"]
    with pytest.raises(ValueError):
        expand("/d{5...2}")


def test_set_layout():
    assert pick_set_layout(6) == (1, 6)
    assert pick_set_layout(16) == (1, 16)
    assert pick_set_layout(32) == (2, 16)
    assert pick_set_layout(20) == (2, 10)
    with pytest.raises(ValueError):
        pick_set_layout(17)


# --- dsync -------------------------------------------------------------------


def test_local_locker_rw_semantics():
    lk = LocalLocker()
    assert lk.lock("res", "u1", "o1")
    assert not lk.lock("res", "u2", "o2")      # exclusive
    assert not lk.rlock("res", "u3", "o3")     # blocked by writer
    assert lk.unlock("res", "u1")
    assert lk.rlock("res", "u4", "o4")
    assert lk.rlock("res", "u5", "o5")         # shared readers
    assert not lk.lock("res", "u6", "o6")      # blocked by readers
    assert lk.runlock("res", "u4")
    assert lk.runlock("res", "u5")
    assert lk.lock("res", "u7", "o7")


def test_drwmutex_quorum():
    lockers = [LocalLocker() for _ in range(5)]
    m1 = DRWMutex(lockers, "bucket/obj", owner="n1")
    assert m1.get_lock(timeout=1.0)
    # second writer cannot reach quorum while m1 holds 5/5
    m2 = DRWMutex(lockers, "bucket/obj", owner="n2")
    assert not m2.get_lock(timeout=0.3)
    m1.unlock()
    assert m2.get_lock(timeout=1.0)
    m2.unlock()
    # readers share
    r1 = DRWMutex(lockers, "bucket/obj", owner="n3")
    r2 = DRWMutex(lockers, "bucket/obj", owner="n4")
    assert r1.get_rlock(timeout=1.0)
    assert r2.get_rlock(timeout=1.0)
    w = DRWMutex(lockers, "bucket/obj", owner="n5")
    assert not w.get_lock(timeout=0.3)
    r1.unlock()
    r2.unlock()


def test_drwmutex_quorum_with_dead_lockers():
    class Dead:
        def lock(self, *a):
            raise ConnectionError

        rlock = unlock = runlock = lock

    lockers = [LocalLocker(), LocalLocker(), LocalLocker(), Dead(), Dead()]
    m = DRWMutex(lockers, "r", owner="n1")
    assert m.get_lock(timeout=1.0)  # 3/5 grants = quorum
    m.unlock()
    lockers = [LocalLocker(), LocalLocker(), Dead(), Dead(), Dead()]
    m = DRWMutex(lockers, "r", owner="n1")
    assert not m.get_lock(timeout=0.3)  # 2/5 < quorum


def test_drwmutex_failed_quorum_releases_async():
    """ISSUE 12 satellite: a failed quorum releases every acquired
    lock ASYNCHRONOUSLY (drwmutex.go:297) — a locker whose unlock
    stalls must not stretch the acquire loop, and the partial grants
    must still drain once the stall clears."""
    gate = threading.Event()

    class SlowUnlock(LocalLocker):
        def unlock(self, resource, uid):
            gate.wait(5.0)  # a stalled peer answering the release
            return super().unlock(resource, uid)

    slow = SlowUnlock()
    dead_count = 3

    class Dead:
        def lock(self, *a):
            raise ConnectionError

        rlock = unlock = runlock = lock

    lockers = [slow, LocalLocker(),
               *[Dead() for _ in range(dead_count)]]
    m = DRWMutex(lockers, "r", owner="n1")
    t0 = time.monotonic()
    assert not m.get_lock(timeout=0.4)  # 2/5 grants < 3 quorum
    elapsed = time.monotonic() - t0
    # the stalled unlock never ran on the acquire path
    assert elapsed < 2.0, elapsed
    gate.set()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if not slow.snapshot() and not lockers[1].snapshot():
            break
        time.sleep(0.02)
    assert not slow.snapshot(), "granted locks must drain after the stall"
    assert not lockers[1].snapshot()


def test_dynamic_timeout_decays_only_on_success(monkeypatch):
    """ISSUE 12 satellite: under injected locker failures the shared
    operation timeout must RISE (»33% failures) and only decay toward
    the slowest recent success when acquisitions actually succeed."""
    from minio_tpu.dist import dsync as ds
    from minio_tpu.utils.dyntimeout import LOG_SIZE, DynamicTimeout
    dyn = DynamicTimeout(0.12, 0.05)
    monkeypatch.setattr(ds, "OPERATION_TIMEOUT", dyn)

    class Dead:
        def lock(self, *a):
            raise ConnectionError

        rlock = unlock = runlock = lock

    dead = [Dead(), Dead(), Dead()]
    start = dyn.timeout()
    for _ in range(LOG_SIZE):  # a full log of failures
        assert not DRWMutex(dead, "r", owner="nX").get_lock()
    assert dyn.timeout() > start, "all-failure window must raise it"
    raised = dyn.timeout()
    good = [LocalLocker(), LocalLocker(), LocalLocker()]
    for _ in range(LOG_SIZE):  # a full log of fast successes
        m = DRWMutex(good, "r", owner="nY")
        assert m.get_lock()
        m.unlock()
    assert dyn.timeout() < raised, "successes must decay it"
    assert dyn.timeout() >= 0.05, "never below the configured floor"


def test_local_locker_monotonic_age():
    """ISSUE 12 satellite: lease/stale age math runs on the monotonic
    clock — a wall-clock (NTP) step cannot mass-expire live locks."""
    lk = LocalLocker()
    assert lk.lock("res", "u1", "o1")
    with lk._lock:
        entry = lk._table["res"][0]
        entry["ts"] -= 10_000.0  # simulated NTP step: wall jumps back
    assert lk.stale_sweep(300.0) == 0, "wall step must not expire it"
    assert not lk.expired("res", "u1")
    with lk._lock:
        lk._table["res"][0]["ts_mono"] -= 10_000.0  # genuinely old
    assert lk.entries_older_than(300.0) == [("res", "u1", "o1")]
    assert lk.touch("res", "u1")  # lease renewal resets the age
    assert lk.entries_older_than(300.0) == []
    with lk._lock:
        lk._table["res"][0]["ts_mono"] -= 10_000.0
    assert lk.stale_sweep(300.0) == 1
    assert lk.expired("res", "u1")


# --- format ------------------------------------------------------------------


def test_format_lifecycle(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(8)]
    fmt = init_format_erasure(disks, 2, 4)
    assert len(fmt["xl"]["sets"]) == 2
    # idempotent reload keeps ids
    fmt2 = init_format_erasure(disks, 2, 4)
    assert fmt2["id"] == fmt["id"]
    assert disks[5].get_disk_id() == fmt["xl"]["sets"][1][1]
    assert find_disk_slot(fmt, disks[5].get_disk_id()) == (1, 1)
    # foreign disk rejected
    alien = XLStorage(str(tmp_path / "alien"))
    init_format_erasure([alien], 1, 1)
    with pytest.raises(errors.CorruptedFormat):
        init_format_erasure([disks[0], alien], 1, 2)


# --- storage REST ------------------------------------------------------------


@pytest.fixture
def rpc_node(tmp_path):
    """Single node serving 4 local disks over RPC + S3."""
    port = free_port()
    dirs = [str(tmp_path / f"nd{i}") for i in range(4)]
    node = Node(dirs, local_url=f"http://127.0.0.1:{port}",
                address="127.0.0.1", port=port, access_key=AK,
                secret_key=SK, default_parity=2)
    node.start()
    yield node
    node.shutdown()


def test_storage_rest_roundtrip(rpc_node, tmp_path):
    """Drive a REMOTE disk client against the node's storage service."""
    from minio_tpu.dist.storage_rest import StorageRESTClient
    from minio_tpu.storage.datatypes import FileInfo
    url = f"http://127.0.0.1:{rpc_node.server.port}"
    disk_path = list(rpc_node.local_disks)[0]
    rc = StorageRESTClient(url, disk_path, SK)
    assert not rc.is_local()
    rc.make_vol("rpcbucket")
    assert rc.stat_vol("rpcbucket").name == "rpcbucket"
    rc.write_all("rpcbucket", "f/x", b"remote-bytes")
    assert rc.read_all("rpcbucket", "f/x") == b"remote-bytes"
    rc.append_file("rpcbucket", "f/x", b"++")
    assert rc.stat_file_size("rpcbucket", "f/x") == 14
    r = rc.read_file_at("rpcbucket", "f/x")
    assert r.read_at(6, 6) == b"-bytes"
    # streaming writer
    w = rc.create_file_writer("rpcbucket", "stream/s1")
    w.write(b"block1")
    w.write(b"block2")
    w.close()
    assert rc.read_all("rpcbucket", "stream/s1") == b"block1block2"
    # version ops over the wire
    import uuid
    fi = FileInfo(volume="rpcbucket", name="obj", version_id="",
                  data_dir=str(uuid.uuid4()), mod_time=time.time(), size=3,
                  metadata={"etag": "abc"})
    fi.data = b"xyz"
    rc.write_metadata("rpcbucket", "obj", fi)
    got = rc.read_version("rpcbucket", "obj", read_data=True)
    assert got.data == b"xyz"
    assert got.metadata["etag"] == "abc"
    assert [f.version_id for f in rc.list_versions("rpcbucket", "obj")] \
        == [""]
    assert list(rc.walk_dir("rpcbucket")) == ["obj"]
    rc.delete_version("rpcbucket", "obj", fi)
    with pytest.raises(errors.FileNotFound):
        rc.read_version("rpcbucket", "obj")
    # typed errors over the wire
    with pytest.raises(errors.VolumeNotFound):
        rc.stat_vol("missing-vol")
    # invalid token rejected
    bad = StorageRESTClient(url, disk_path, "wrong-secret")
    with pytest.raises(errors.StorageError):
        bad.stat_vol("rpcbucket")
    rc.close()


def test_storage_rest_commit_part_roundtrip(rpc_node):
    """A remote drive's part commit is ONE RPC (ISSUE 43: ``commitpart``,
    where ``renamefile`` + ``writeall`` were two): the shard staged over
    the wire lands beside its sidecar, the staging directory goes, and the
    drive's typed errors come back as themselves."""
    from minio_tpu.dist.storage_rest import StorageRESTClient
    from minio_tpu.obs import metrics as mx
    from minio_tpu.storage.xlstorage import META_MULTIPART, META_TMP
    url = f"http://127.0.0.1:{rpc_node.server.port}"
    disk_path = list(rpc_node.local_disks)[0]
    rc = StorageRESTClient(url, disk_path, SK)
    w = rc.create_file_writer(META_TMP, "t1/part.4")
    w.write(b"shard-")
    w.write(b"bytes")
    w.close()
    calls = []
    orig = rc.rpc.call
    rc.rpc.call = lambda method, *a, **kw: (calls.append(method),
                                            orig(method, *a, **kw))[1]
    key = 'minio_tpu_storage_part_commits_total{route="native"}'
    before = mx.counters_snapshot().get(key, 0)
    sidecar = bytes(range(256)) * 3  # binary, as msgpack is
    rc.commit_part(META_TMP, "t1/part.4", META_MULTIPART, "hash/up/part.4",
                   sidecar)
    assert calls == ["commitpart"]
    assert mx.counters_snapshot().get(key, 0) - before == 1
    assert rc.read_all(META_MULTIPART, "hash/up/part.4") == b"shard-bytes"
    assert rc.read_all(META_MULTIPART, "hash/up/part.4.meta") == sidecar
    assert rc.list_dir(META_MULTIPART, "hash/up") == ["part.4",
                                                      "part.4.meta"]
    assert rc.list_dir(META_TMP, "") == []
    # typed errors over the wire: nothing staged; no such volume (the
    # staged shard stays for the sweep)
    with pytest.raises(errors.FileNotFound):
        rc.commit_part(META_TMP, "t1/part.4", META_MULTIPART,
                       "hash/up/part.5", b"m")
    w = rc.create_file_writer(META_TMP, "t2/part.1")
    w.write(b"s")
    w.close()
    with pytest.raises(errors.VolumeNotFound):
        rc.commit_part(META_TMP, "t2/part.1", "missing-vol", "up/part.1",
                       b"m")
    assert rc.list_dir(META_TMP, "t2") == ["part.1"]
    assert rc.list_dir(META_MULTIPART, "hash/up") == ["part.4",
                                                      "part.4.meta"]
    rc.close()


def test_two_node_multipart_parts_commit_on_remote_drives(cluster):
    """A part PUT through node 0 commits on node 1's drives too (three of
    the six are remote: one ``commitpart`` RPC each), is listed from either
    node, completes, and reads back through node 1."""
    from minio_tpu.objectlayer.multipart import upload_path
    from minio_tpu.storage.xlstorage import META_MULTIPART, META_TMP
    n0, n1 = cluster
    n0.obj.make_bucket("mpshared")
    uid = n0.obj.new_multipart_upload("mpshared", "big")
    bodies = [rng_bytes(5 << 20, seed=5), rng_bytes(300 << 10, seed=6)]
    parts = [n0.obj.put_object_part("mpshared", "big", uid, i + 1,
                                    io.BytesIO(b), len(b))
             for i, b in enumerate(bodies)]
    up = upload_path("mpshared", "big", uid)
    for n in cluster:
        for d in n.local_disks.values():
            assert sorted(x for x in d.list_dir(META_MULTIPART, up)
                          if x.startswith("part.")) == [
                "part.1", "part.1.meta", "part.2", "part.2.meta"]
            assert d.list_dir(META_TMP, "") == []
    assert [p.part_number for p in
            n1.obj.list_object_parts("mpshared", "big", uid).parts] == [1, 2]
    n1.obj.complete_multipart_upload("mpshared", "big", uid, parts)
    c1 = S3Client(f"http://127.0.0.1:{n1.server.port}", AK, SK)
    r = c1.get_object("mpshared", "big")
    assert r.status_code == 200 and r.content == b"".join(bodies)


def test_single_node_rpc_cluster_s3(rpc_node):
    """S3 traffic against the node built through the Node assembly."""
    c = S3Client(f"http://127.0.0.1:{rpc_node.server.port}", AK, SK)
    assert c.put_bucket("nb").status_code == 200
    data = rng_bytes(256 << 10, seed=1)
    assert c.put_object("nb", "o", data).status_code == 200
    assert c.get_object("nb", "o").content == data


# --- multi-node cluster ------------------------------------------------------


@pytest.fixture
def cluster(tmp_path):
    """2 nodes x 3 disks each = one 6-drive erasure set across 'hosts'
    (both in this process on different ports)."""
    ports = [free_port(), free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    args = []
    for ni in range(2):
        for di in range(3):
            d = tmp_path / f"n{ni}" / f"d{di}"
            d.parent.mkdir(exist_ok=True)
            args.append(f"{urls[ni]}{d}")
    nodes = []
    for ni in range(2):
        node = Node(args, local_url=urls[ni], address="127.0.0.1",
                    port=ports[ni], access_key=AK, secret_key=SK,
                    default_parity=2)
        nodes.append(node)
    threads = [threading.Thread(target=n.start) for n in nodes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    for n in nodes:
        assert n.obj is not None, "node failed to start"
    yield nodes
    for n in nodes:
        n.shutdown()


def test_iam_sync_across_nodes(cluster):
    """Create a user on node A -> it can authenticate (and is authorized)
    on node B without restart (reference peer IAM sync,
    cmd/peer-rest-common.go:33-44)."""
    n0, n1 = cluster
    c_root = S3Client(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    assert c_root.request("PUT", "/iamsync").status_code == 200
    n0.server.iam.add_user("synceduser", "syncedsecret99",
                           policies=["readwrite"])
    c_new = S3Client(f"http://127.0.0.1:{n1.server.port}",
                     "synceduser", "syncedsecret99")
    deadline = time.time() + 10
    while time.time() < deadline:
        r = c_new.request("GET", "/iamsync")
        if r.status_code == 200:
            break
        time.sleep(0.1)
    assert r.status_code == 200, r.text
    # removal propagates too
    n0.server.iam.remove_user("synceduser")
    deadline = time.time() + 10
    while time.time() < deadline:
        r = c_new.request("GET", "/iamsync")
        if r.status_code == 403:
            break
        time.sleep(0.1)
    assert r.status_code == 403, r.status_code


def test_two_node_cluster_put_get(cluster):
    n0, n1 = cluster
    c0 = S3Client(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    c1 = S3Client(f"http://127.0.0.1:{n1.server.port}", AK, SK)
    assert c0.put_bucket("shared").status_code == 200
    data = rng_bytes(768 << 10, seed=2)
    # write through node 0, read through node 1 (shards span both nodes)
    assert c0.put_object("shared", "cross/obj", data).status_code == 200
    r = c1.get_object("shared", "cross/obj")
    assert r.status_code == 200 and r.content == data
    # every node's local disks hold some shards
    for n in cluster:
        held = 0
        for d in n.local_disks.values():
            try:
                d.read_version("shared", "cross/obj")
                held += 1
            except errors.StorageError:
                pass
        assert held == 3, "shards must spread across both nodes"
    # delete via node 1, gone on node 0
    assert c1.delete_object("shared", "cross/obj").status_code == 204
    assert c0.get_object("shared", "cross/obj").status_code == 404


def test_two_node_heal_after_disk_wipe(cluster):
    """verify-healing.sh analogue: wipe a remote node's disk, heal from
    the surviving shards, verify the wiped disk is repopulated."""
    n0, n1 = cluster
    c0 = S3Client(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    c0.put_bucket("healb")
    data = rng_bytes(512 << 10, seed=3)
    c0.put_object("healb", "obj", data)
    # wipe one of node1's disks
    wiped = list(n1.local_disks.values())[0]
    shutil.rmtree(os.path.join(wiped.base, "healb"))
    # heal through node 0 (reaches the wiped disk via storage RPC)
    n0.obj.heal_bucket("healb")
    res = n0.obj.heal_object("healb", "obj")
    assert "missing" in res.before_state
    assert res.after_state.count("ok") == 6
    wiped.read_version("healb", "obj")  # repopulated
    assert c0.get_object("healb", "obj").content == data


def test_cluster_locks_are_shared(cluster):
    n0, n1 = cluster
    m0 = n0.ns_lock.new_lock("b", "o")
    assert m0.get_lock(timeout=2)
    m1 = n1.ns_lock.new_lock("b", "o")
    assert not m1.get_lock(timeout=0.5), \
        "node1 must see node0's lock via lock RPC"
    m0.unlock()
    assert m1.get_lock(timeout=2)
    m1.unlock()


def test_bucket_metadata_propagation(cluster):
    n0, n1 = cluster
    c0 = S3Client(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    c0.put_bucket("metab")
    body = (b'<VersioningConfiguration><Status>Enabled</Status>'
            b'</VersioningConfiguration>')
    c0.request("PUT", "/metab", query={"versioning": ""}, body=body)
    # node1's cache was invalidated via peer RPC; it reads the new config
    assert n1.bucket_meta.versioning_enabled("metab")


def test_metacache_cluster_reuse(cluster, monkeypatch):
    """Node B serves a listing from the metacache blocks node A's walk
    persisted on the shared disks — no namespace walk on B (reference
    cluster-shared metacache streams, cmd/metacache-server-pool.go:59)."""
    n0, n1 = cluster
    c0 = S3Client(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    c1 = S3Client(f"http://127.0.0.1:{n1.server.port}", AK, SK)
    assert c0.request("PUT", "/mcbucket").status_code == 200
    data = rng_bytes(256)
    for i in range(25):
        assert c0.put_object("mcbucket", f"k{i:03d}", data).status_code \
            == 200
    # node A lists (recursive) -> becomes the builder
    r = c0.request("GET", "/mcbucket", query={"list-type": "2"})
    assert r.status_code == 200
    # wait for every set's build on node A to finish
    from minio_tpu.objectlayer.erasure_objects import ErasureObjects

    def each_set(node):
        obj = node.obj
        pools = getattr(obj, "pools", [obj])
        for p in pools:
            sets = getattr(p, "sets", [p])
            for s in sets:
                if isinstance(s, ErasureObjects):
                    yield s
    deadline = time.time() + 15
    while time.time() < deadline:
        states = [st for s in each_set(n0)
                  for st in s.metacache._states.values()]
        if states and all(st.ended and st.error is None for st in states):
            break
        time.sleep(0.05)
    assert states and all(st.ended for st in states)
    # node B lists: must come from blocks, not a walk
    from minio_tpu.objectlayer import metacache as mc
    walked = {"n": 0}
    real = mc.merged_entries

    def counting(disks, bucket, *a, **kw):
        if bucket == "mcbucket":
            walked["n"] += 1
        return real(disks, bucket, *a, **kw)

    monkeypatch.setattr(mc, "merged_entries", counting)
    r1 = c1.request("GET", "/mcbucket", query={"list-type": "2"})
    assert r1.status_code == 200
    assert all(f"k{i:03d}" in r1.text for i in range(25))
    assert walked["n"] == 0, "node B walked despite node A's cache"


def test_peer_control_plane_breadth(cluster):
    """The peer RPC observability fan-out (reference peer-rest-common.go:
    CPULoadInfo/Log/GetLocks/GetBandwidth/BackgroundHealStatus/metrics):
    each node can interrogate the other."""
    n0, n1 = cluster
    peer = n0.peers[0]  # n0's client for n1
    info = peer.proc_info()
    assert info["cpu"]["count"] >= 1
    assert info["process"]["pid"] > 0
    m = peer.metrics()
    assert isinstance(m, dict)
    assert peer.get_locks() == []
    bw = peer.get_bandwidth()
    assert "bucketStats" in bw
    logs = peer.console_log(10)
    assert isinstance(logs, list)
    # Node startup attaches the background plane (scanner/MRF/autoheal)
    st = peer.background_heal_status()
    assert "mrf" in st and "autoheal" in st
    assert st["mrf"]["queued"] == 0
    # profiling fan-out: start on the peer, download a sampler report
    peer.start_profiling("cpu")
    time.sleep(0.1)
    data = peer.download_profiling()
    assert b"# samples:" in data


def test_admin_peer_aggregation(cluster):
    """Admin bandwidth/top-locks with ?peers=1 merge every node's view."""
    n0, _ = cluster
    from minio_tpu.madmin import AdminClient
    adm = AdminClient(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    rep = adm._json("GET", "bandwidth", {"peers": "1"})
    assert "bucketStats" in rep
    locks = adm._json("GET", "top/locks", {"peers": "1"})
    assert "locks" in locks
    heal = adm._json("GET", "bg-heal-status")
    assert isinstance(heal, dict)


def test_cluster_profile_fanout(cluster):
    """`GET /minio/admin/v3/profile?peers=1` (ISSUE 14): the continuous
    profiler's top report aggregated across dist nodes — one row per
    node (the `profile` peer RPC), each carrying samples + subsystem
    shares; `seconds=` forces a fresh concurrent window on every
    node."""
    n0, _ = cluster
    from minio_tpu.madmin import AdminClient
    adm = AdminClient(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    rep = adm.profile(peers=True, seconds=0.5)
    nodes = rep["nodes"]
    assert len(nodes) >= 2, nodes
    ok = [n for n in nodes if "error" not in n]
    assert len(ok) >= 2, nodes
    for n in ok:
        assert n.get("endpoint"), n
        assert n["samples"] > 0, n
        assert "subsystems" in n and "lock_contention" in n
    endpoints = {n["endpoint"] for n in ok}
    assert len(endpoints) >= 2, endpoints


def test_cluster_device_fanout(cluster):
    """`GET /minio/admin/v3/device?peers=1` (ISSUE 16): the device
    plane aggregated across dist nodes via the new `devicestatus` peer
    RPC — one row per node, each carrying the lane ledger, compile
    table and roofline maps."""
    n0, _ = cluster
    from minio_tpu.madmin import AdminClient
    from minio_tpu.obs import device
    device.note_compile("test.fanout", "uint32[8]", 0.01)
    adm = AdminClient(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    rep = adm.device_status(peers=True)
    nodes = rep["nodes"]
    assert len(nodes) >= 2, nodes
    ok = [n for n in nodes if "error" not in n]
    assert len(ok) >= 2, nodes
    for n in ok:
        assert n.get("endpoint"), n
        assert {"bulk", "interactive", "mesh"} <= set(n["ledger"])
        assert "compile" in n and "roofline" in n
        assert isinstance(n["ledger_balanced"], bool)
    endpoints = {n["endpoint"] for n in ok}
    assert len(endpoints) >= 2, endpoints
    # both dist nodes run in THIS process, so the local note_compile
    # shows on the local row (the row whose endpoint answered)
    assert any(any(r["op"] == "test.fanout"
                   for r in n["compile"]["table"]) for n in ok)


def test_cluster_bucketstats_fanout(cluster):
    """`GET /minio/admin/v3/bucketstats?peers=1` (ISSUE 18): the
    per-bucket analytics report aggregated across dist nodes via the
    new `bucketstats` peer RPC — one row per node, each carrying the
    tracked-bucket rollups and projection."""
    n0, _ = cluster
    from minio_tpu.madmin import AdminClient
    from minio_tpu.obs import bucketstats
    bucketstats.record_request("fanoutbkt", "getobject", 200, 0.01,
                               bytes_out=64)
    adm = AdminClient(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    rep = adm.bucket_stats(peers=True)
    nodes = rep["nodes"]
    assert len(nodes) >= 2, nodes
    ok = [n for n in nodes if "error" not in n]
    assert len(ok) >= 2, nodes
    for n in ok:
        assert n.get("endpoint"), n
        assert "buckets" in n and "projection" in n
        assert n["top_n"] >= 1
    endpoints = {n["endpoint"] for n in ok}
    assert len(endpoints) >= 2, endpoints
    # both dist nodes run in THIS process, so the charge above shows
    # on every row (shared in-process registry)
    assert any("fanoutbkt" in n["buckets"] for n in ok)


def test_cluster_health_snapshot(cluster):
    """`GET /minio/admin/v3/health` aggregates the node health snapshot
    (disk states, lane utilization, QoS saturation, heal backlog, SLO
    verdicts) across dist peers, plus the cluster rollup (ISSUE 10
    acceptance: the >=2-node aggregated snapshot)."""
    n0, n1 = cluster
    from minio_tpu.madmin import AdminClient
    adm = AdminClient(f"http://127.0.0.1:{n0.server.port}", AK, SK)
    h = adm.cluster_health()
    assert h["cluster"]["nodes"] >= 2, h["cluster"]
    assert h["cluster"]["nodes_offline"] == 0
    # each node's SNAPSHOT lists all 6 set disks it mounts (3 local +
    # 3 remote clients), but the rollup dedupes by endpoint — the
    # cluster has 6 physical disks, not 2 x 6 node views
    assert h["cluster"]["disks_total"] == 6
    assert all(n["disks"]["total"] == 6 for n in h["nodes"])
    assert isinstance(h["cluster"]["healthy"], bool)
    endpoints = {n.get("endpoint") for n in h["nodes"]}
    assert len(endpoints) >= 2, endpoints
    for node in h["nodes"]:
        # every reachable node row carries the full plane set
        assert "disks" in node and "qos" in node and "slo" in node
        assert set(node["slo"]["classes"]) == {
            "interactive", "control", "background"}
    # ?peers=0 keeps it to the answering node
    local = adm.cluster_health(peers=False)
    assert local["cluster"]["nodes"] == 1
    # the peer RPC serves the same snapshot shape directly
    peer = n0.peers[0]
    snap = peer.health_snapshot()
    assert "disks" in snap and "slo" in snap
