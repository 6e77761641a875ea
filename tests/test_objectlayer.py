"""Object-layer tests — the analogue of the reference's backend-generic
object_api_suite_test.go + erasure-object_test.go: CRUD, quorum with offline
disks (naughtyDisk-style), versioning, multipart, heal, listing; run against
ErasureObjects, ErasureSets and ServerPools."""
import io
import os
import shutil
import uuid

import numpy as np
import pytest

from minio_tpu.objectlayer import (ErasureObjects, ErasureSets, ServerPools,
                                   ObjectOptions)
from minio_tpu.objectlayer import datatypes as dt
from minio_tpu.objectlayer.datatypes import CompletePart
from minio_tpu.storage import XLStorage
from minio_tpu.utils import errors
from naughty import NaughtyDisk


def mk_disks(tmp_path, n, prefix="disk"):
    return [XLStorage(str(tmp_path / f"{prefix}{i}")) for i in range(n)]


def rng_bytes(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


@pytest.fixture
def ol(tmp_path):
    """4+2 single set (BASELINE config 1 shape)."""
    obj = ErasureObjects(mk_disks(tmp_path, 6), default_parity=2)
    obj.make_bucket("bucket")
    return obj


# --- basic CRUD --------------------------------------------------------------


def test_put_get_roundtrip(ol):
    data = rng_bytes(2 << 20, seed=1)
    oi = ol.put_object("bucket", "dir/obj", io.BytesIO(data), len(data))
    assert oi.size == len(data)
    assert oi.etag
    got = ol.get_object_bytes("bucket", "dir/obj")
    assert got == data
    info = ol.get_object_info("bucket", "dir/obj")
    assert info.size == len(data)
    assert info.etag == oi.etag


def test_put_small_and_empty(ol):
    for size in (0, 1, 100, 4096):
        data = rng_bytes(size, seed=size)
        ol.put_object("bucket", f"o{size}", io.BytesIO(data), size)
        assert ol.get_object_bytes("bucket", f"o{size}") == data


def test_range_get(ol):
    data = rng_bytes((2 << 20) + 777, seed=2)
    ol.put_object("bucket", "o", io.BytesIO(data), len(data))
    from minio_tpu.erasure.streaming import BufferSink
    for off, ln in [(0, 10), (100, 1 << 20), ((1 << 20) - 1, 2),
                    (len(data) - 5, 5)]:
        sink = BufferSink()
        ol.get_object("bucket", "o", sink, off, ln)
        assert sink.getvalue() == data[off: off + ln], (off, ln)
    with pytest.raises(dt.InvalidRange):
        sink = BufferSink()
        ol.get_object("bucket", "o", sink, len(data), 10)


@pytest.mark.parametrize("pad", [0, 200 << 10], ids=["inline", "files"])
def test_overwrite(ol, pad):
    """What a replaced version held is reclaimed on every disk: its data
    directory (shard files, over 128 KiB), or its entry in xl.meta's
    ``Data`` (an inline version, which has no directory at all)."""
    from minio_tpu.storage.xlmeta import XLMeta
    first, second = b"first" + b"1" * pad, b"second!" + b"2" * pad
    ol.put_object("bucket", "o", io.BytesIO(first), len(first))
    ol.put_object("bucket", "o", io.BytesIO(second), len(second))
    assert ol.get_object_bytes("bucket", "o") == second
    for d in ol.disks:
        entries = [e for e in d.list_dir("bucket", "o")
                   if e.endswith("/")]
        held = XLMeta.load(d.read_all("bucket", "o/xl.meta")).data
        if pad:
            assert len(entries) == 1, f"leaked data dirs: {entries}"
            assert not held
        else:
            assert not entries, f"an inline version has no dir: {entries}"
            assert len(held) == 1, f"leaked Data entries: {list(held)}"


def test_delete(ol):
    ol.put_object("bucket", "o", io.BytesIO(b"x"), 1)
    ol.delete_object("bucket", "o")
    with pytest.raises(dt.ObjectNotFound):
        ol.get_object_info("bucket", "o")
    # idempotent-ish: deleting a non-existent object is OK (S3 semantics)
    ol.delete_object("bucket", "o")


def test_bucket_lifecycle(tmp_path):
    obj = ErasureObjects(mk_disks(tmp_path, 6), default_parity=2)
    obj.make_bucket("b1")
    with pytest.raises(dt.BucketExists):
        obj.make_bucket("b1")
    with pytest.raises(dt.BucketNameInvalid):
        obj.make_bucket(".bad")
    obj.make_bucket("b2")
    assert [b.name for b in obj.list_buckets()] == ["b1", "b2"]
    obj.put_object("b1", "o", io.BytesIO(b"z"), 1)
    with pytest.raises(dt.BucketNotEmpty):
        obj.delete_bucket("b1")
    obj.delete_bucket("b1", force=True)
    with pytest.raises(dt.BucketNotFound):
        obj.get_bucket_info("b1")
    with pytest.raises(dt.BucketNotFound):
        obj.put_object("nope", "o", io.BytesIO(b"z"), 1)


def test_content_type_and_user_meta(ol):
    opts = ObjectOptions(user_defined={
        "content-type": "text/css", "x-amz-meta-color": "blue"})
    ol.put_object("bucket", "o", io.BytesIO(b"body"), 4, opts)
    info = ol.get_object_info("bucket", "o")
    assert info.content_type == "text/css"
    assert info.user_defined.get("x-amz-meta-color") == "blue"


# --- quorum / fault injection ------------------------------------------------


def test_put_with_offline_disks(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    data = rng_bytes(1 << 20, seed=3)
    # 2 disks offline: write quorum (4) still met
    obj._disks[1] = None
    obj._disks[4] = None
    oi = obj.put_object("b", "o", io.BytesIO(data), len(data))
    assert oi.size == len(data)
    assert obj.get_object_bytes("b", "o") == data
    # 3 offline: below write quorum
    obj._disks[5] = None
    with pytest.raises(dt.InsufficientWriteQuorum):
        obj.put_object("b", "o2", io.BytesIO(data), len(data))


def test_get_with_lost_shards(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    data = rng_bytes((1 << 20) + 333, seed=4)
    obj.put_object("b", "o", io.BytesIO(data), len(data))
    # wipe 2 whole disks AFTER write -> read must reconstruct
    for i in (0, 3):
        shutil.rmtree(os.path.join(disks[i].base, "b"))
        os.makedirs(os.path.join(disks[i].base, "b"))
    assert obj.get_object_bytes("b", "o") == data
    # wipe a third -> below read quorum
    shutil.rmtree(os.path.join(disks[5].base, "b"))
    os.makedirs(os.path.join(disks[5].base, "b"))
    with pytest.raises((dt.InsufficientReadQuorum, dt.ObjectNotFound)):
        obj.get_object_bytes("b", "o")


def test_heal_on_read_callback(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    data = rng_bytes(1 << 20, seed=5)
    obj.put_object("b", "o", io.BytesIO(data), len(data))
    calls = []
    obj.on_partial = lambda b, o, v: calls.append((b, o, v))
    shutil.rmtree(os.path.join(disks[2].base, "b"))
    os.makedirs(os.path.join(disks[2].base, "b"))
    assert obj.get_object_bytes("b", "o") == data
    assert calls, "degraded read must signal MRF"


def test_put_naughty_disk_write_failures(tmp_path):
    disks = mk_disks(tmp_path, 6)
    # one disk fails every call
    disks[2] = NaughtyDisk(disks[2], default_err=errors.FaultyDisk())
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    data = rng_bytes(1 << 20, seed=6)
    oi = obj.put_object("b", "o", io.BytesIO(data), len(data))
    assert oi.size == len(data)
    assert obj.get_object_bytes("b", "o") == data


# --- versioning --------------------------------------------------------------


def test_versioned_put_get_delete(ol):
    opts = ObjectOptions(versioned=True)
    d1, d2 = b"version-one", b"version-two!"
    oi1 = ol.put_object("bucket", "v", io.BytesIO(d1), len(d1), opts)
    oi2 = ol.put_object("bucket", "v", io.BytesIO(d2), len(d2), opts)
    assert oi1.version_id and oi2.version_id
    assert oi1.version_id != oi2.version_id
    # latest
    assert ol.get_object_bytes("bucket", "v") == d2
    # by version
    assert ol.get_object_bytes(
        "bucket", "v", ObjectOptions(version_id=oi1.version_id)) == d1
    # soft delete -> delete marker
    dm = ol.delete_object("bucket", "v", ObjectOptions(versioned=True))
    assert dm.delete_marker and dm.version_id
    with pytest.raises(dt.ObjectNotFound):
        ol.get_object_info("bucket", "v")
    # old version still readable
    assert ol.get_object_bytes(
        "bucket", "v", ObjectOptions(version_id=oi1.version_id)) == d1
    # list versions shows 3 entries (2 data + 1 marker)
    lv = ol.list_object_versions("bucket", "v")
    assert len(lv.objects) == 3
    assert lv.objects[0].delete_marker
    # hard delete specific version
    ol.delete_object("bucket", "v",
                     ObjectOptions(version_id=oi1.version_id, versioned=True))
    with pytest.raises(dt.VersionNotFound):
        ol.get_object_bytes("bucket", "v",
                            ObjectOptions(version_id=oi1.version_id))


# --- listing -----------------------------------------------------------------


def test_list_objects(ol):
    names = ["a/1", "a/2", "b/x/deep", "c", "d"]
    for n in names:
        ol.put_object("bucket", n, io.BytesIO(b"d"), 1)
    r = ol.list_objects("bucket")
    assert [o.name for o in r.objects] == ["a/1", "a/2", "b/x/deep", "c", "d"]
    # delimiter
    r = ol.list_objects("bucket", delimiter="/")
    assert r.prefixes == ["a/", "b/"]
    assert [o.name for o in r.objects] == ["c", "d"]
    # prefix
    r = ol.list_objects("bucket", prefix="a/")
    assert [o.name for o in r.objects] == ["a/1", "a/2"]
    # pagination
    r = ol.list_objects("bucket", max_keys=2)
    assert r.is_truncated and len(r.objects) == 2
    r2 = ol.list_objects("bucket", marker=r.objects[-1].name, max_keys=10)
    assert [o.name for o in r2.objects] == ["b/x/deep", "c", "d"]


# --- multipart ---------------------------------------------------------------


def test_multipart_upload(ol):
    part_size = 5 << 20
    p1 = rng_bytes(part_size, seed=7)
    p2 = rng_bytes(part_size, seed=8)
    p3 = rng_bytes(1 << 20, seed=9)  # last part may be small
    uid = ol.new_multipart_upload("bucket", "mp/obj")
    e1 = ol.put_object_part("bucket", "mp/obj", uid, 1, io.BytesIO(p1),
                            len(p1))
    e2 = ol.put_object_part("bucket", "mp/obj", uid, 2, io.BytesIO(p2),
                            len(p2))
    e3 = ol.put_object_part("bucket", "mp/obj", uid, 3, io.BytesIO(p3),
                            len(p3))
    lp = ol.list_object_parts("bucket", "mp/obj", uid)
    assert [p.part_number for p in lp.parts] == [1, 2, 3]
    lu = ol.list_multipart_uploads("bucket")
    assert [u.upload_id for u in lu.uploads] == [uid]
    oi = ol.complete_multipart_upload(
        "bucket", "mp/obj", uid,
        [CompletePart(1, e1.etag), CompletePart(2, e2.etag),
         CompletePart(3, e3.etag)])
    assert oi.etag.endswith("-3")
    assert oi.size == 2 * part_size + len(p3)
    assert ol.get_object_bytes("bucket", "mp/obj") == p1 + p2 + p3
    # ranged read across part boundary
    from minio_tpu.erasure.streaming import BufferSink
    sink = BufferSink()
    ol.get_object("bucket", "mp/obj", sink, part_size - 10, 20)
    assert sink.getvalue() == (p1 + p2)[part_size - 10: part_size + 10]
    # upload dir reaped
    assert ol.list_multipart_uploads("bucket").uploads == []


def test_multipart_errors(ol):
    uid = ol.new_multipart_upload("bucket", "o")
    with pytest.raises(dt.NoSuchUpload):
        ol.put_object_part("bucket", "o", "bogus", 1, io.BytesIO(b"x"), 1)
    e1 = ol.put_object_part("bucket", "o", uid, 1, io.BytesIO(b"tiny"), 4)
    e2 = ol.put_object_part("bucket", "o", uid, 2, io.BytesIO(b"tiny2"), 5)
    # non-terminal part below 5MiB
    with pytest.raises(dt.EntityTooSmall):
        ol.complete_multipart_upload(
            "bucket", "o", uid,
            [CompletePart(1, e1.etag), CompletePart(2, e2.etag)])
    # wrong etag
    with pytest.raises(dt.InvalidPart):
        ol.complete_multipart_upload("bucket", "o", uid,
                                     [CompletePart(1, "deadbeef")])
    # out of order
    with pytest.raises(dt.InvalidPartOrder):
        ol.complete_multipart_upload(
            "bucket", "o", uid,
            [CompletePart(2, e2.etag), CompletePart(1, e1.etag)])
    ol.abort_multipart_upload("bucket", "o", uid)
    with pytest.raises(dt.NoSuchUpload):
        ol.list_object_parts("bucket", "o", uid)


# --- heal --------------------------------------------------------------------


def test_heal_object_missing_disk(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    data = rng_bytes((2 << 20) + 17, seed=10)
    obj.put_object("b", "o", io.BytesIO(data), len(data))
    # wipe 2 disks' copy of the object
    for i in (1, 4):
        shutil.rmtree(os.path.join(disks[i].base, "b", "o"))
    res = obj.heal_object("b", "o")
    assert res.before_state.count("missing") == 2
    assert res.after_state.count("ok") == 6
    # now all disks can serve: drop the other 2 good data disks
    obj2 = ErasureObjects(disks, default_parity=2)
    obj2._disks[0] = None
    obj2._disks[2] = None
    assert obj2.get_object_bytes("b", "o") == data


def _empty(disk, bucket, key=None):
    """What replacing a drive (or losing one object's shards on it)
    leaves: the bucket directory stays, its contents are gone."""
    base = os.path.join(disk.base, bucket)
    for name in ([key] if key else os.listdir(base)):
        shutil.rmtree(os.path.join(base, name))


@pytest.mark.parametrize("size", [5 << 18, 10 << 20],
                         ids=["1.25MiB", "10MiB"])
@pytest.mark.parametrize("route", ["sequence", "on_read"])
@pytest.mark.parametrize("k,m", [(4, 2), (8, 4)], ids=["4p2", "8p4"])
def test_replaced_drive_heals_and_reads_back_with_exactly_k(
        tmp_path, k, m, route, size):
    """A drive is replaced (emptied), the set stays under reads, and the
    drive is rebuilt by an admin heal sequence or by heal-on-read alone.
    Then m OTHER drives lose every shard, which leaves exactly k, the
    healed ones among them: one shard a heal left wrong or missing is a
    failed GET here (the read-back of the benchmark's heal-drive cells,
    which at 4+2 leaves exactly k as well)."""
    import threading
    import time

    from minio_tpu.runtime.dispatch import global_queue
    from minio_tpu.scanner.healseq import HealSequence
    from minio_tpu.scanner.mrf import MRFHealer
    disks = mk_disks(tmp_path, k + m)
    obj = ErasureObjects(disks, default_parity=m)
    obj.make_bucket("b")
    bodies = {f"obj-{i:05d}": rng_bytes(size, seed=100 + i)
              for i in range(4)}
    for key, body in bodies.items():
        obj.put_object("b", key, io.BytesIO(body), len(body))
    _empty(disks[0], "b")
    mrf = MRFHealer(obj)
    lane = global_queue().stats()["interactive_lane"]["items"]
    wrong: list[str] = []

    def read_all():
        for key, body in bodies.items():
            if obj.get_object_bytes("b", key) != body:
                wrong.append(key)

    def settle(busy):
        deadline = time.monotonic() + 120
        while busy() and time.monotonic() < deadline:
            time.sleep(0.05)

    try:
        if route == "sequence":
            seq = HealSequence(obj, "b").start()
            readers = [threading.Thread(target=read_all, daemon=True,
                                        name=f"heal-reader-{i}")
                       for i in range(2)]
            for t in readers:
                t.start()
            for t in readers:
                t.join(timeout=120)
            settle(lambda: seq.status == "running")
            assert (seq.status, seq.scanned, seq.healed, seq.failed) == \
                ("done", len(bodies), len(bodies), 0), seq.summary()
        else:
            obj.on_partial = mrf.add_partial
            mrf.start()
            read_all()       # every GET sees drive 0 short and says so
            settle(lambda: mrf.healed < len(bodies))
            assert mrf.healed >= len(bodies), mrf.stats()
            assert mrf.failed == 0, mrf.stats()
    finally:
        mrf.stop()
    assert not wrong, wrong
    # the rebuilds rode the dispatch queue's interactive lane
    assert global_queue().stats()["interactive_lane"]["items"] > lane
    for key in bodies:
        disks[0].read_version("b", key)     # xl.meta back on the drive
        for j in range(1, m + 1):
            _empty(disks[j], "b", key)
    for key, body in bodies.items():
        assert obj.get_object_bytes("b", key) == body, key


def test_heal_object_corrupt_shard(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    data = rng_bytes(1 << 20, seed=11)
    obj.put_object("b", "o", io.BytesIO(data), len(data))
    # corrupt one shard file (truncate)
    fi = disks[0].read_version("b", "o")
    part = os.path.join(disks[0].base, "b", "o", fi.data_dir, "part.1")
    with open(part, "r+b") as f:
        f.truncate(100)
    res = obj.heal_object("b", "o")
    assert "corrupt" in res.before_state
    assert res.after_state.count("ok") == 6
    assert obj.get_object_bytes("b", "o") == data


def test_heal_deep_scan_detects_bitflip(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    data = rng_bytes(1 << 20, seed=12)
    obj.put_object("b", "o", io.BytesIO(data), len(data))
    fi = disks[2].read_version("b", "o")
    part = os.path.join(disks[2].base, "b", "o", fi.data_dir, "part.1")
    with open(part, "r+b") as f:
        f.seek(5000)
        b = f.read(1)
        f.seek(5000)
        f.write(bytes([b[0] ^ 0xFF]))
    # normal scan (size check) can't see it; deep scan can
    res = obj.heal_object("b", "o", scan_mode="deep")
    assert res.before_state[2] == "corrupt"
    assert res.after_state.count("ok") == 6
    assert obj.get_object_bytes("b", "o") == data


def test_heal_delete_marker_propagation(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    obj.put_object("b", "o", io.BytesIO(b"x"), 1,
                   ObjectOptions(versioned=True))
    obj.delete_object("b", "o", ObjectOptions(versioned=True))
    # wipe marker from one disk: restore obj dir from another? simpler —
    # heal with all markers present is a no-op that reports ok
    res = obj.heal_object("b", "o")
    assert res.after_state.count("ok") == 6


def test_heal_bucket(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    shutil.rmtree(os.path.join(disks[3].base, "b"))
    res = obj.heal_bucket("b")
    assert res.before_state[3] == "missing"
    assert res.after_state.count("ok") == 6


def test_heal_dangling_removal(tmp_path):
    disks = mk_disks(tmp_path, 6)
    obj = ErasureObjects(disks, default_parity=2)
    obj.make_bucket("b")
    obj.put_object("b", "o", io.BytesIO(b"payload"), 7)
    # destroy beyond repair: keep only 2 disks' copies (< read quorum 4)
    for i in range(4):
        shutil.rmtree(os.path.join(disks[i].base, "b", "o"))
    res = obj.heal_object("b", "o", remove_dangling=True)
    for d in disks:
        with pytest.raises(errors.StorageError):
            d.read_version("b", "o")


# --- sets / pools ------------------------------------------------------------


def test_erasure_sets_placement_and_crud(tmp_path):
    sets = ErasureSets(mk_disks(tmp_path, 8), set_count=2, drives_per_set=4,
                       default_parity=2)
    sets.make_bucket("b")
    seen_sets = set()
    blobs = {}
    for i in range(16):
        name = f"obj-{i}"
        seen_sets.add(sets.get_hashed_set_index(name))
        data = rng_bytes(8192 + i, seed=i)
        blobs[name] = data
        sets.put_object("b", name, io.BytesIO(data), len(data))
    assert seen_sets == {0, 1}, "objects should spread across sets"
    for name, data in blobs.items():
        assert sets.get_hashed_set("b-ignored") is not None
        from minio_tpu.erasure.streaming import BufferSink
        sink = BufferSink()
        sets.get_object("b", name, sink)
        assert sink.getvalue() == data
    r = sets.list_objects("b")
    assert len(r.objects) == 16
    deleted, errs = sets.delete_objects("b", [f"obj-{i}" for i in range(16)])
    assert all(e is None for e in errs)
    assert sets.list_objects("b").objects == []


def test_server_pools_routing(tmp_path):
    p0 = ErasureSets(mk_disks(tmp_path, 4, "p0d"), 1, 4, default_parity=2)
    p1 = ErasureSets(mk_disks(tmp_path, 4, "p1d"), 1, 4, default_parity=2)
    pools = ServerPools([p0, p1])
    pools.make_bucket("b")
    data = rng_bytes(64 << 10, seed=20)
    pools.put_object("b", "o", io.BytesIO(data), len(data))
    from minio_tpu.erasure.streaming import BufferSink
    sink = BufferSink()
    pools.get_object("b", "o", sink)
    assert sink.getvalue() == data
    # overwrite routes to the pool already owning the object
    idx = pools.get_pool_idx("b", "o")
    pools.put_object("b", "o", io.BytesIO(b"new"), 3)
    assert pools.get_pool_idx("b", "o") == idx
    pools.delete_object("b", "o")
    with pytest.raises(dt.ObjectNotFound):
        pools.get_object_info("b", "o")


def test_server_pools_delete_reaches_second_pool(tmp_path):
    """A new object goes to the pool with most free space, and two pools
    on one file system tie until a neighbour writes or removes a file
    between the two readings: under load `test_server_pools_routing` met
    its object in pool 1, where the delete was acknowledged by pool 0 (an
    S3 delete of a missing name succeeds) and the object stayed. Here the
    object is PUT into pool 1 on purpose."""
    p0 = ErasureSets(mk_disks(tmp_path, 4, "p0d"), 1, 4, default_parity=2)
    p1 = ErasureSets(mk_disks(tmp_path, 4, "p1d"), 1, 4, default_parity=2)
    pools = ServerPools([p0, p1])
    pools.make_bucket("b")
    for name in ("o", "batch"):
        p1.put_object("b", name, io.BytesIO(b"in pool 1"), 9)
        assert pools.get_pool_idx("b", name) == 1
    pools.delete_object("b", "o")
    deleted, errs = pools.delete_objects("b", ["batch", "never-there"])
    assert errs == [None, None] and len(deleted) == 2
    for name in ("o", "batch"):
        with pytest.raises(dt.ObjectNotFound):
            pools.get_object_info("b", name)
    # a name no pool holds is still deleted without error
    pools.delete_object("b", "never-there")


# --- ADVICE round-1 regressions ---------------------------------------------


def test_self_copy_replace_keeps_per_disk_erasure_index(ol):
    """Metadata-only self-copy must write each disk its OWN erasure.index
    (ADVICE r1 high: all disks ended up claiming index of the quorum pick,
    making the object permanently unreadable)."""
    data = rng_bytes(256 << 10, seed=7)
    ol.put_object("bucket", "sc", io.BytesIO(data), len(data),
                  ObjectOptions(user_defined={"x-amz-meta-a": "1"}))
    opts = ObjectOptions(user_defined={"x-amz-meta-b": "2"},
                         metadata_replace=True)
    ol.copy_object("bucket", "sc", "bucket", "sc", None,
                   ObjectOptions(), opts)
    # every disk still holds a distinct shard index
    idxs = sorted(d.read_version("bucket", "sc").erasure.index
                  for d in ol.disks)
    assert idxs == list(range(1, len(ol.disks) + 1))
    # object still readable after the metadata rewrite
    assert ol.get_object_bytes("bucket", "sc") == data
    info = ol.get_object_info("bucket", "sc")
    # REPLACE semantics: old user key dropped, new one present
    assert "x-amz-meta-a" not in info.user_defined
    assert info.user_defined.get("x-amz-meta-b") == "2"


def test_self_copy_merge_directive_keeps_old_meta(ol):
    data = rng_bytes(1024, seed=8)
    ol.put_object("bucket", "scm", io.BytesIO(data), len(data),
                  ObjectOptions(user_defined={"x-amz-meta-a": "1"}))
    ol.copy_object("bucket", "scm", "bucket", "scm", None, ObjectOptions(),
                   ObjectOptions(user_defined={"x-amz-meta-b": "2"}))
    info = ol.get_object_info("bucket", "scm")
    assert info.user_defined.get("x-amz-meta-a") == "1"
    assert info.user_defined.get("x-amz-meta-b") == "2"


def test_small_object_get_never_serves_shard_bytes(ol):
    """ADVICE r1 high: sizes where size - ceil(size/k) equals the bitrot
    digest overhead used to return digest||shard bytes with HTTP 200."""
    # k=4 here; the old bug fired when ceil(size/4)+32 == size ⇒ size≈43
    # and at 64B with k=2 configs; sweep a range to be safe.
    for size in range(1, 200):
        data = rng_bytes(size, seed=size)
        ol.put_object("bucket", f"tiny{size}", io.BytesIO(data), size)
        assert ol.get_object_bytes("bucket", f"tiny{size}") == data, size


def test_cross_block_range_reads(tmp_path):
    """Ranges straddling erasure-block boundaries must assemble exactly
    (the default block is 4 MiB, so suite-sized objects are often
    single-block — this pins multi-block coverage explicitly)."""
    import numpy as np
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=1)
    obj.make_bucket("xb")
    bs = obj.block_size
    body = np.random.default_rng(5).integers(
        0, 256, 2 * bs + 12345, dtype=np.uint8).tobytes()
    obj.put_object("xb", "o", io.BytesIO(body), len(body))
    for off, ln in ((bs - 7, 14),              # straddles block 0/1
                    (2 * bs - 3, 100),         # straddles block 1/2
                    (bs - 1, bs + 2),          # spans a whole block
                    (0, len(body)),            # everything
                    (len(body) - 5, 5)):       # tail
        sink = io.BytesIO()
        obj.get_object("xb", "o", sink, offset=off, length=ln)
        assert sink.getvalue() == body[off:off + ln], (off, ln)
