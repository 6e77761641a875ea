"""A version at or under 128 KiB lives in its drives' ``xl.meta`` as k+m
bitrot-framed erasure shards, one a drive (ISSUE 39): no data directory, no
``part.1``, nothing staged, and never a whole copy of the body. Over 8+4
and 4+2 on ``XLStorage`` drives, with seeded bodies, held to the plain
references ``benchmark/lib/inline_ref.py`` and ``hh_ref.py`` (which import
nothing of the program):

* which sizes are inline and which are not: layout on every drive, body,
  ETag, ranged reads, bytes at rest;
* read-back with any ``parity`` drives' copies gone, refusal with one more;
* bitrot inside a drive's ``xl.meta`` (trailer CRC made good): served right,
  charged as a deep heal, found by ``verify_file``, healed byte-equal; with
  ``parity`` + 1 such drives refused, never served wrong;
* an emptied drive healed to the ``xl.meta`` it held before;
* replacement in every direction leaves no ``Data`` entry and no data
  directory behind; DELETE, delete markers, versions;
* write quorum and parked heal debt with drives offline; SSE-S3; multipart;
  copy; metadata rewrites; a remote drive over the storage RPC."""
import hashlib
import io
import json
import os
import shutil
import struct
import sys
import zlib
from dataclasses import replace

import numpy as np
import pytest

from minio_tpu.objectlayer import ErasureObjects
from minio_tpu.objectlayer import datatypes as dt
from minio_tpu.objectlayer.datatypes import CompletePart, ObjectOptions
from minio_tpu.objectlayer.metadata import hash_order
from minio_tpu.obs import metrics as mx
from minio_tpu.obs import spans as sp
from minio_tpu.storage import XLStorage
from minio_tpu.storage.xlmeta import SMALL_FILE_THRESHOLD, XLMeta
from minio_tpu.storage.xlstorage import META_TMP
from minio_tpu.utils import errors

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))
import inline_ref  # noqa: E402


def _geometry(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["geometry"]


#: id -> (drives, parity, the configuration's stated geometry)
GEOMS = {"8p4": (12, 4, _geometry("xl-8p4-12d")),
         "4p2": (6, 2, _geometry("xl-4p2-6d"))}
KIB = 1 << 10
INLINE_SIZES = (1, 100, 4096, 65536, 131071, 131072)
FILE_SIZES = (131073, 262144, 0)


def body_of(seed, size):
    return np.random.default_rng([39, seed]).bytes(size)


@pytest.fixture(params=sorted(GEOMS))
def geo(request, tmp_path):
    """(layer, drive directories, parity, geometry) of one fresh set."""
    n, parity, geom = GEOMS[request.param]
    dirs = [str(tmp_path / f"d{i:02d}") for i in range(n)]
    ol = ErasureObjects([XLStorage(d) for d in dirs], default_parity=parity)
    ol.make_bucket("b")
    return ol, dirs, parity, geom


def obj_dirs(dirs, key, bucket="b"):
    return [os.path.join(d, bucket, key) for d in dirs]


def journal(obj_dir) -> XLMeta:
    with open(os.path.join(obj_dir, "xl.meta"), "rb") as f:
        return XLMeta.load(f.read())


def held_to_reference(dirs, key, geom, body, layout):
    """The drives as they lie, by the plain reference: the body, every
    frame's digest, the parity shards, the layout."""
    got = inline_ref.check_object(obj_dirs(dirs, key), geom, len(body),
                                  hashlib.sha256(body).hexdigest())
    assert (got["body_mismatch"], got["digest_bad"],
            got["parity_mismatch"]) == (0, 0, 0), got["why"]
    assert got["layout"] == layout and got["size"] == len(body)
    return got


def assert_inline(dirs, key, n_versions=1, tmp=True):
    """xl.meta alone in the object's directory on every drive, carrying
    that drive's shard: no two drives hold the same bytes; nothing left
    staged (``tmp=False`` beside a live server, whose health probe and
    healers pass through ``.minio.sys/tmp``)."""
    shards = []
    for od in obj_dirs(dirs, key):
        assert os.listdir(od) == ["xl.meta"], od
        held = journal(od).data
        assert len(held) == n_versions
        shards += list(held.values())
    assert len(set(shards)) == len(shards) or len(shards[0]) <= 33
    for d in dirs if tmp else ():
        assert os.listdir(os.path.join(d, META_TMP)) == []
    return shards


def assert_files(dirs, key):
    for od in obj_dirs(dirs, key):
        names = sorted(os.listdir(od))
        assert len(names) == 2 and names[1] == "xl.meta", names
        assert os.listdir(os.path.join(od, names[0])) == ["part.1"]
        assert journal(od).data == {}


# --- which sizes are inline --------------------------------------------------

@pytest.mark.parametrize("size", INLINE_SIZES + FILE_SIZES + (-1,))
def test_layout_body_etag_ranges_and_bytes_at_rest_by_size(geo, size):
    ol, dirs, parity, geom = geo
    unknown = size < 0
    body = body_of(size % 1000, 65536 if unknown else size)
    oi = ol.put_object("b", "k/o", io.BytesIO(body), size)
    assert oi.size == len(body) and oi.etag == hashlib.md5(body).hexdigest()
    inline = not unknown and 0 < size <= SMALL_FILE_THRESHOLD
    assert inline == (size in INLINE_SIZES)
    if inline:
        shards = assert_inline(dirs, "k/o")
        # a shard a drive, never a whole copy: (k+m)/k of the body
        k = len(dirs) - parity
        assert all(len(s) < max(len(body), 64) for s in shards) or k == 1
    else:
        assert_files(dirs, "k/o")
    assert ol.get_object_bytes("b", "k/o") == body
    assert ol.get_object_info("b", "k/o").etag == oi.etag
    for off, n in ((0, 1), (len(body) // 3, len(body) // 2),
                   (max(len(body) - 1, 0), 1)):
        if off + n > len(body):
            continue
        sink = io.BytesIO()
        ol.get_object("b", "k/o", sink, off, n)
        assert sink.getvalue() == body[off:off + n], (off, n)
    if len(body):
        got = held_to_reference(dirs, "k/o", geom, body,
                                "inline" if inline else "files")
        assert got["bytes"] <= inline_ref.at_rest_limit(len(body), geom)


def test_inline_shard_is_what_a_part_file_would_hold(geo):
    """Byte for byte: the shard in ``Data`` is the ``part.1`` the same
    body gets as shard files (written here with its size undeclared)."""
    ol, dirs, parity, geom = geo
    body = body_of(7, 65536 + 13)
    ol.put_object("b", "same", io.BytesIO(body), len(body))
    inline = [next(iter(journal(od).data.values()))
              for od in obj_dirs(dirs, "same")]
    ol.put_object("b", "same", io.BytesIO(body), -1)
    files = []
    for od in obj_dirs(dirs, "same"):
        ddir = [n for n in os.listdir(od) if n != "xl.meta"][0]
        with open(os.path.join(od, ddir, "part.1"), "rb") as f:
            files.append(f.read())
    assert inline == files


# --- drives gone -------------------------------------------------------------

def test_reads_back_with_parity_drives_gone_and_refuses_with_one_more(geo):
    ol, dirs, parity, geom = geo
    n, k = len(dirs), len(dirs) - parity
    body = body_of(1, 65536)
    order = hash_order("b/gone", n)   # order[i]: shard index on drive i
    data_drives = [i for i in range(n) if order[i] <= k]
    parity_drives = [i for i in range(n) if order[i] > k]
    for lost in (data_drives[:parity], parity_drives[:parity],
                 data_drives[-1:] + parity_drives[:parity - 1]):
        ol.put_object("b", "gone", io.BytesIO(body), len(body))
        for i in lost:
            shutil.rmtree(os.path.join(dirs[i], "b", "gone"))
        assert ol.get_object_bytes("b", "gone") == body
        sink = io.BytesIO()
        ol.get_object("b", "gone", sink, 1000, 3000)
        assert sink.getvalue() == body[1000:4000]
    for i in [i for i in range(n) if i not in lost][:1]:
        shutil.rmtree(os.path.join(dirs[i], "b", "gone"))
    with pytest.raises(dt.InsufficientReadQuorum):
        ol.get_object_bytes("b", "gone")


def test_a_lost_data_shard_is_rebuilt_on_the_dispatch_queue(geo, monkeypatch):
    """The rebuild of a degraded inline GET is routed as the ``plain``
    route of shard files is: through the dispatch queue, whose CPU route
    (``MINIO_TPU_DISPATCH_MODE=cpu``, the benchmark's ``verify_env``)
    loads and compiles no device program."""
    from minio_tpu.obs import device as dev
    from minio_tpu.runtime.dispatch import global_queue
    ol, dirs, parity, geom = geo
    body = body_of(8, 65536)
    ol.put_object("b", "q", io.BytesIO(body), len(body))
    shutil.rmtree(os.path.join(
        dirs[hash_order("b/q", len(dirs)).index(1)], "b", "q"))
    monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", "cpu")
    before = global_queue().stats()
    compiles = dev.status()["compile"]["compiles_total"]
    assert ol.get_object_bytes("b", "q") == body
    after = global_queue().stats()
    assert after["cpu_items"] == before["cpu_items"] + 1
    assert after["device_items"] == before["device_items"]
    assert dev.status()["compile"]["compiles_total"] == compiles


# --- bitrot inside xl.meta ---------------------------------------------------

def rot_inline_shard(obj_dir, at=40):
    """Flip one byte inside the shard this drive's xl.meta carries and make
    the trailer's CRC good again: the journal parses, the shard is rotten."""
    path = os.path.join(obj_dir, "xl.meta")
    with open(path, "rb") as f:
        blob = bytearray(f.read())
    shard = next(iter(XLMeta.load(bytes(blob)).data.values()))
    pos = bytes(blob).index(shard) + min(at, len(shard) - 1)
    blob[pos] ^= 0x5A
    blob[-4:] = struct.pack("<I", zlib.crc32(bytes(blob[:-8])) & 0xFFFFFFFF)
    with open(path, "wb") as f:
        f.write(bytes(blob))
    XLMeta.load(bytes(blob))  # still a valid journal


def test_bitrot_in_an_inline_shard_is_found_served_right_and_healed(geo):
    ol, dirs, parity, geom = geo
    n, k = len(dirs), len(dirs) - parity
    body = body_of(2, 65536)
    ol.put_object("b", "rot", io.BytesIO(body), len(body))
    before = [open(os.path.join(od, "xl.meta"), "rb").read()
              for od in obj_dirs(dirs, "rot")]
    charged = []
    ol.on_partial = lambda b, o, v="", scan_mode="normal", **kw: \
        charged.append((o, scan_mode))
    victim = hash_order("b/rot", n).index(1)    # a DATA shard's drive
    rot_inline_shard(obj_dirs(dirs, "rot")[victim])
    assert inline_ref.check_object(obj_dirs(dirs, "rot"), geom)[
        "digest_bad"] == 1
    # served right, from parity, and charged as a DEEP heal
    assert ol.get_object_bytes("b", "rot") == body
    assert charged == [("rot", "deep")]
    disk = ol.disks[victim]
    fi = disk.read_version("b", "rot", read_data=True)
    disk.check_parts("b", "rot", fi)    # the framed length is right
    with pytest.raises(errors.FileCorrupt):
        disk.verify_file("b", "rot", fi)
    with pytest.raises(errors.FileCorrupt):     # read without its data too
        disk.verify_file("b", "rot", disk.read_version("b", "rot"))
    # a normal heal's length check cannot see it, a deep heal does
    assert ol.heal_object("b", "rot").before_state.count("ok") == n
    res = ol.heal_object("b", "rot", scan_mode="deep")
    assert res.before_state[victim] == "corrupt"
    assert res.after_state == ["ok"] * n
    assert [open(os.path.join(od, "xl.meta"), "rb").read()
            for od in obj_dirs(dirs, "rot")] == before
    # a shard of the wrong LENGTH is what check_parts is for
    short = replace(fi, data=fi.data[:-1])
    with pytest.raises(errors.FileCorrupt):
        disk.check_parts("b", "rot", short)
    # parity + 1 drives rotten: refused, never served wrong
    for i in range(parity + 1):
        rot_inline_shard(obj_dirs(dirs, "rot")[i], at=50 + i)
    sink = io.BytesIO()
    with pytest.raises(dt.InsufficientReadQuorum):
        ol.get_object("b", "rot", sink)
    assert sink.getvalue() == b""


# --- heal --------------------------------------------------------------------

@pytest.mark.parametrize("shard", ["data", "parity"])
def test_an_emptied_drive_heals_to_the_xl_meta_it_held(geo, shard):
    ol, dirs, parity, geom = geo
    n, k = len(dirs), len(dirs) - parity
    body = body_of(3, 100 * KIB)
    ol.put_object("b", "h/o", io.BytesIO(body), len(body))
    order = hash_order("b/h/o", n)
    victim = order.index(1 if shard == "data" else n)
    path = os.path.join(dirs[victim], "b", "h", "o", "xl.meta")
    before = open(path, "rb").read()
    shutil.rmtree(os.path.join(dirs[victim], "b"))
    os.makedirs(os.path.join(dirs[victim], "b"))
    c0 = mx.counters_snapshot().get(
        'minio_tpu_objectlayer_inline_versions_total{op="heal"}', 0.0)
    res = ol.heal_object("b", "h/o")
    assert res.before_state[victim] == "missing"
    assert res.after_state == ["ok"] * n
    assert open(path, "rb").read() == before    # its OWN shard, byte-equal
    assert mx.counters_snapshot()[
        'minio_tpu_objectlayer_inline_versions_total{op="heal"}'] - c0 == 1
    assert_inline(dirs, "h/o")
    held_to_reference(dirs, "h/o", geom, body, "inline")
    # and it serves with `parity` OTHER drives gone
    for i in [i for i in range(n) if i != victim][:parity]:
        shutil.rmtree(os.path.join(dirs[i], "b", "h", "o"))
    assert ol.get_object_bytes("b", "h/o") == body


# --- replacement -------------------------------------------------------------

@pytest.mark.parametrize("first,second", [(64 * KIB, 32 * KIB),
                                          (64 * KIB, 300 * KIB),
                                          (300 * KIB, 64 * KIB)],
                         ids=["inline-over-inline", "files-over-inline",
                              "inline-over-files"])
def test_a_replaced_version_leaves_nothing_behind(geo, first, second):
    ol, dirs, parity, geom = geo
    a, b = body_of(4, first), body_of(5, second)
    ol.put_object("b", "r", io.BytesIO(a), len(a))
    ol.put_object("b", "r", io.BytesIO(b), len(b))
    assert ol.get_object_bytes("b", "r") == b
    if second <= SMALL_FILE_THRESHOLD:
        assert_inline(dirs, "r")
    else:
        assert_files(dirs, "r")
    held_to_reference(dirs, "r", geom, b,
                      "inline" if second <= SMALL_FILE_THRESHOLD else "files")
    ol.delete_object("b", "r")
    for od in obj_dirs(dirs, "r"):
        assert not os.path.exists(od)
    with pytest.raises(dt.ObjectNotFound):
        ol.get_object_info("b", "r")


def test_versions_and_a_delete_marker_in_a_versioned_bucket(geo):
    ol, dirs, parity, geom = geo
    ver = ObjectOptions(versioned=True)
    a, b = body_of(6, 64 * KIB), body_of(7, 10 * KIB)
    va = ol.put_object("b", "v", io.BytesIO(a), len(a), ver).version_id
    vb = ol.put_object("b", "v", io.BytesIO(b), len(b), ver).version_id
    assert va and vb and va != vb
    assert_inline(dirs, "v", n_versions=2)
    assert ol.get_object_bytes("b", "v") == b
    assert ol.get_object_bytes("b", "v", ObjectOptions(version_id=va)) == a
    marker = ol.delete_object("b", "v", ver)
    assert marker.delete_marker
    with pytest.raises(dt.ObjectNotFound):
        ol.get_object_info("b", "v")
    assert ol.get_object_bytes("b", "v", ObjectOptions(version_id=vb)) == b
    assert_inline(dirs, "v", n_versions=2)
    # one version deleted by id: its Data entry goes with it
    ol.delete_object("b", "v", ObjectOptions(version_id=va, versioned=True))
    for od in obj_dirs(dirs, "v"):
        m = journal(od)
        assert len(m.data) == 1 and len(m.versions) == 2
    assert ol.get_object_bytes("b", "v", ObjectOptions(version_id=vb)) == b
    with pytest.raises((dt.VersionNotFound, dt.ObjectNotFound)):
        ol.get_object_bytes("b", "v", ObjectOptions(version_id=va))


def test_a_metadata_rewrite_leaves_every_drive_its_own_shard(geo):
    """Tags and a self-copy rewrite xl.meta in place on every drive: each
    keeps ITS shard (the quorum pick's is another drive's)."""
    ol, dirs, parity, geom = geo
    body = body_of(8, 64 * KIB)
    ol.put_object("b", "t", io.BytesIO(body), len(body))
    shards = assert_inline(dirs, "t")
    ol.put_object_tags("b", "t", "a=1&b=2")
    oi = ol.get_object_info("b", "t")
    ol.copy_object("b", "t", "b", "t", oi, ObjectOptions(),
                   ObjectOptions(user_defined={"x-amz-meta-k": "v"}))
    assert assert_inline(dirs, "t") == shards
    assert ol.get_object_tags("b", "t") == "a=1&b=2"
    assert ol.get_object_bytes("b", "t") == body
    held_to_reference(dirs, "t", geom, body, "inline")


# --- multipart, copy ---------------------------------------------------------

def test_a_one_part_multipart_upload_keeps_its_part_file(geo):
    ol, dirs, parity, geom = geo
    body = body_of(9, 64 * KIB)
    uid = ol.new_multipart_upload("b", "mp")
    part = ol.put_object_part("b", "mp", uid, 1, io.BytesIO(body), len(body))
    ol.complete_multipart_upload("b", "mp", uid,
                                 [CompletePart(1, part.etag)])
    assert_files(dirs, "mp")
    assert ol.get_object_bytes("b", "mp") == body
    held_to_reference(dirs, "mp", geom, body, "files")


def test_copy_of_an_inline_source_is_inline(geo):
    ol, dirs, parity, geom = geo
    body = body_of(10, 64 * KIB)
    ol.put_object("b", "src", io.BytesIO(body), len(body))
    oi = ol.copy_object("b", "src", "b", "dst/copy",
                        ol.get_object_info("b", "src"), ObjectOptions(),
                        ObjectOptions())
    assert oi.etag == hashlib.md5(body).hexdigest()
    assert_inline(dirs, "dst/copy")
    assert ol.get_object_bytes("b", "dst/copy") == body
    held_to_reference(dirs, "dst/copy", geom, body, "inline")


# --- counters and spans ------------------------------------------------------

def test_counters_and_span_attributes_say_inline(geo):
    ol, dirs, parity, geom = geo
    fam = "minio_tpu_objectlayer_inline_"
    routes = "minio_tpu_pipeline_get_blocks_total"

    def snap():
        return {k: v for k, v in mx.counters_snapshot().items()
                if k.startswith((fam, routes))}

    def moved(c0):
        return {k.removeprefix("minio_tpu_"): v - c0.get(k, 0.0)
                for k, v in snap().items() if v != c0.get(k, 0.0)}

    small, big = body_of(11, 64 * KIB), body_of(12, 200 * KIB)
    root, tok = sp.begin_request(sp.new_trace_id())
    try:
        c0 = snap()
        ol.put_object("b", "s", io.BytesIO(small), len(small))
        ol.put_object("b", "l", io.BytesIO(big), len(big))
        assert moved(c0) == {
            'objectlayer_inline_versions_total{op="put"}': 1.0,
            'objectlayer_inline_bytes_total{op="put"}': float(len(small))}
        c0 = snap()
        ol.get_object("b", "s", io.BytesIO(), 5, 1000)
        ol.get_object_bytes("b", "l")
        got = moved(c0)
        assert got.pop('pipeline_get_blocks_total{route="native_fd"}') == 1.0
        assert got == {
            'objectlayer_inline_versions_total{op="get"}': 1.0,
            'objectlayer_inline_bytes_total{op="get"}': 1000.0,
            'pipeline_get_blocks_total{route="inline"}': 1.0}
        with sp._lock:
            spans = [dict(s) for s in sp._active[root.trace_id]["spans"]]
    finally:
        sp.finish_request(root, tok, name="s3.putobject", duration_s=0.0,
                          status=200)
    said = [(s["name"], s["attrs"]["object"], s["attrs"].get("inline"))
            for s in spans if s["name"] in ("objectlayer.put_object",
                                            "objectlayer.get_object")]
    assert said == [("objectlayer.put_object", "s", True),
                    ("objectlayer.put_object", "l", None),
                    ("objectlayer.get_object", "s", True),
                    ("objectlayer.get_object", "l", None)]


# --- drives offline: write quorum and parked debt (8+4, served) --------------

@pytest.fixture
def served(tmp_path, monkeypatch):
    import test_drive_offline as off
    from minio_tpu.scanner import mrf as mrf_mod
    monkeypatch.setenv("MINIO_TPU_HEALTH_COOLDOWN_S", "0.3")
    monkeypatch.setattr(mrf_mod, "RETRY_BASE_S", 0.2)
    s = off.Set(str(tmp_path))
    yield s, off
    s.close()


def test_put_with_four_drives_offline_parks_its_debt_with_five_refuses(
        served):
    s, off = served
    body = body_of(13, 64 * KIB)
    for i in range(4):
        s.kill(i)
    c0 = off.counters("minio_tpu_mrf")
    r = s.c.put_object(off.BUCKET, "small", body)
    assert r.status_code == 200, r.text
    assert r.headers["ETag"].strip('"') == hashlib.md5(body).hexdigest()
    assert off.moved(c0, "minio_tpu_mrf_charges_total") == {
        'minio_tpu_mrf_charges_total{outcome="parked_known",'
        'source="write"}': 1.0}
    assert s.mrf.stats()["parked_offline"] == 1
    assert s.c.get_object(off.BUCKET, "small").content == body
    live = [d for d in s.dirs if os.path.isdir(d)]
    assert len(live) == 8
    assert_inline(live, "small", tmp=False)
    # the drives return: the debt is paid with each drive's own shard
    for i in range(4):
        s.revive(i, stale=True)
    off.wait_until(lambda: all(os.path.exists(os.path.join(
        d, off.BUCKET, "small", "xl.meta")) for d in s.dirs), timeout=30,
        msg="the returned drives get their shards")
    off.wait_until(lambda: s.mrf.stats()["parked_offline"] == 0, timeout=30)
    assert_inline(s.dirs, "small", tmp=False)
    held_to_reference(s.dirs, "small", off.GEOMETRY, body, "inline")
    # five dead: 7 < k = 8, refused at quorum, nothing parked, nothing left
    for i in range(5):
        s.kill(i)
    r = s.c.put_object(off.BUCKET, "small2", body)
    assert r.status_code == 503 and "<Code>SlowDownWrite</Code>" in r.text
    assert s.mrf.stats()["parked_offline"] == 0
    for d in s.dirs[5:]:
        assert not os.path.exists(os.path.join(d, off.BUCKET, "small2"))


# --- SSE-S3 ------------------------------------------------------------------

def test_an_sse_s3_object_of_64_kib_is_inline_and_opens(tmp_path):
    pytest.importorskip("cryptography")
    from minio_tpu.crypto import kms as kms_mod
    from minio_tpu.server import S3Server
    from s3client import S3Client
    old = kms_mod._kms
    kms_mod.set_kms(kms_mod.LocalKMS(bytes.fromhex("39" * 32)))
    dirs = [str(tmp_path / f"d{i}") for i in range(6)]
    obj = ErasureObjects([XLStorage(d) for d in dirs], default_parity=2)
    srv = S3Server(obj, "127.0.0.1", 0, access_key="sseak",
                   secret_key="ssesk123")
    srv.start_background()
    try:
        c = S3Client(srv.endpoint(), "sseak", "ssesk123")
        assert c.request("PUT", "/b").status_code == 200
        body = body_of(14, 64 * KIB)
        r = c.request("PUT", "/b/sealed.dat", body=body, headers={
            "x-amz-server-side-encryption": "AES256"})
        assert r.status_code == 200, r.text
        g = c.request("GET", "/b/sealed.dat")
        assert g.status_code == 200 and g.content == body
        assert g.headers["x-amz-server-side-encryption"] == "AES256"
        rg = c.request("GET", "/b/sealed.dat",
                       headers={"Range": "bytes=100-4195"})
        assert rg.status_code == 206 and rg.content == body[100:4196]
        # what is sharded is the SEALED stream: inline, and no run of the
        # plaintext at rest
        shards = assert_inline(dirs, "sealed.dat")
        assert body[:64] not in b"".join(shards)
        got = inline_ref.check_object(obj_dirs(dirs, "sealed.dat"),
                                      GEOMS["4p2"][2])
        assert (got["body_mismatch"], got["digest_bad"],
                got["parity_mismatch"], got["layout"]) == (0, 0, 0, "inline")
        assert got["size"] > len(body)
    finally:
        srv.shutdown()
        kms_mod._kms = old


# --- a remote drive ----------------------------------------------------------

def test_a_remote_drive_carries_the_shard_over_the_storage_rpc(tmp_path):
    """``tests/test_dist.py``'s set-up: a node serves its drives over the
    storage RPC; an erasure set of remote clients writes and reads an
    inline version through it (``rename_data`` carries the shard out,
    ``read_version(read_data=True)`` brings it back, a STAT leaves it)."""
    from minio_tpu.dist.node import Node
    from minio_tpu.dist.storage_rest import StorageRESTClient
    from test_dist import AK, SK, free_port
    port = free_port()
    dirs = [str(tmp_path / f"nd{i}") for i in range(4)]
    node = Node(dirs, local_url=f"http://127.0.0.1:{port}",
                address="127.0.0.1", port=port, access_key=AK,
                secret_key=SK, default_parity=2)
    node.start()
    clients = []
    try:
        url = f"http://127.0.0.1:{node.server.port}"
        clients = [StorageRESTClient(url, p, SK) for p in node.local_disks]
        assert not any(c.is_local() for c in clients)
        ol = ErasureObjects(clients, default_parity=2)
        ol.make_bucket("rb")
        body = body_of(15, 64 * KIB)
        ol.put_object("rb", "far", io.BytesIO(body), len(body))
        assert ol.get_object_bytes("rb", "far") == body
        sink = io.BytesIO()
        ol.get_object("rb", "far", sink, 7, 70)
        assert sink.getvalue() == body[7:77]
        shards = []
        for d in node.local_disks:
            od = os.path.join(d, "rb", "far")
            assert os.listdir(od) == ["xl.meta"]
            shards.append(next(iter(journal(od).data.values())))
        assert len(set(shards)) == 4
        for c, shard in zip(clients, shards):
            assert c.read_version("rb", "far", read_data=True).data == shard
            assert c.read_version("rb", "far").data is None
        # healed over the wire too
        shutil.rmtree(os.path.join(list(node.local_disks)[1], "rb", "far"))
        res = ol.heal_object("rb", "far")
        assert res.after_state == ["ok"] * 4
        assert next(iter(journal(os.path.join(
            list(node.local_disks)[1], "rb", "far")).data.values())) \
            == shards[1]
    finally:
        for c in clients:
            c.close()
        node.shutdown()
