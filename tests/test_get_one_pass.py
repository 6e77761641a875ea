"""A GET takes ONE quorum metadata pass, shared by its headers and its
body (``get_object_n_info``, the reference's GetObjectNInfo): the counter
``minio_tpu_objectlayer_quorum_meta_reads_total{op}`` over real HTTP for
every body route, what a held handle serves after an overwrite, and the
layers that forward or wrap the entry."""
import hashlib
import io
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from s3client import S3Client  # noqa: E402

from minio_tpu.cache import CacheObjects  # noqa: E402
from minio_tpu.crypto import kms as kms_mod  # noqa: E402
from minio_tpu.fs import FSObjects  # noqa: E402
from minio_tpu.objectlayer import (ErasureObjects, ErasureSets,  # noqa: E402
                                   ObjectOptions, ServerPools)
from minio_tpu.objectlayer import datatypes as dt  # noqa: E402
from minio_tpu.obs import metrics as mx  # noqa: E402
from minio_tpu.server import S3Server  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402
from minio_tpu.utils import errors  # noqa: E402
from minio_tpu.utils.hashreader import etag_from_parts  # noqa: E402

AK, SK = "onepassak", "onepasssk"
BUCKET = "onepass"
MIB = 1 << 20
FAMILY = "minio_tpu_objectlayer_quorum_meta_reads_total"
SSE_S3 = {"x-amz-server-side-encryption": "AES256"}
#: unequal parts, so a range can straddle the first boundary
PART_SIZES = (5 * MIB + 70001, 5 * MIB, 1000)


def body_of(seed: int, size: int) -> bytes:
    return np.random.default_rng([30, seed]).bytes(size)


def passes(op: str) -> float:
    return mx.counters_snapshot().get(f'{FAMILY}{{op="{op}"}}', 0.0)


PLAIN = body_of(1, MIB + 17)
#: under the 128 KiB an object is kept at as a shard a drive inside
#: xl.meta: the metadata pass brings the shards, the body is decoded from
#: them in memory
INLINE = body_of(2, 1000)
TEXT = b"compressible line of text\n" * 8000  # ~200 KB, stored compressed
PARTS = [body_of(10 + i, n) for i, n in enumerate(PART_SIZES)]
MULTIPART = b"".join(PARTS)
ACROSS = (PART_SIZES[0] - 4097, PART_SIZES[0] + 70000)  # first boundary


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """One 4+2 server with compression and SSE-S3 available, holding one
    object for each body route; the PUT responses' ETags by key."""
    pytest.importorskip("cryptography")
    os.environ["MINIO_TPU_COMPRESSION"] = "on"
    old_kms = kms_mod._kms
    kms_mod.set_kms(kms_mod.LocalKMS(bytes.fromhex("5a" * 32)))
    tmp = tmp_path_factory.mktemp("onepass")
    obj = ErasureObjects([XLStorage(str(tmp / f"d{i}")) for i in range(6)],
                         default_parity=2)
    server = S3Server(obj, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    server.start_background()
    c = S3Client(server.endpoint(), AK, SK)
    assert c.request("PUT", f"/{BUCKET}").status_code == 200
    etags = {}
    for key, body in (("plain.dat", PLAIN), ("small.dat", INLINE),
                      ("log.txt", TEXT)):
        r = c.request("PUT", f"/{BUCKET}/{key}", body=body)
        assert r.status_code == 200, r.text
        etags[key] = r.headers["ETag"]
    from minio_tpu.utils.compress import META_COMPRESSION
    assert [k for k in etags if obj.get_object_info(BUCKET, k).internal.get(
        META_COMPRESSION)] == ["log.txt"]
    r = c.request("POST", f"/{BUCKET}/mp-sse", query={"uploads": ""},
                  headers=SSE_S3)
    assert r.status_code == 200, r.text
    uid = re.search(r"<UploadId>([^<]+)</UploadId>", r.text).group(1)
    part_etags = []
    for n, body in enumerate(PARTS, 1):
        r = c.request("PUT", f"/{BUCKET}/mp-sse", body=body,
                      query={"partNumber": str(n), "uploadId": uid})
        assert r.status_code == 200, r.text
        part_etags.append(r.headers["ETag"].strip('"'))
    xml = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>\"{e}\"</ETag></Part>"
        for n, e in enumerate(part_etags, 1)) + "</CompleteMultipartUpload>"
    r = c.request("POST", f"/{BUCKET}/mp-sse", query={"uploadId": uid},
                  body=xml.encode())
    assert r.status_code == 200, r.text
    etags["mp-sse"] = f'"{etag_from_parts(part_etags)}"'
    yield c, server, etags
    server.shutdown()
    kms_mod._kms = old_kms
    os.environ.pop("MINIO_TPU_COMPRESSION", None)


WHOLE = {"plain.dat": PLAIN, "small.dat": INLINE, "log.txt": TEXT,
         "mp-sse": MULTIPART}
#: (key, Range header, the body it must return, its place in the object)
ROUTES = {
    "plain": ("plain.dat", None, PLAIN, None),
    "ranged": ("plain.dat", "bytes=4096-1000000", PLAIN[4096:1000001],
               f"bytes 4096-1000000/{len(PLAIN)}"),
    "inline": ("small.dat", None, INLINE, None),
    "compressed": ("log.txt", None, TEXT, None),
    "compressed-ranged": ("log.txt", "bytes=100000-100999",
                          TEXT[100000:101000],
                          f"bytes 100000-100999/{len(TEXT)}"),
    "sse-multipart": ("mp-sse", None, MULTIPART, None),
    "sse-multipart-across-parts": (
        "mp-sse", f"bytes={ACROSS[0]}-{ACROSS[1]}",
        MULTIPART[ACROSS[0]:ACROSS[1] + 1],
        f"bytes {ACROSS[0]}-{ACROSS[1]}/{len(MULTIPART)}"),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_one_get_is_one_quorum_metadata_pass(env, route):
    """Exactly one pass a GET on every body route, and the answer the
    two-pass handler gave: the body bit for bit, the headers HEAD (whose
    ``get_object_info`` pass is untouched) gives for the same key."""
    c, _, etags = env
    key, rng, want, content_range = ROUTES[route]
    head = c.request("HEAD", f"/{BUCKET}/{key}")
    assert head.status_code == 200
    before = passes("get"), passes("head")
    r = c.request("GET", f"/{BUCKET}/{key}",
                  headers={"Range": rng} if rng else None)
    assert (passes("get") - before[0], passes("head") - before[1]) == (1, 0)
    assert r.status_code == (206 if rng else 200)
    assert hashlib.sha256(r.content).digest() == \
        hashlib.sha256(want).digest()
    assert r.headers["Content-Length"] == str(len(want))
    assert r.headers.get("Content-Range") == content_range
    assert r.headers["ETag"] == etags[key]
    assert head.headers["Content-Length"] == str(len(WHOLE[key]))
    for name in ("ETag", "Last-Modified", "Content-Type", "Accept-Ranges",
                 "x-amz-server-side-encryption"):
        assert r.headers.get(name) == head.headers.get(name), name
    assert r.headers.get("x-amz-server-side-encryption") == (
        "AES256" if key == "mp-sse" else None)


def test_one_head_is_one_pass_and_an_absent_key_costs_no_second(env):
    c = env[0]
    before = passes("get"), passes("head")
    assert c.request("HEAD", f"/{BUCKET}/plain.dat").status_code == 200
    assert (passes("get") - before[0], passes("head") - before[1]) == (0, 1)
    r = c.request("GET", f"/{BUCKET}/never-put")
    assert r.status_code == 404 and "<Code>NoSuchKey</Code>" in r.text
    assert (passes("get") - before[0], passes("head") - before[1]) == (1, 1)


def test_select_reads_the_object_it_described_in_one_pass(env):
    c = env[0]
    assert c.request("PUT", f"/{BUCKET}/rows.csv",
                     body=b"a,b\n1,2\n3,4\n").status_code == 200
    xml = ("<SelectObjectContentRequest><Expression>select * from s3object"
           "</Expression><ExpressionType>SQL</ExpressionType>"
           "<InputSerialization><CSV><FileHeaderInfo>USE</FileHeaderInfo>"
           "</CSV></InputSerialization><OutputSerialization><CSV/>"
           "</OutputSerialization></SelectObjectContentRequest>")
    before = passes("get"), passes("head")
    r = c.request("POST", f"/{BUCKET}/rows.csv",
                  query={"select": "", "select-type": "2"},
                  body=xml.encode())
    assert r.status_code == 200 and b"1,2\n3,4\n" in r.content
    assert (passes("get") - before[0], passes("head") - before[1]) == (1, 0)


def test_every_served_body_passes_erasure_objects_get_object(env,
                                                             monkeypatch):
    """The benchmark's control ``cpu_run.py --break get-byte`` breaks the
    served GET by replacing ``ErasureObjects.get_object``: the handle's
    read has to call in there, or that control stops biting (it did, in
    this PR's first draft) and ``correct`` is no longer shown to see a
    wrong body."""
    c = env[0]
    seen = []
    orig = ErasureObjects.get_object

    def get_object(self, bucket, key, writer, *a, **kw):
        seen.append((key, a, sorted(kw)))
        return orig(self, bucket, key, writer, *a, **kw)
    monkeypatch.setattr(ErasureObjects, "get_object", get_object)
    for key in ("plain.dat", "log.txt", "mp-sse"):
        before = passes("get")
        r = c.request("GET", f"/{BUCKET}/{key}",
                      headers={"Range": "bytes=5-104"})
        assert r.status_code == 206 and r.content == WHOLE[key][5:105]
        assert passes("get") - before == 1
    assert [k for k, _, _ in seen] == ["plain.dat", "log.txt", "mp-sse"]
    assert seen[0][1:] == ((5, 100), ["held"])


@pytest.mark.parametrize("headers", [None, SSE_S3], ids=["plain", "sse-s3"])
def test_one_part_put_is_one_pass_over_the_upload(env, headers):
    """The part handler reads the upload's record to tell an encrypted
    upload, and ``put_object_part`` writes from that pass: one a part, as
    one a GET (the uploaders of ``multipart-sse.8p4`` trade turns at the
    interpreter lock with its readers)."""
    c, _, _ = env
    key = "mp-plain" if headers is None else "mp-again"
    r = c.request("POST", f"/{BUCKET}/{key}", query={"uploads": ""},
                  headers=headers)
    uid = re.search(r"<UploadId>([^<]+)</UploadId>", r.text).group(1)
    etags = []
    for n, body in enumerate(PARTS[1:], 1):
        before = passes("upload")
        r = c.request("PUT", f"/{BUCKET}/{key}", body=body,
                      query={"partNumber": str(n), "uploadId": uid})
        assert r.status_code == 200, r.text
        assert passes("upload") - before == 1
        etags.append(r.headers["ETag"].strip('"'))
    xml = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>\"{e}\"</ETag></Part>"
        for n, e in enumerate(etags, 1)) + "</CompleteMultipartUpload>"
    assert c.request("POST", f"/{BUCKET}/{key}", query={"uploadId": uid},
                     body=xml.encode()).status_code == 200
    r = c.request("GET", f"/{BUCKET}/{key}")
    assert r.content == b"".join(PARTS[1:])
    assert r.headers["ETag"] == f'"{etag_from_parts(etags)}"'
    assert r.headers.get("x-amz-server-side-encryption") == (
        "AES256" if headers else None)
    # a part for an upload that is gone is refused, held pass or not
    r = c.request("PUT", f"/{BUCKET}/{key}", body=b"late",
                  query={"partNumber": "1", "uploadId": uid})
    assert r.status_code == 404 and "NoSuchUpload" in r.text


# --- the object layer --------------------------------------------------------


class Collect:
    """A sink that keeps what it was handed, however the read ends."""

    def __init__(self):
        self.got = bytearray()

    def write(self, b):
        self.got += b
        return len(b)


@pytest.fixture
def ol(tmp_path):
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(6)], default_parity=2)
    obj.make_bucket("b")
    return obj


@pytest.mark.parametrize("first,second", [
    (300_000, 200_000), (300_000, 300_000), (300_000, 100),
    (1000, 5000), (1000, 300_000)],
    ids=["shorter", "same-size", "to-small", "small-to-small",
         "small-to-larger"])
def test_a_held_handle_never_serves_another_version(ol, first, second):
    """get_object_n_info, an overwrite with another body, then read on the
    old handle: the first version's bytes or an error, and whatever
    reached the sink before an error is the first version's too."""
    a, b = body_of(41, first), body_of(42, second)
    ol.put_object("b", "k", io.BytesIO(a), len(a))
    oi, held = ol.get_object_n_info("b", "k")
    assert oi.size == first
    ol.put_object("b", "k", io.BytesIO(b), len(b))
    sink = Collect()
    try:
        held.read(sink)
    except (dt.ObjectAPIError, errors.StorageError):
        assert a.startswith(bytes(sink.got))
    else:
        assert len(sink.got) == oi.size
        assert hashlib.sha256(sink.got).digest() == \
            hashlib.sha256(a).digest()
    # and a fresh call sees the second version, headers and body alike
    oi2, held2 = ol.get_object_n_info("b", "k")
    sink = Collect()
    held2.read(sink)
    assert oi2.size == second and oi2.etag != oi.etag
    assert bytes(sink.got) == b


def test_data_inlined_in_xl_meta_rides_in_the_handle(ol):
    """A version at or under 128 KiB lives in its drives' xl.meta, a shard
    a drive (never the whole body), and the pass that read the journals
    brought the shards: a held handle still serves the version it read
    after an overwrite, which a handle over shard files cannot (its data
    directory is purged under it)."""
    a, b = body_of(44, 1000), body_of(45, 2000)
    ol.put_object("b", "k", io.BytesIO(a), len(a))
    before = mx.counters_snapshot().get(
        'minio_tpu_pipeline_get_blocks_total{route="inline"}', 0.0)
    oi, held = ol.get_object_n_info("b", "k")
    shards = [f.data for f in held.fis]
    assert all(s is not None and len(s) < len(a) for s in shards)
    assert len(set(shards)) == len(shards)  # each drive its OWN shard
    for d in ol.disks:
        assert [e for e in d.list_dir("b", "k")] == ["xl.meta"]
    ol.put_object("b", "k", io.BytesIO(b), len(b))
    sink = Collect()
    held.read(sink, 10, 500)
    assert bytes(sink.got) == a[10:510] and oi.size == len(a)
    assert ol.get_object_bytes("b", "k") == b
    assert mx.counters_snapshot()[
        'minio_tpu_pipeline_get_blocks_total{route="inline"}'] - before == 2


def test_handle_reads_ranges_repeatedly_and_checks_them(ol):
    a = body_of(43, 3 * MIB + 5)
    put = ol.put_object("b", "k", io.BytesIO(a), len(a))
    before = passes("get")
    oi, held = ol.get_object_n_info("b", "k")
    assert (oi.etag, oi.size) == (put.etag, len(a))
    for off, n in ((0, -1), (MIB - 3, 2 * MIB), (len(a) - 1, 1), (7, 0)):
        sink = Collect()
        assert held.read(sink, off, n) is oi
        assert bytes(sink.got) == (a[off:] if n < 0 else a[off:off + n])
    with pytest.raises(dt.InvalidRange):
        held.read(Collect(), len(a) - 1, 2)
    assert passes("get") - before == 1
    sink = Collect()
    assert ol.get_object("b", "k", sink, 5, 10).etag == put.etag
    assert bytes(sink.got) == a[5:15]
    assert passes("get") - before == 2


def test_n_info_answers_absent_and_deleted_as_get_object_info_does(tmp_path):
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=2)
    obj.make_bucket("b")
    for call in (obj.get_object_n_info, obj.get_object_info):
        with pytest.raises(dt.BucketNotFound):
            call("nope", "k")
        with pytest.raises(dt.ObjectNotFound):
            call("b", "k")
        with pytest.raises(dt.ObjectNameInvalid):
            call("b", "../k")
    versioned = ObjectOptions(versioned=True)
    v1 = obj.put_object("b", "k", io.BytesIO(b"one"), 3, versioned)
    marker = obj.delete_object("b", "k", versioned)
    assert marker.delete_marker
    for call in (obj.get_object_n_info, obj.get_object_info):
        with pytest.raises(dt.ObjectNotFound):
            call("b", "k")
        with pytest.raises(dt.MethodNotAllowed):
            call("b", "k", ObjectOptions(version_id=marker.version_id))
    oi, held = obj.get_object_n_info(
        "b", "k", ObjectOptions(version_id=v1.version_id))
    sink = Collect()
    held.read(sink)
    assert oi.version_id == v1.version_id and bytes(sink.got) == b"one"


def _disks(tmp_path, n, prefix):
    return [XLStorage(str(tmp_path / f"{prefix}{i}")) for i in range(n)]


def _sets(tmp_path):
    return ErasureSets(_disks(tmp_path, 8, "s"), 2, 4, default_parity=2)


def _pools(tmp_path):
    return ServerPools([
        ErasureSets(_disks(tmp_path, 4, "p0d"), 1, 4, default_parity=2),
        ErasureSets(_disks(tmp_path, 4, "p1d"), 1, 4, default_parity=2)])


def _fs(tmp_path):
    return FSObjects(str(tmp_path / "fs"))


def _cache(tmp_path):
    inner = ErasureObjects(_disks(tmp_path, 4, "c"), default_parity=1)
    return CacheObjects(inner, str(tmp_path / "cache"))


@pytest.mark.parametrize("make,own_passes", [
    (_sets, 1), (_pools, None), (_fs, 0), (_cache, None)],
    ids=["sets", "pools", "fs", "cache"])
def test_every_layer_names_the_entry(tmp_path, make, own_passes):
    """Sets and pools forward it to the set that owns the key (one pass);
    a layer with no metadata to hold answers with its two calls."""
    layer = make(tmp_path)
    layer.make_bucket("b")
    bodies = {f"k{i}": body_of(50 + i, 200_000 + i) for i in range(4)}
    for k, v in bodies.items():
        layer.put_object("b", k, io.BytesIO(v), len(v))
    for k, v in bodies.items():
        before = passes("get")
        oi, held = layer.get_object_n_info("b", k)
        sink = Collect()
        held.read(sink, 10, 1000)
        assert oi.size == len(v) and bytes(sink.got) == v[10:1010]
        if own_passes is not None:
            assert passes("get") - before == own_passes
    with pytest.raises(dt.ObjectNotFound):
        layer.get_object_n_info("b", "absent")


@pytest.mark.parametrize("make,own_passes", [(_sets, 0), (_pools, 1)],
                         ids=["sets", "pools"])
def test_a_part_is_written_from_the_upload_record_it_is_handed(
        tmp_path, make, own_passes):
    """``put_object_part(..., upload=get_multipart_info(...))`` makes no
    pass of its own (pools still look for the pool that has the upload)."""
    from minio_tpu.objectlayer.datatypes import CompletePart
    layer = make(tmp_path)
    layer.make_bucket("b")
    uid = layer.new_multipart_upload("b", "k")
    info = layer.get_multipart_info("b", "k", uid)
    v = body_of(62, 5 * MIB + 3)
    for upload, want in ((info, own_passes), (None, own_passes + 1)):
        before = passes("upload")
        pi = layer.put_object_part("b", "k", uid, 1, io.BytesIO(v), len(v),
                                   upload=upload)
        assert passes("upload") - before == want
    layer.complete_multipart_upload("b", "k", uid,
                                    [CompletePart(1, pi.etag)])
    sink = Collect()
    layer.get_object("b", "k", sink)
    assert bytes(sink.got) == v


def test_pools_find_the_object_in_the_second_pool(tmp_path):
    pools = _pools(tmp_path)
    pools.make_bucket("b")
    v = body_of(60, 150_000)
    pools.pools[1].put_object("b", "k", io.BytesIO(v), len(v))
    oi, held = pools.get_object_n_info("b", "k")
    sink = Collect()
    held.read(sink)
    assert oi.size == len(v) and bytes(sink.got) == v


def test_the_cache_serves_the_body_of_its_own_entry(tmp_path):
    """``CacheObjects`` delegates unknown names to the layer it wraps: the
    entry must not hand out that layer's handle, or no GET would ever be
    served from, or stored in, the cache."""
    co = _cache(tmp_path)
    co.make_bucket("b")
    v = body_of(61, 256 << 10)
    co.put_object("b", "k", io.BytesIO(v), len(v))
    for want_hits in (0, 1):
        oi, body = co.get_object_n_info("b", "k")
        sink = Collect()
        body.read(sink)
        assert oi.size == len(v) and bytes(sink.got) == v
        assert co.hits == want_hits
