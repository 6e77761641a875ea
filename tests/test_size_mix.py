"""A bucket that holds every size (ISSUE 44): inline versions, one-part
shard files and multipart uploads side by side in one erasure set. Over
8+4 and 4+2 on ``XLStorage`` drives with the configurations' stated
geometry and seeded bodies, every object is held to the plain reference
``benchmark/lib/sizes_ref.py`` (which imports nothing of the program):

* a ladder of sizes across every edge (the inline threshold, the fused
  ETag's floor, a block, a part): the layout its size names, the body,
  every frame's digest, the parity drives' plain encode, the ETag by its
  class's rule, ranged reads across a block and a part boundary, bytes at
  rest within the stated bound, read-back with ``parity`` drives gone;
* the route counters and span attributes say what the size names;
* a request's record carries ``object_bytes`` for PUT, GET, STAT, DELETE, a
  part and a Complete;
* ``sizes_ref`` fails an object whose layout is not its size's.

No case reads a clock."""
import hashlib
import io
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

from minio_tpu.objectlayer import ErasureObjects
from minio_tpu.objectlayer.datatypes import CompletePart
from minio_tpu.obs import attribution, timeline
from minio_tpu.obs import metrics as mx
from minio_tpu.obs import spans as sp
from minio_tpu.storage import XLStorage

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))
import sizes_ref  # noqa: E402

KIB, MIB = 1 << 10, 1 << 20


def _geometry(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["geometry"]


SIZES = _geometry("xl-8p4-12d-sizes")
#: id -> (drives, parity, the configuration's stated geometry; what the
#: clients do about parts is ``xl-8p4-12d-sizes``'s in both)
GEOMS = {"8p4": (12, 4, SIZES),
         "4p2": (6, 2, {**_geometry("xl-4p2-6d"), **{
             k: SIZES[k] for k in ("multipart_part_bytes",
                                   "multipart_min_bytes", "rs_field_poly",
                                   "rs_matrix")}})}
#: id -> (size, the part size the client uses: None = the geometry's)
LADDER = {
    "300B": (300, None),
    "inline_edge": (131072, None),
    "inline_edge+1": (131073, None),
    "1MiB-1": (MIB - 1, None),
    "1MiB": (MIB, None),
    "4MiB-1": (4 * MIB - 1, None),
    "4MiB": (4 * MIB, None),
    "4MiB+1": (4 * MIB + 1, None),
    "9MiB+7": (9 * MIB + 7, None),
    "16MiB_one_put": (16 * MIB, None),
    "5MiB+5MiB+1B": (10 * MIB + 1, 5 * MIB),
    "16MiB+16MiB+3B": (32 * MIB + 3, None),
}


def body_of(seed, size):
    return np.random.default_rng([44, seed]).bytes(size)


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


def put_as_a_client_would(ol, key, body, geom):
    """One PUT, or from ``multipart_min_bytes`` an upload of
    ``multipart_part_bytes`` parts: what ``lib/sizes_client.py`` sends."""
    sizes = sizes_ref.part_sizes(len(body), geom)
    if len(sizes) == 1:
        return ol.put_object("b", key, io.BytesIO(body), len(body))
    uid, parts, lo = ol.new_multipart_upload("b", key), [], 0
    for n, size in enumerate(sizes, start=1):
        pi = ol.put_object_part("b", key, uid, n,
                                io.BytesIO(body[lo: lo + size]), size)
        parts.append(CompletePart(n, pi.etag))
        lo += size
    return ol.complete_multipart_upload("b", key, uid, parts)


def held_to_reference(dirs, key, geom, body):
    got = sizes_ref.check(obj_dirs(dirs, key), geom, len(body),
                          hashlib.sha256(body).hexdigest())
    assert (got["body_mismatch"], got["digest_bad"], got["parity_mismatch"],
            got["layout_wrong"], got["bytes_over"]) == (0, 0, 0, 0, 0), \
        got["why"]
    return got


@pytest.mark.parametrize("case", sorted(LADDER))
def test_every_size_is_what_its_size_names(geo, case):
    ol, dirs, parity, geom = geo
    size, part = LADDER[case]
    if part is not None:
        geom = {**geom, "multipart_part_bytes": part,
                "multipart_min_bytes": part + 1}
    body = body_of(size % 997, size)
    oi = put_as_a_client_would(ol, "k/o", body, geom)
    # the ETag, by its class's rule; the layout, the body, the digests,
    # the parity and the bytes at rest, from the drives as they lie
    want = sizes_ref.etag_of(body, geom)
    assert oi.size == size and oi.etag == want
    assert ol.get_object_info("b", "k/o").etag == want
    layout, parts = sizes_ref.layout_of(size, geom)
    assert ("-" in want) == (len(parts) > 1)
    got = held_to_reference(dirs, "k/o", geom, body)
    assert got["layout"] == layout and got["size"] == size
    assert got["bytes"] <= got["limit"] < 2 * got["bytes"] + 12 * 4096 * 3
    # whole, and by ranges across a block's and a part's edge
    assert ol.get_object_bytes("b", "k/o") == body
    edges = {geom["block_bytes"], parts[0], size - 1, 1}
    for edge in sorted(e for e in edges if 0 < e < size):
        lo, hi = max(0, edge - 70_000), min(size, edge + 70_001)
        sink = io.BytesIO()
        ol.get_object("b", "k/o", sink, lo, hi - lo)
        assert sink.getvalue() == body[lo:hi], (case, lo, hi)
    # ``parity`` drives' copies gone: still bit-exact
    rng = np.random.default_rng([44, size])
    for i in rng.permutation(len(dirs))[:parity]:
        shutil.rmtree(obj_dirs(dirs, "k/o")[i])
    assert ol.get_object_bytes("b", "k/o") == body


@pytest.mark.parametrize("size,route", [
    (70 * KIB, "inline"), (300 * KIB, "file"), (11 * MIB, "multipart")])
def test_the_route_counters_and_span_attributes_say_what_the_size_names(
        geo, size, route):
    ol, dirs, parity, geom = geo
    geom = {**geom, "multipart_part_bytes": 5 * MIB,
            "multipart_min_bytes": 5 * MIB + 1}
    assert sizes_ref.route_of(size, geom) == route
    fam = "minio_tpu_objectlayer_put_"
    body = body_of(7, size)
    c0 = {k: v for k, v in mx.counters_snapshot().items()
          if k.startswith(fam)}
    assert len(c0) == 6         # three routes, there from the start
    root, tok = sp.begin_request(sp.new_trace_id())
    try:
        put_as_a_client_would(ol, "o", body, geom)
        ol.get_object_bytes("b", "o")
        with sp._lock:
            spans = [dict(s) for s in sp._active[root.trace_id]["spans"]]
    finally:
        sp.finish_request(root, tok, name="s3.putobject", duration_s=0.0,
                          status=200)
    moved = {k.removeprefix(fam): v - c0[k]
             for k, v in mx.counters_snapshot().items()
             if k.startswith(fam) and v != c0[k]}
    assert moved == {f'versions_total{{route="{route}"}}': 1.0,
                     f'bytes_total{{route="{route}"}}': float(size)}
    said = {s["name"]: s["attrs"].get("route") for s in spans}
    committed = "objectlayer.complete_multipart_upload" \
        if route == "multipart" else "objectlayer.put_object"
    assert said[committed] == route
    # a read names the layout it found, not how the version was sent
    assert said["objectlayer.get_object"] is None
    assert {s["name"]: s["attrs"].get("layout") for s in spans}[
        "objectlayer.get_object"] == route
    if route == "multipart":    # a part is no version: it names no route
        assert said["objectlayer.put_object_part"] is None


# --- a request's record ------------------------------------------------------------

AK, SK = "szak", "szsecret1"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from s3client import S3Client

    from minio_tpu.server.s3api import S3Server
    root = tmp_path_factory.mktemp("sizes")
    ol = ErasureObjects([XLStorage(str(root / f"d{i}")) for i in range(6)],
                        default_parity=2)
    srv = S3Server(ol, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    srv.start_background()
    c = S3Client(srv.endpoint(), AK, SK)
    assert c.put_bucket("b").status_code == 200
    yield srv, c
    srv.shutdown()


def _record(api: str, rid: str) -> dict:
    """The record of request ``rid`` (kept after the reply has gone out)."""
    for _ in range(400):
        for r in attribution.between(0.0, float("inf")) or ():
            if r["id"] == rid:
                assert r["api"] == api
                return r
        time.sleep(0.01)
    raise AssertionError(f"no record of {api} {rid}")


def _upload(c, key, parts):
    r = c.request("POST", f"/b/{key}", query={"uploads": ""})
    uid = r.text.split("<UploadId>")[1].split("</UploadId>")[0]
    out, etags = [("newmultipartupload", r, -1)], []
    for n, body in enumerate(parts, start=1):
        r = c.request("PUT", f"/b/{key}", body=body,
                      query={"partNumber": str(n), "uploadId": uid})
        etags.append(r.headers["ETag"])
        out.append(("putobjectpart", r, len(body)))
    xml = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>{e}</ETag></Part>"
        for n, e in enumerate(etags, start=1)) + "</CompleteMultipartUpload>"
    r = c.request("POST", f"/b/{key}", query={"uploadId": uid},
                  body=xml.encode())
    out.append(("completemultipartupload", r, sum(map(len, parts))))
    return out


@pytest.mark.parametrize("size", [1000, 200 * KIB, 5 * MIB + 77])
def test_a_requests_record_names_the_size_of_its_object(served, size):
    """``bytes`` is what the request consumed and sent (~0 for a STAT and
    a DELETE); ``object_bytes`` is the object's size for all four, a part's
    for a part PUT, the whole object's for a Complete, -1 where there is
    no object (a Create, a GET of a key that is gone)."""
    os.environ.pop("MINIO_TPU_TIMELINE", None)
    timeline.configure()
    _, c = served
    key, body = f"o-{size}", body_of(3, size)
    if size > 5 * MIB:
        calls = _upload(c, key, [body[:5 * MIB], body[5 * MIB:]])
    else:
        calls = [("putobject", c.put_object("b", key, body=body), size)]
    calls += [("getobject", c.get_object("b", key), size),
              ("headobject", c.head_object("b", key), size),
              ("deleteobject", c.delete_object("b", key), size),
              ("getobject", c.get_object("b", key), -1),
              ("deleteobject", c.delete_object("b", key), -1)]
    for api, resp, want in calls:
        assert resp.status_code in (200, 204, 404), resp.text
        rec = _record(api, resp.headers["x-amz-request-id"])
        assert rec["object_bytes"] == want, (api, rec)
        if api in ("headobject", "deleteobject"):
            assert rec["bytes"] < 1000
    assert calls[-2][1].status_code == 404


def test_a_unit_that_touches_no_object_says_minus_one():
    from minio_tpu.obs import stages
    attribution.reset()
    attribution.record("get", stages.StageTimes(), 0.001)
    u = attribution.begin("rid", "headobject")
    stages.touched(4096)
    attribution.finish(u, status=200)
    recs = attribution.between(0.0, float("inf"))
    assert [r["object_bytes"] for r in recs] == [-1, 4096]
    attribution.reset()


# --- the reference fails what is not its size's ---------------------------------------

@pytest.mark.parametrize("size,as_if", [
    (100 * KIB, "files"), (300 * KIB, "parts"), (11 * MIB, "one_part")])
def test_the_reference_fails_an_object_whose_layout_is_not_its_sizes(
        geo, monkeypatch, size, as_if):
    """Sound bytes in the wrong layout: every other count stays 0 and
    ``layout_wrong`` alone says so."""
    from minio_tpu.objectlayer import erasure_objects as eo
    ol, dirs, parity, geom = geo
    small = {**geom, "multipart_part_bytes": 5 * MIB,
             "multipart_min_bytes": 5 * MIB + 1}
    body = body_of(9, size)
    if as_if == "files":        # shard files under the inline threshold
        monkeypatch.setattr(eo, "SMALL_FILE_THRESHOLD", 0)
        ol.put_object("b", "o", io.BytesIO(body), size)
    elif as_if == "parts":      # an upload of parts where one PUT is due
        uid = ol.new_multipart_upload("b", "o")
        cut = size // 2
        ps = [ol.put_object_part("b", "o", uid, n, io.BytesIO(b), len(b))
              for n, b in ((1, body[:cut]), (2, body[cut:]))]
        # (S3's floor for a part that is not the last is 5 MiB)
        monkeypatch.setattr("minio_tpu.objectlayer.multipart.MIN_PART_SIZE",
                            0)
        ol.complete_multipart_upload(
            "b", "o", uid, [CompletePart(n, p.etag)
                            for n, p in enumerate(ps, start=1)])
    else:                       # one PUT where parts are due
        ol.put_object("b", "o", io.BytesIO(body), size)
    got = sizes_ref.check(obj_dirs(dirs, "o"), small, size,
                          hashlib.sha256(body).hexdigest())
    assert got["layout_wrong"] == 1, got
    assert (got["body_mismatch"], got["digest_bad"],
            got["parity_mismatch"]) == (0, 0, 0), got["why"]
    assert ol.get_object_bytes("b", "o") == body


def test_the_reference_says_what_each_size_has_to_be():
    g = SIZES
    assert [sizes_ref.route_of(n, g) for n in (
        1, 131072, 131073, 16 * MIB, 16 * MIB + 1, 64 * MIB)] == [
        "inline", "inline", "file", "file", "multipart", "multipart"]
    assert sizes_ref.part_sizes(16 * MIB, g) == [16 * MIB]
    assert sizes_ref.part_sizes(16 * MIB + 1, g) == [16 * MIB, 1]
    assert sizes_ref.part_sizes(64 * MIB - 1, g) == [16 * MIB] * 3 + [
        16 * MIB - 1]
    assert sizes_ref.layout_of(4096, g) == ("inline", [4096])
    # (k+m)/k of the size, 1/512 of that in digests, 4 KiB a drive a part
    assert sizes_ref.at_rest_limit(8 * MIB, g) == \
        12 * MIB + 12 * MIB // 512 + 12 * 4096
    assert sizes_ref.at_rest_limit(32 * MIB + 8, g) == \
        48 * MIB + 12 + (48 * MIB + 12) // 512 + 12 * 4096 * 3
    assert sizes_ref.at_rest_limit(4096, g) == 6144 + 12 * 4096
