"""Multipart uploads under SSE over real HTTP, held to the plain reference
(``minio_tpu/crypto/sse_ref.py``, docs/sse.md "Multipart objects"), for both
package ciphers: round trips under SSE-S3, SSE-KMS and SSE-C, what the
drives hold at rest, ranges across packages and parts, tampering that only
the AEAD can see, wrong keys, degraded reads, sizes in HEAD and LIST."""
import base64
import glob
import hashlib
import os
import re
import shutil
import sys

import numpy as np
import pytest

pytest.importorskip("cryptography")     # SSE-S3 seals data keys with AES-GCM

sys.path.insert(0, os.path.dirname(__file__))
from s3client import S3Client  # noqa: E402

from minio_tpu.crypto import kms as kms_mod  # noqa: E402
from minio_tpu.crypto import sse as sse_mod  # noqa: E402
from minio_tpu.crypto import sse_ref  # noqa: E402
from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.objectlayer.datatypes import SSEDecryptError  # noqa: E402
from minio_tpu.obs import metrics as mx  # noqa: E402
from minio_tpu.obs import stages  # noqa: E402
from minio_tpu.server import S3Server  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402
from minio_tpu.utils.hashreader import etag_from_parts  # noqa: E402

AK, SK = "mpsseak", "mpssesk"
MASTER = bytes.fromhex("5a" * 32)
KEY = bytes(range(32))
BUCKET = "mpsse"
MIB = 1 << 20
PKG = sse_mod.PKG_SIZE
#: unequal parts; the last is shorter than a package
SIZES = (5 * MIB + 70001, 5 * MIB, 1000)
STARTS = (0, SIZES[0], SIZES[0] + SIZES[1])
SSE_S3 = {"x-amz-server-side-encryption": "AES256"}
SSE_KMS = {"x-amz-server-side-encryption": "aws:kms"}


def ssec(key: bytes = KEY) -> dict:
    return {
        "x-amz-server-side-encryption-customer-algorithm": "AES256",
        "x-amz-server-side-encryption-customer-key":
            base64.b64encode(key).decode(),
        "x-amz-server-side-encryption-customer-key-md5":
            base64.b64encode(hashlib.md5(key).digest()).decode()}


def body_of(seed: int, size: int) -> bytes:
    return np.random.default_rng([26, seed]).bytes(size)


def parts_of(seed: int, sizes=SIZES) -> list[bytes]:
    return [body_of(seed * 100 + i, n) for i, n in enumerate(sizes)]


class Env:
    """One server, one cipher: the drives' directories, a client and the
    objects the module's tests share."""

    def __init__(self, cipher, server, dirs):
        self.cipher, self.server, self.dirs = cipher, server, dirs
        self.c = S3Client(server.endpoint(), AK, SK)
        self.etags: dict[str, list[str]] = {}

    def create(self, key, headers) -> str:
        r = self.c.request("POST", f"/{BUCKET}/{key}", query={"uploads": ""},
                           headers=headers)
        assert r.status_code == 200, r.text
        self.create_headers = r.headers
        return re.search(r"<UploadId>([^<]+)</UploadId>", r.text).group(1)

    def part(self, key, uid, n, body, headers=None):
        return self.c.request(
            "PUT", f"/{BUCKET}/{key}",
            query={"partNumber": str(n), "uploadId": uid}, body=body,
            headers=headers)

    def complete(self, key, uid, etags: dict[int, str]):
        xml = "<CompleteMultipartUpload>" + "".join(
            f"<Part><PartNumber>{n}</PartNumber><ETag>\"{e}\"</ETag></Part>"
            for n, e in sorted(etags.items())) + "</CompleteMultipartUpload>"
        return self.c.request("POST", f"/{BUCKET}/{key}",
                              query={"uploadId": uid}, body=xml.encode())

    def upload(self, key, parts, create_headers, part_headers=None,
               numbers=None) -> list[str]:
        uid = self.create(key, create_headers)
        etags = {}
        for n, body in zip(numbers or range(1, len(parts) + 1), parts):
            r = self.part(key, uid, n, body, part_headers)
            assert r.status_code == 200, r.text
            etags[n] = r.headers["ETag"].strip('"')
        r = self.complete(key, uid, etags)
        assert r.status_code == 200, r.text
        self.etags[key] = [etags[n] for n in sorted(etags)]
        return self.etags[key]

    def object_files(self, key) -> list[str]:
        return sorted(glob.glob(os.path.join(
            self.dirs[0][:-2] + "*", BUCKET, key, "*", "part.*")))


@pytest.fixture(scope="module", params=["aes-gcm", "chacha20"])
def env(request, tmp_path_factory):
    os.environ["MINIO_TPU_SSE_CIPHER"] = request.param
    os.environ["MINIO_TPU_SSE_DEVICE"] = "off"   # numpy lane, same bytes
    old_kms = kms_mod._kms
    kms_mod.set_kms(kms_mod.LocalKMS(MASTER))
    tmp = tmp_path_factory.mktemp("mpsse")
    dirs = [str(tmp / f"d{i}") for i in range(6)]
    obj = ErasureObjects([XLStorage(d) for d in dirs], default_parity=2)
    server = S3Server(obj, "127.0.0.1", 0, access_key=AK, secret_key=SK)
    server.start_background()
    e = Env(request.param, server, dirs)
    assert e.c.request("PUT", f"/{BUCKET}").status_code == 200
    # the shared objects: part 2 of "s3" is uploaded twice, out of order
    uid = e.create("s3", SSE_S3)
    assert e.create_headers["x-amz-server-side-encryption"] == "AES256"
    bodies, etags = parts_of(1), {}
    first = e.part("s3", uid, 2, body_of(999, SIZES[1]))
    e.first_etag_of_part_2 = first.headers["ETag"].strip('"')
    for n in (3, 1, 2):
        r = e.part("s3", uid, n, bodies[n - 1])
        assert r.status_code == 200, r.text
        etags[n] = r.headers["ETag"].strip('"')
    assert e.complete("s3", uid, etags).status_code == 200
    e.etags["s3"] = [etags[n] for n in (1, 2, 3)]
    e.upload("c", parts_of(2), ssec(), ssec())
    yield e
    server.shutdown()
    kms_mod._kms = old_kms
    os.environ.pop("MINIO_TPU_SSE_CIPHER", None)
    os.environ.pop("MINIO_TPU_SSE_DEVICE", None)


WANT_CIPHER = {"aes-gcm": sse_ref.AESGCM_NAME, "chacha20": sse_ref.CHACHA_NAME}


def fetch(env, key, headers=None):
    """(status, bytes received): a response the server cuts short counts
    with what arrived before the cut."""
    import requests
    r = env.c.request("GET", f"/{BUCKET}/{key}", headers=headers,
                      stream=True)
    got = bytearray()
    try:
        for piece in r.iter_content(1 << 16):
            got += piece
    except requests.exceptions.RequestException:
        pass
    return r.status_code, bytes(got)


def test_sse_s3_round_trip(env):
    body = b"".join(parts_of(1))
    r = env.c.request("GET", f"/{BUCKET}/s3")
    assert r.status_code == 200 and r.content == body
    assert int(r.headers["Content-Length"]) == len(body)
    assert r.headers["x-amz-server-side-encryption"] == "AES256"
    assert r.headers["ETag"].strip('"') == etag_from_parts(env.etags["s3"])
    assert r.headers["ETag"].strip('"').endswith("-3")
    assert env.first_etag_of_part_2 not in env.etags["s3"]   # re-uploaded


def test_sse_c_round_trip(env):
    body = b"".join(parts_of(2))
    r = env.c.request("GET", f"/{BUCKET}/c", headers=ssec())
    assert r.status_code == 200 and r.content == body
    assert r.headers[
        "x-amz-server-side-encryption-customer-algorithm"] == "AES256"
    assert r.headers["ETag"].strip('"') == etag_from_parts(env.etags["c"])


def test_sse_kms_round_trip_with_gapped_part_numbers(env):
    """Parts 2, 5 and 9 become parts 1..3 of the object; their keys still
    derive from the numbers they were uploaded under."""
    bodies = parts_of(3)
    env.upload("kms", bodies, SSE_KMS, numbers=(2, 5, 9))
    r = env.c.request("GET", f"/{BUCKET}/kms")
    assert r.status_code == 200 and r.content == b"".join(bodies)
    assert r.headers["x-amz-server-side-encryption"] == "aws:kms"
    version, stored, _ = sse_ref.stored_object(env.dirs, BUCKET, "kms")
    assert [p["m"]["sse-part"] for p in version["parts"]] == ["2", "5", "9"]
    oek = sse_ref.unseal_oek(version["meta"], BUCKET, "kms",
                             master_key=MASTER)
    streams = sse_ref.streams_of(version["meta"], version["parts"], oek)
    assert sse_ref.open_object(WANT_CIPHER[env.cipher], streams,
                               b"".join(stored)) == b"".join(bodies)


def windows(body: bytes, stride: int = 65521) -> list[bytes]:
    return [body[i:i + 64] for i in range(0, len(body) - 64, stride)]


@pytest.mark.parametrize("key,seed", [("s3", 1), ("c", 2)])
def test_at_rest_decrypts_under_the_reference(env, key, seed):
    """The shard files of the drives, reassembled, open under the plain
    reference to the bodies, and hold no 64-byte run of them."""
    bodies = parts_of(seed)
    version, stored, files = sse_ref.stored_object(env.dirs, BUCKET, key)
    meta = version["meta"]
    assert meta[sse_mod.META_CIPHER] == WANT_CIPHER[env.cipher]
    assert meta[sse_mod.META_MULTIPART] == "1"
    assert [p["as"] for p in version["parts"]] == list(SIZES)
    assert [p["s"] for p in version["parts"]] == \
        [sse_ref.enc_size(n) for n in SIZES]
    oek = sse_ref.unseal_oek(meta, BUCKET, key, master_key=MASTER,
                             client_key=KEY)
    streams = sse_ref.streams_of(meta, version["parts"], oek)
    for s, body, ct in zip(streams, bodies, stored):
        assert sse_ref.open_stream(meta[sse_mod.META_CIPHER], s, ct) == body
    assert len({(s.key, s.iv[:8]) for s in streams}) == len(streams)
    assert len(files) == 6 * len(SIZES)
    for blob in files.values():
        for w in windows(b"".join(bodies)):
            assert w not in blob
    with pytest.raises(sse_ref.BadTag):     # another master key opens nothing
        sse_ref.unseal_oek(meta, BUCKET, key, master_key=bytes(32),
                           client_key=bytes(32))


def test_a_part_uploaded_twice_never_repeats_a_nonce(env):
    """Same part number, same key: the IV is new for every request, so the
    same body seals to other bytes (the part's ETag is of the stored
    stream)."""
    uid = env.create("twice", SSE_S3)
    body = body_of(7, 70000)
    etags = {env.part("twice", uid, 1, body).headers["ETag"]
             for _ in range(3)}
    assert len(etags) == 3
    upath = glob.glob(os.path.join(env.dirs[0], ".minio.sys", "multipart",
                                   "*", uid))[0]
    import msgpack
    with open(os.path.join(upath, "part.1.meta"), "rb") as f:
        side = msgpack.unpackb(f.read(), raw=False)
    assert side["actual_size"] == 70000
    assert side["size"] == sse_ref.enc_size(70000)
    assert side["meta"][sse_mod.PART_NUMBER] == "1"
    assert len(base64.b64decode(side["meta"][sse_mod.PART_IV])) == 12
    r = env.c.request("GET", f"/{BUCKET}/twice", query={"uploadId": uid})
    assert "<Size>70000</Size>" in r.text, r.text   # ListParts: plaintext


def test_create_under_sse_leaves_no_plaintext_part(env):
    """Before complete: the staged part files are ciphertext already."""
    uid = env.create("staged", SSE_S3)
    body = body_of(8, 5 * MIB)
    assert env.part("staged", uid, 1, body).status_code == 200
    staged = glob.glob(os.path.join(env.dirs[0][:-2] + "*", ".minio.sys",
                                    "multipart", "*", uid, "part.1"))
    assert len(staged) == 6
    for path in staged:
        with open(path, "rb") as f:
            blob = f.read()
        for w in windows(body):
            assert w not in blob


E1, E2 = STARTS[1], STARTS[2]
RANGES = {
    "inside_one_package": (1000, 1999),
    "package_edges": (PKG, 2 * PKG - 1),
    "across_a_package_edge": (PKG - 1, PKG),
    "starts_and_ends_inside_packages": (3 * PKG + 17, 9 * PKG + 5),
    "last_byte_of_part_1": (E1 - 1, E1 - 1),
    "first_byte_of_part_2": (E1, E1),
    "across_the_first_part_edge": (E1 - 3, E1 + 3),
    "whole_part_2": (E1, E2 - 1),
    "across_three_parts": (E1 - 70000, E2 + 10),
    "inside_the_short_last_part": (E2 + 10, E2 + 20),
    "to_the_end": (E2 - PKG - 1, sum(SIZES) - 1),
    "everything": (0, sum(SIZES) - 1),
}


@pytest.mark.parametrize("name", sorted(RANGES))
def test_ranged_get(env, name):
    lo, hi = RANGES[name]
    body = b"".join(parts_of(1))
    r = env.c.request("GET", f"/{BUCKET}/s3",
                      headers={"Range": f"bytes={lo}-{hi}"})
    assert r.status_code == 206, r.text
    assert r.content == body[lo:hi + 1]
    assert r.headers["Content-Range"] == f"bytes {lo}-{hi}/{len(body)}"


def test_suffix_range_of_an_sse_c_object(env):
    body = b"".join(parts_of(2))
    r = env.c.request("GET", f"/{BUCKET}/c",
                      headers={**ssec(), "Range": "bytes=-1500"})
    assert r.status_code == 206 and r.content == body[-1500:]


def test_range_plan_equals_the_reference(env):
    """``plan_range`` against ``sse_ref.map_range`` on seeded ranges: the
    same stored span, the same packages, the same skips."""
    rng = np.random.default_rng([26, 5])
    plains = [3 * PKG + 5, PKG, 0, 17, 2 * PKG]
    streams = tuple(sse_mod.PartStream(bytes([i]) * 32, bytes(12), n)
                    for i, n in enumerate(plains))
    total = sum(plains)
    for _ in range(300):
        lo = int(rng.integers(0, total))
        ln = int(rng.integers(1, total - lo + 1))
        off, enc_len, segs = sse_mod.plan_range(streams, lo, ln)
        pieces = sse_ref.map_range(plains, lo, ln)
        assert off == pieces[0].stored_off
        assert enc_len == sum(p.stored_len for p in pieces)
        assert [(s.key[0], s.seq0, s.skip, s.limit, s.stored)
                for s in segs] == \
            [(p.part, p.pkg0, p.skip, p.take, p.stored_len) for p in pieces]


def _flip_under_the_bitrot_frame(path: str, chunk: int, at: int) -> None:
    """Flip one stored byte of a shard file and recompute the digest of
    its bitrot chunk, so that the frame verifies and only the AEAD can see
    the change."""
    from minio_tpu.erasure.bitrot import DEFAULT_BITROT_ALGO
    with open(path, "rb") as f:
        blob = bytearray(f.read())
    step = 32 + chunk
    frame = (at // chunk) * step
    blob[frame + 32 + at % chunk] ^= 0x01
    h = DEFAULT_BITROT_ALGO.new()
    h.update(bytes(blob[frame + 32:frame + step]))
    blob[frame:frame + 32] = h.digest()
    with open(path, "wb") as f:
        f.write(blob)


def test_flipped_ciphertext_byte_fails_the_get(env):
    bodies = parts_of(4)
    env.upload("flip", bodies, SSE_S3)
    version, _, files = sse_ref.stored_object(env.dirs, BUCKET, "flip")
    chunk = int(version["meta"]["x-minio-internal-bitrot-chunk"])
    # data shard 1 of part 2, inside the part's second package
    path = next(p for p in files if p.endswith("part.2") and
                sse_ref.read_xl_meta(os.path.join(
                    os.path.dirname(os.path.dirname(p)),
                    "xl.meta"))["ec"]["i"] == 1)
    _flip_under_the_bitrot_frame(path, chunk, PKG + 4000)
    body = b"".join(bodies)
    status, got = fetch(env, "flip")
    assert len(got) < len(body) and got == body[:len(got)]
    assert len(got) <= STARTS[1]        # nothing of the part's first flush
    # a range that needs the package fails, one that does not is served
    lo = STARTS[1] + PKG + 10
    status, got = fetch(env, "flip", {"Range": f"bytes={lo}-{lo + 99}"})
    assert got == b""
    r = env.c.request("GET", f"/{BUCKET}/flip",
                      headers={"Range": f"bytes=100-{PKG}"})
    assert r.status_code == 206 and r.content == body[100:PKG + 1]


def test_swapped_parts_fail_the_get(env):
    """Parts 1 and 2 have one size; their shard files change places on
    every drive. Frames and sizes still fit: only the part keys differ."""
    bodies = parts_of(5, (5 * MIB, 5 * MIB, 777))
    env.upload("swap", bodies, SSE_S3)
    assert env.c.request("GET", f"/{BUCKET}/swap").content == b"".join(bodies)
    files = env.object_files("swap")
    assert len(files) == 18
    for p1 in (f for f in files if f.endswith("part.1")):
        p2 = p1[:-1] + "2"
        os.rename(p1, p1 + ".x")
        os.rename(p2, p1)
        os.rename(p1 + ".x", p2)
    status, got = fetch(env, "swap")
    assert got == b""
    lo = 5 * MIB + 5
    status, got = fetch(env, "swap", {"Range": f"bytes={lo}-{lo + 9}"})
    assert got == b""
    r = env.c.request("GET", f"/{BUCKET}/swap",
                      headers={"Range": f"bytes={10 * MIB}-{10 * MIB + 9}"})
    assert r.content == bodies[2][:10]      # part 3 is where it was


def test_sse_c_wrong_or_missing_key_is_refused(env):
    uid = env.create("ckeys", ssec())
    body = body_of(9, 70000)
    assert env.part("ckeys", uid, 1, body).status_code == 400    # no key
    r = env.part("ckeys", uid, 1, body, ssec(bytes(reversed(KEY))))
    assert r.status_code == 403, r.text
    r = env.part("ckeys", uid, 1, body, ssec())
    assert r.status_code == 200
    assert env.complete("ckeys", uid, {
        1: r.headers["ETag"].strip('"')}).status_code == 200
    assert env.c.request("GET", f"/{BUCKET}/ckeys").status_code == 400
    r = env.c.request("GET", f"/{BUCKET}/ckeys",
                      headers=ssec(bytes(reversed(KEY))))
    assert r.status_code == 403 and body[:64] not in r.content
    assert env.c.request("HEAD", f"/{BUCKET}/ckeys").status_code == 400
    assert env.c.request("GET", f"/{BUCKET}/ckeys",
                         headers=ssec()).content == body
    # SSE-C headers on a part of an upload that has no SSE-C: refused
    uid = env.create("plainup", None)
    assert env.part("plainup", uid, 1, body, ssec()).status_code == 400


def test_degraded_get_decrypts_bit_exact(env):
    bodies = parts_of(6)
    env.upload("degraded", bodies, SSE_S3)
    for d in env.dirs[1:3]:             # ``parity`` drives' shards gone
        shutil.rmtree(os.path.join(d, BUCKET, "degraded"))
    r = env.c.request("GET", f"/{BUCKET}/degraded")
    assert r.status_code == 200 and r.content == b"".join(bodies)
    lo, hi = STARTS[1] - 10, STARTS[2] + 10
    r = env.c.request("GET", f"/{BUCKET}/degraded",
                      headers={"Range": f"bytes={lo}-{hi}"})
    assert r.content == b"".join(bodies)[lo:hi + 1]


def test_head_and_list_give_plaintext_sizes(env):
    total = sum(SIZES)
    r = env.c.request("HEAD", f"/{BUCKET}/s3")
    assert r.status_code == 200
    assert int(r.headers["Content-Length"]) == total
    assert r.headers["x-amz-server-side-encryption"] == "AES256"
    r = env.c.request("HEAD", f"/{BUCKET}/c", headers=ssec())
    assert int(r.headers["Content-Length"]) == total
    for query in ({"prefix": "s3"}, {"list-type": "2", "prefix": "s3"}):
        r = env.c.request("GET", f"/{BUCKET}", query=query)
        assert f"<Size>{total}</Size>" in r.text, r.text
    stored = env.server.obj.get_object_info(BUCKET, "s3").size
    assert stored == sum(sse_ref.enc_size(n) for n in SIZES) != total


def test_plaintext_multipart_is_as_before(env):
    bodies = parts_of(10, (5 * MIB, 1234))
    env.upload("plain", bodies, None)
    r = env.c.request("GET", f"/{BUCKET}/plain")
    assert r.content == b"".join(bodies)
    assert "x-amz-server-side-encryption" not in r.headers
    version, stored, _ = sse_ref.stored_object(env.dirs, BUCKET, "plain")
    assert b"".join(stored) == b"".join(bodies)
    assert all("m" not in p for p in version["parts"])
    assert sse_mod.META_SCHEME not in version["meta"]


def test_upload_part_copy_answers_501_and_stores_nothing(env):
    uid = env.create("copytarget", None)
    r = env.part("copytarget", uid, 1, b"",
                 {"x-amz-copy-source": f"/{BUCKET}/s3"})
    assert r.status_code == 501 and "NotImplemented" in r.text
    r = env.c.request("GET", f"/{BUCKET}/copytarget",
                      query={"uploadId": uid})
    assert r.status_code == 200 and "<PartNumber>" not in r.text


# --- the open side works a block at a time (docs/sse.md "The open side") -----

UNIT = PKG + sse_mod.TAG
#: parts whose edges fall inside packages and blocks; the middle one is
#: shorter than a package
SMALL = (PKG + 5, 17, PKG)
CIPHERS = ["aes-gcm", "chacha20"]


@pytest.fixture
def host_lane(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "off")   # numpy, same bytes


def sealed_by_the_reference(cipher: str, sizes, seed: int):
    """(streams, stored bytes, plaintext) of an object of ``sizes`` parts,
    every package sealed by ``sse_ref``."""
    name = WANT_CIPHER[cipher]
    bodies = parts_of(seed, sizes)
    streams = [sse_ref.Stream(bytes([40 + i]) * 32, bytes([i]) * 12, n)
               for i, n in enumerate(sizes)]
    stored = b"".join(
        sse_ref.aead_seal(name, s.key, s.iv[:8] + seq.to_bytes(4, "big"),
                          body[seq * PKG:(seq + 1) * PKG],
                          sse_ref.AAD + seq.to_bytes(4, "big"))
        for s, body in zip(streams, bodies)
        for seq in range(-(-len(body) // PKG)))
    return streams, stored, b"".join(bodies)


class RecordingSink:
    """Keeps a copy of every write it is handed (the writer sends a view
    of a buffer it uses again)."""

    def __init__(self):
        self.writes: list[bytes] = []

    def write(self, b):
        self.writes.append(bytes(b))
        return len(b)


def open_through(cipher, streams, stored, lo, ln, size, sink):
    """What a GET of plaintext [lo, lo + ln) does to the writer: the stored
    span ``plan_range`` names arrives cut where the object's ``size``-byte
    blocks end, each piece in a pooled buffer that is overwritten as soon
    as ``write`` returns. Returns the number of writes handed over."""
    segs = tuple(sse_mod.PartStream(*s) for s in streams)
    off, enc_len, plan = sse_mod.plan_range(segs, lo, ln)
    dw = sse_mod.RangeDecryptWriter(sink, plan, WANT_CIPHER[cipher], "b",
                                    "o")
    pooled, handed, at = bytearray(size), 0, off
    while at < off + enc_len:
        stop = min(off + enc_len, (at // size + 1) * size)
        pooled[:stop - at] = stored[at:stop]
        dw.write(memoryview(pooled)[:stop - at])
        pooled[:] = b"\xa5" * size
        handed += 1
        at = stop
    dw.finish()
    return handed


@pytest.mark.parametrize("sizes,size", [
    (SMALL, 1), (SMALL, PKG + 15), (SIZES, PKG + 15), (SIZES, UNIT),
    (SIZES, 4 * MIB), (SIZES, sse_ref.enc_size(SIZES[0]))],
    ids=["1B", "small-64KiB+15", "64KiB+15", "one-package", "4MiB",
         "a-whole-part"])
@pytest.mark.parametrize("cipher", CIPHERS)
def test_open_hands_the_sink_one_write_a_block(host_lane, cipher, sizes,
                                               size):
    streams, stored, body = sealed_by_the_reference(cipher, sizes, 27)
    sink = RecordingSink()
    handed = open_through(cipher, streams, stored, 0, len(body), size, sink)
    assert b"".join(sink.writes) == body == sse_ref.read_range(
        WANT_CIPHER[cipher], streams, stored, 0, len(body))
    assert len(sink.writes) <= handed + 1
    assert all(sink.writes)         # no empty write reaches the sink


SPANS = {
    "inside_one_package": (1000, 999),
    "starts_and_ends_inside_packages": (3 * PKG + 17, 6 * PKG - 12),
    "across_a_block_edge": (4 * MIB - 70000, 140001),
    "across_the_first_part_edge": (E1 - PKG - 3, 2 * PKG + 9),
    "across_three_parts": (E1 - 70000, SIZES[1] + 70010),
    "the_last_byte": (sum(SIZES) - 1, 1),
}


@pytest.mark.parametrize("name", sorted(SPANS))
@pytest.mark.parametrize("cipher", CIPHERS)
def test_open_of_a_range_equals_the_reference(host_lane, cipher, name):
    lo, ln = SPANS[name]
    streams, stored, body = sealed_by_the_reference(cipher, SIZES, 28)
    for size in (4 * MIB, 3 * UNIT + 7):
        sink = RecordingSink()
        handed = open_through(cipher, streams, stored, lo, ln, size, sink)
        assert b"".join(sink.writes) == body[lo:lo + ln] \
            == sse_ref.read_range(WANT_CIPHER[cipher], streams, stored, lo,
                                  ln)
        assert len(sink.writes) <= handed + 1


@pytest.mark.parametrize("cipher", CIPHERS)
def test_single_stream_writer_keeps_no_view_of_its_input(host_lane, cipher):
    """``DecryptWriter`` by itself (a single-PUT object): the caller's
    buffer is overwritten after every ``write``; the straddling package
    must have been copied, everything else opened before the return."""
    (s,), stored, body = sealed_by_the_reference(cipher, (40 * PKG + 77,),
                                                 29)
    sink = RecordingSink()
    dw = sse_mod.DecryptWriter(sink, s.key, s.iv, 0, 5, len(body) - 9, "b",
                               "o", cipher=WANT_CIPHER[cipher])
    size = 17 * UNIT + 1000     # one lane call of 16 and one of 1, a carry
    pooled, handed = bytearray(size), 0
    for at in range(0, len(stored), size):
        piece = stored[at:at + size]
        pooled[:len(piece)] = piece
        dw.write(memoryview(pooled)[:len(piece)])
        pooled[:] = bytes(size)
        handed += 1
    dw.finish()
    assert b"".join(sink.writes) == body[5:-4]
    assert len(sink.writes) <= handed + 1


@pytest.mark.parametrize("cipher", CIPHERS)
def test_bad_tag_in_a_blocks_last_package_releases_nothing_of_it(
        host_lane, cipher):
    """The release rule is a block's: 40 packages arrive in one write, the
    flipped byte sits in the last of them (the third lane call); the 39
    that verified before it stay unsent."""
    streams, stored, body = sealed_by_the_reference(cipher, (100 * PKG,),
                                                    30)
    size = 40 * UNIT
    bad = bytearray(stored)
    bad[2 * size - 20] ^= 1         # the second block's package 39
    sink = RecordingSink()
    with pytest.raises(SSEDecryptError) as e:
        open_through(cipher, streams, bad, 0, len(body), size, sink)
    assert (e.value.bucket, e.value.object) == ("b", "o")
    assert sink.writes == [body[:40 * PKG]]


class CountingBody:
    """A request body that says how it was read."""

    def __init__(self, body: bytes):
        import io
        self.raw, self.reads = io.BytesIO(body), []

    def read(self, n: int = -1) -> bytes:
        self.reads.append(n)
        return self.raw.read(n)


@pytest.mark.parametrize("cipher", CIPHERS)
def test_seal_reads_a_flush_of_body_at_a_time(host_lane, cipher):
    """``EncryptReader`` asks its source for 16 packages (1 MiB) in one
    read, not for a package a read, and what it seals is what the
    reference seals package by package."""
    (s,), stored, body = sealed_by_the_reference(cipher, (3 * MIB + 70001,),
                                                 31)
    src = CountingBody(body)
    er = sse_mod.EncryptReader(src, s.key, s.iv, WANT_CIPHER[cipher])
    out = bytearray(len(stored) + 100)
    assert er.readinto(out) == len(stored) and bytes(out[:len(stored)]) \
        == stored
    assert src.reads[0] == sse_mod.FLUSH_PKGS * PKG
    assert len(src.reads) <= 2 * (len(body) // MIB + 1)


def counter(prefix: str, **labels) -> float:
    return sum(v for k, v in mx.counters_snapshot().items()
               if k.startswith(prefix)
               and all(f'{a}="{b}"' in k for a, b in labels.items()))


def test_counters_and_stages(env):
    short = env.cipher
    before = {
        "parts": counter("minio_tpu_multipart_parts_total", sse="S3"),
        "completes": counter("minio_tpu_multipart_completes_total",
                             sse="S3"),
        "seal_s": counter("minio_tpu_workloads_sse_seconds_total",
                          cipher=short, op="seal"),
        "open_s": counter("minio_tpu_workloads_sse_seconds_total",
                          cipher=short, op="open"),
        "seal_b": counter("minio_tpu_workloads_sse_bytes_total",
                          cipher=short, op="seal")}
    bodies = parts_of(11, (5 * MIB, 100))
    env.upload("counted", bodies, SSE_S3)
    opened = {"writes": counter("minio_tpu_workloads_sse_sink_writes_total",
                                op="open"),
              "bytes": counter("minio_tpu_workloads_sse_bytes_total",
                               op="open")}
    assert env.c.request("GET", f"/{BUCKET}/counted").status_code == 200
    # a whole-object GET hands the sink a block at a time, not a package
    writes = counter("minio_tpu_workloads_sse_sink_writes_total",
                     op="open") - opened["writes"]
    mib = (counter("minio_tpu_workloads_sse_bytes_total", op="open")
           - opened["bytes"]) / MIB
    assert mib > 5 and 0 < writes / mib < 1
    assert counter("minio_tpu_multipart_parts_total", sse="S3") \
        == before["parts"] + 2
    assert counter("minio_tpu_multipart_completes_total", sse="S3") \
        == before["completes"] + 1
    assert counter("minio_tpu_workloads_sse_seconds_total", cipher=short,
                   op="seal") > before["seal_s"]
    assert counter("minio_tpu_workloads_sse_seconds_total", cipher=short,
                   op="open") > before["open_s"]
    assert counter("minio_tpu_workloads_sse_bytes_total", cipher=short,
                   op="seal") == before["seal_b"] + 5 * MIB + 100
    # the stages, where a collector is armed around the object layer
    import io
    with stages.collect() as st:
        er = sse_mod.EncryptReader(io.BytesIO(bodies[1]), bytes(32),
                                   bytes(12), WANT_CIPHER[env.cipher])
        sealed = er.read()
        dw = sse_mod.DecryptWriter(io.BytesIO(), bytes(32), bytes(12), 0, 0,
                                   -1, cipher=WANT_CIPHER[env.cipher])
        dw.write(sealed)
        dw.finish()
    assert st.seconds["sse_seal"] > 0 and st.seconds["sse_open"] > 0


def test_pure_python_chacha_equals_the_wheel(monkeypatch):
    """The reference's fallback for hosts without ``cryptography`` is the
    same function (RFC 8439): seal with one, open with the other."""
    key, nonce, aad = bytes(range(32)), bytes(range(12)), b"aad-26"
    data = body_of(12, 1000)
    with_wheel = sse_ref.aead_seal(sse_ref.CHACHA_NAME, key, nonce, data,
                                   aad)
    monkeypatch.setattr(sse_ref, "HAVE_CRYPTOGRAPHY", False)
    assert sse_ref.aead_seal(sse_ref.CHACHA_NAME, key, nonce, data,
                             aad) == with_wheel
    assert sse_ref.aead_open(sse_ref.CHACHA_NAME, key, nonce, with_wheel,
                             aad) == data
    with pytest.raises(sse_ref.BadTag):
        sse_ref.aead_open(sse_ref.CHACHA_NAME, key, nonce,
                          with_wheel[:-1] + bytes([with_wheel[-1] ^ 1]), aad)


def test_reference_imports_nothing_of_the_code_under_test():
    import ast
    with open(sse_ref.__file__) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert node.level == 0, "relative import in the reference"
            names.add(node.module.split(".")[0])
        elif isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
    assert names <= {"__future__", "base64", "hashlib", "hmac", "os",
                     "struct", "typing", "cryptography", "msgpack"}
