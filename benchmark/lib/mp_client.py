#!/usr/bin/env python
"""The load-generating client of the multipart kinds: ``lib/client.py`` with
request headers, multipart calls and ranged GETs added. Same rules: no JAX,
nothing of the program, every body made from a seed, SHA-256 of every body
sent and received, one monotonic clock. Same protocol on stdin/stdout (it is
``client.main``), with these plan types beside ``ops`` and ``loop``:

``mp``           a fixed list of operations, run at once; one operation may
                 give several records. Operations: UPLOAD (a whole multipart
                 upload: Create, the parts in turn, Complete), RGET (a
                 ranged GET), STAGE (Create and one part, then what the
                 drives hold of the upload is searched for the part's
                 plaintext, then Abort), and everything ``client.do_op`` has.
``upload_loop``  a closed loop from ``t_start`` to ``t_end``: Create, the
                 parts one after another, Complete, then the next key of the
                 thread's ring (an overwrite once the ring is full); the
                 parts are the thread's ``bodies``, made and hashed before
                 ``t_start``.

Records: CREATE (status, ``sse``: what the response said of encryption), PUT
(one part: key, part, size, sha, etag), COMPLETE (key, etag, ``parts``: the
part ETags the server returned, size and sha of the whole object), RGET (lo,
hi, n, sha, ``sha_ref``: SHA-256 of the same slice of the bodies), ABORT,
STAGE (``found``: 64-byte runs of the part found on the drives)."""
from __future__ import annotations

import glob
import hashlib
import http.client
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import client  # noqa: E402
import sigv4  # noqa: E402
from client import etag_of, make_body  # noqa: E402


class S3(client.S3):
    """``client.S3`` whose calls may carry request headers (unsigned: the
    signature covers host, date and payload hash, as there)."""

    def call(self, method, path, query=None, body=None, admin=False,
             headers=None):
        target, hdrs = sigv4.sign(
            method, self.host, path, query or {}, self.ak, self.sk,
            client.EMPTY_SHA if admin else sigv4.UNSIGNED_PAYLOAD)
        hdrs.update(headers or {})
        if body is not None:
            hdrs["content-length"] = str(len(body))
        for attempt in (0, 1):
            try:
                if self.conn is None:
                    self.conn = http.client.HTTPConnection(self.host,
                                                           timeout=120)
                self.conn.request(method, target, body=body, headers=hdrs)
                r = self.conn.getresponse()
                break
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
        sha, n, head = hashlib.sha256(), 0, b""
        while True:
            got = r.readinto(self.buf)
            if not got:
                break
            sha.update(self.buf[:got])
            if len(head) < 65536:
                head += bytes(self.buf[: min(got, 65536 - len(head))])
            n += got
        if r.will_close:
            self.close()
        return r.status, dict(r.getheaders()), n, sha.hexdigest(), head


def timed(rec: dict, call) -> tuple:
    """Run one request into ``rec``: t0, t1, status, err."""
    rec["t0"] = time.monotonic()
    try:
        out = call()
        rec["status"] = out[0]
        if out[0] >= 300:
            rec["err"] = out[4][:200].decode("utf-8", "replace")
    except Exception as e:  # noqa: BLE001 — a failed operation is a record
        out = None
        rec.update(status=-1, err=f"{type(e).__name__}: {e}")
    rec["t1"] = time.monotonic()
    return out


def upload(s3: S3, bucket: str, key: str, parts: list[dict],
           sse: str | None, stop_at: float | None = None) -> list[dict]:
    """One multipart upload. ``parts`` are {"_body", "_sha", "body"} as
    ``ready`` leaves them. Past ``stop_at`` the upload is given up between
    two requests (Abort) and never becomes visible."""
    out = []
    rec = {"op": "CREATE", "key": key}
    res = timed(rec, lambda: s3.call(
        "POST", f"/{bucket}/{key}", {"uploads": ""},
        headers={"x-amz-server-side-encryption": sse} if sse else None))
    out.append(rec)
    if rec["status"] != 200:
        s3.close()
        return out
    rec["sse"] = res[1].get("x-amz-server-side-encryption", "")
    uid = re.search(rb"<UploadId>([^<]+)</UploadId>", res[4]).group(1).decode()
    etags, whole = [], hashlib.sha256()
    for n, p in enumerate(parts, start=1):
        if stop_at is not None and time.monotonic() >= stop_at:
            break
        rec = {"op": "PUT", "key": key, "part": n, "size": len(p["_body"]),
               "sha": p["_sha"], "body": p["body"]}
        res = timed(rec, lambda: s3.call(
            "PUT", f"/{bucket}/{key}",
            {"partNumber": str(n), "uploadId": uid}, body=p["_body"]))
        out.append(rec)
        if rec["status"] != 200:
            s3.close()
            break
        rec["etag"] = etag_of(res[1])
        etags.append(rec["etag"])
        whole.update(p["_body"])
    if len(etags) < len(parts):
        rec = {"op": "ABORT", "key": key}
        timed(rec, lambda: s3.call("DELETE", f"/{bucket}/{key}",
                                   {"uploadId": uid}))
        out.append(rec)
        return out
    xml = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>\"{e}\"</ETag></Part>"
        for n, e in enumerate(etags, start=1)) + "</CompleteMultipartUpload>"
    rec = {"op": "COMPLETE", "key": key, "parts": etags,
           "size": sum(len(p["_body"]) for p in parts),
           "sha": whole.hexdigest()}
    res = timed(rec, lambda: s3.call("POST", f"/{bucket}/{key}",
                                     {"uploadId": uid}, body=xml.encode()))
    if rec["status"] == 200:
        m = re.search(rb"<ETag>(?:&quot;|&#34;|\")?([0-9a-f-]+)", res[4])
        rec["etag"] = m.group(1).decode() if m else ""
    out.append(rec)
    return out


def ready(specs: list, size: int, cache: dict | None = None) -> list[dict]:
    """The part bodies named by ``specs``, made and hashed (each spec once;
    ``cache`` carries them from one call to the next)."""
    cache = {} if cache is None else cache
    out = []
    for spec in specs:
        key = (tuple(spec), size)
        if key not in cache:
            body = make_body(spec, size)
            cache[key] = {"body": spec, "_body": body,
                          "_sha": hashlib.sha256(body).hexdigest()}
        out.append(cache[key])
    return out


def ranged_get(s3: S3, op: dict) -> dict:
    """GET of the plaintext range [lo, hi] of an object whose parts are
    ``parts`` (specs) of ``part_bytes`` each; the slice it must equal is
    made here from the seeds."""
    lo, hi, size = op["lo"], op["hi"], op["part_bytes"]
    ref = hashlib.sha256()
    for i in range(lo // size, hi // size + 1):
        body = make_body(op["parts"][i], size)
        ref.update(body[max(lo - i * size, 0): hi + 1 - i * size])
    rec = {"op": "RGET", "key": op["key"], "lo": lo, "hi": hi,
           "sha_ref": ref.hexdigest()}
    res = timed(rec, lambda: s3.call(
        "GET", f"/{op['bucket']}/{op['key']}",
        headers={"range": f"bytes={lo}-{hi}"}))
    if res is not None:
        rec.update(n=res[2], sha=res[3],
                   content_range=res[1].get("Content-Range", ""))
    return rec


def windows(body: bytes, count: int) -> list[bytes]:
    """``count`` 64-byte runs of ``body``, evenly spread."""
    stride = max(64, (len(body) - 64) // count)
    return [body[i:i + 64] for i in range(0, len(body) - 64, stride)][:count]


def stage(s3: S3, op: dict) -> list[dict]:
    """Create under SSE, one part, then look at what the drives hold of
    the upload before it is completed: no 64-byte run of the part may be
    there. Then Abort."""
    bucket, key = op["bucket"], op["key"]
    part = ready([op["part"]], op["part_bytes"])[0]
    out = []
    rec = {"op": "STAGE", "key": key, "found": -1}
    res = timed(rec, lambda: s3.call(
        "POST", f"/{bucket}/{key}", {"uploads": ""},
        headers={"x-amz-server-side-encryption": op["sse"]}))
    out.append(rec)
    if rec["status"] != 200:
        return out
    uid = re.search(rb"<UploadId>([^<]+)</UploadId>", res[4]).group(1).decode()
    status = s3.call("PUT", f"/{bucket}/{key}",
                     {"partNumber": "1", "uploadId": uid},
                     body=part["_body"])[0]
    staged = [p for d in op["drive_dirs"] for p in glob.glob(os.path.join(
        d, ".minio.sys", "multipart", "*", uid, "part.1"))]
    rec["files"] = len(staged)
    if status == 200 and staged:
        rec["found"] = 0
        for path in staged:
            with open(path, "rb") as f:
                blob = f.read()
            rec["found"] += sum(w in blob
                                for w in windows(part["_body"], 8))
    s3.call("DELETE", f"/{bucket}/{key}", {"uploadId": uid})
    return out


def run_mp(cfg: dict, plan: dict) -> list[dict]:
    s3, out = S3(cfg), []
    for op in plan["ops"]:
        if op["op"] == "UPLOAD":
            out += upload(s3, op["bucket"], op["key"],
                          ready(op["parts"], op["part_bytes"],
                                plan.setdefault("_cache", {})
                                if op.get("reuse") else None), op["sse"])
        elif op["op"] == "RGET":
            out.append(ranged_get(s3, op))
        elif op["op"] == "STAGE":
            out += stage(s3, op)
        else:
            out.append(client.do_op(s3, op))
    s3.close()
    return out


def run_upload_loop(cfg: dict, plan: dict) -> list[dict]:
    s3 = S3(cfg)
    late = time.monotonic() - plan["t_start"]
    if late > 0:
        return [{"op": "PLAN", "key": "upload_loop", "status": -1, "t0": 0.0,
                 "t1": 0.0, "err": f"started {late:.3f} s after t_start"}]
    time.sleep(-late)
    out, i = [], 0
    while time.monotonic() < plan["t_end"]:
        key = f"{plan['prefix']}-{i % plan['ring']:03d}"
        out += upload(s3, plan["bucket"], key, plan["_parts"], plan["sse"],
                      stop_at=plan["t_end"])
        i += 1
    s3.close()
    return out


def prepare(cfg: dict, plans: list[dict]) -> None:
    """``client.prepare`` for its own plan types; the upload loops' bodies
    are made and hashed here, before any thread starts."""
    theirs = [p for p in plans if p["type"] in ("ops", "loop")]
    if theirs:
        _client_prepare(cfg, theirs)
    for plan in plans:
        if plan["type"] == "upload_loop":
            plan["_parts"] = ready(plan["bodies"], plan["part_bytes"])


_client_prepare = client.prepare
client.prepare = prepare
client.RUNNERS.update(mp=run_mp, upload_loop=run_upload_loop)

if __name__ == "__main__":
    sys.exit(client.main())
