#!/usr/bin/env python
"""The load-generating client of kind ``mixed_sizes``: ``lib/mp_client.py``
(and so ``lib/client.py``) with a size for every key and every PUT. Same
rules: no JAX, nothing of the program, every body made from a seed, SHA-256
of every body sent and received, one monotonic clock, the same protocol on
stdin/stdout. Plan types beside theirs:

``sized``       a fixed list of operations, run at once. SPUT is a PUT of
                ``size`` bytes made from ``body`` ([seed, stream, index]):
                up to ``multipart_part_bytes`` of the geometry one PUT
                request, over it a multipart upload as minio-go sends it
                (Create, ``ceil(size / part)`` parts of ``part`` bytes, the
                last one short, Complete; no SSE header). Everything
                ``client.do_op`` has runs as there; an operation that names
                a ``size`` has it in its record.
``sizes_loop``  ``client.py``'s ``loop`` over keys that each have a size
                (``keys``: [[key, size], ...]): a closed loop from
                ``t_start`` to ``t_end``, operations drawn from shuffled
                copies of ``deck``, each key uniform over the thread's own
                live keys (as ``loop`` draws); a PUT writes a new key with the next of the thread's
                ``bodies`` ([{"body": spec, "size": n}, ...], made, hashed
                and referenced before ``t_start``), taken in an order the
                plan's ``rng`` shuffles, anew each time all have been sent.

Records: as ``client.py``'s, each with the ``size`` of the object it
touched. A multipart SPUT is ONE record of op ``PUT`` (``t0`` Create sent,
``t1`` Complete's reply read, ``status`` Complete's 200 or that of the
request that failed, ``mp`` the number of parts, ``etag`` what Complete
answered, ``etag_ref`` the fold of the part ETags the server returned),
after one sub-record ``PART`` a part (part, size, t0, t1, status, etag,
``etag_ref``: the host reference's ETag of the part's bytes)."""
from __future__ import annotations

import hashlib
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import client  # noqa: E402
import mp_client  # noqa: E402  (puts its plan types into client's)
import sizes_ref  # noqa: E402
from mp_client import S3, etag_of, timed  # noqa: E402
from mp_model import fold  # noqa: E402

MINE = ("sized", "sizes_loop")


def ready(cfg: dict, puts: list[dict]) -> None:
    """Make, hash and reference every body of ``puts`` (SPUT operations),
    in this one thread: ``_body``, ``_sha`` and, of a body sent whole,
    ``_etag_ref``; of one sent in parts, ``_parts`` [(lo, hi, the part's
    reference ETag)]."""
    geom = cfg["geometry"]
    pieces, owners = [], []
    for o in puts:
        o["_body"] = client.make_body(o["body"], o["size"])
        o["_sha"] = hashlib.sha256(o["_body"]).hexdigest()
        view, lo = memoryview(o["_body"]), 0
        o["_parts"] = []
        for n in sizes_ref.part_sizes(o["size"], geom):
            pieces.append(view[lo: lo + n])
            owners.append((o, lo, lo + n))
            lo += n
    for (o, lo, hi), ref in zip(owners,
                                sizes_ref.reference_etags(pieces, geom)):
        o["_parts"].append((lo, hi, ref))
    for o in puts:
        if len(o["_parts"]) == 1:
            o["_etag_ref"] = o.pop("_parts")[0][2]


def sput(s3: S3, op: dict) -> list[dict]:
    """One sized PUT: its record, after its parts' where it went up in
    parts."""
    bucket, key = op["bucket"], op["key"]
    if "_parts" not in op:
        rec = client.do_op(s3, dict(op, op="PUT"))
        return [rec]
    out = []
    rec = {"op": "PUT", "key": key, "size": op["size"], "body": op["body"],
           "sha": op["_sha"], "mp": len(op["_parts"]), "etag": "",
           "etag_ref": "", "t0": time.monotonic()}

    def failed(step: dict) -> list[dict]:
        rec.update(status=step["status"], err=step.get("err", ""),
                   t1=time.monotonic())
        s3.close()
        return out + [rec]
    step = {}
    res = timed(step, lambda: s3.call("POST", f"/{bucket}/{key}",
                                      {"uploads": ""}))
    if step["status"] != 200:
        return failed(step)
    uid = re.search(rb"<UploadId>([^<]+)</UploadId>", res[4]).group(1).decode()
    view, etags = memoryview(op["_body"]), []
    for n, (lo, hi, ref) in enumerate(op["_parts"], start=1):
        part = {"op": "PART", "key": key, "part": n, "size": hi - lo,
                "etag_ref": ref}
        res = timed(part, lambda: s3.call(
            "PUT", f"/{bucket}/{key}",
            {"partNumber": str(n), "uploadId": uid}, body=view[lo:hi]))
        out.append(part)
        if part["status"] != 200:
            try:
                s3.call("DELETE", f"/{bucket}/{key}", {"uploadId": uid})
            except Exception:  # noqa: BLE001 — the part's failure stands
                pass
            return failed(part)
        part["etag"] = etag_of(res[1])
        etags.append(part["etag"])
    xml = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>\"{e}\"</ETag></Part>"
        for n, e in enumerate(etags, start=1)) + "</CompleteMultipartUpload>"
    step = {}
    res = timed(step, lambda: s3.call("POST", f"/{bucket}/{key}",
                                      {"uploadId": uid}, body=xml.encode()))
    if step["status"] != 200:
        return failed(step)
    m = re.search(rb"<ETag>(?:&quot;|&#34;|\")?([0-9a-f-]+)", res[4])
    rec.update(status=200, t1=step["t1"], etag_ref=fold(etags),
               etag=m.group(1).decode() if m else "")
    return out + [rec]


def do(s3: S3, op: dict) -> list[dict]:
    if op["op"] == "SPUT":
        return sput(s3, op)
    rec = client.do_op(s3, op)
    if "size" in op:
        rec["size"] = op["size"]
    return [rec]


def run_sized(cfg: dict, plan: dict) -> list[dict]:
    s3, out = S3(cfg), []
    for op in plan["ops"]:
        out += do(s3, op)
    s3.close()
    return out


def run_sizes_loop(cfg: dict, plan: dict) -> list[dict]:
    """``client.run_loop`` with a size a key: the same deck and the same
    draw, uniform over the thread's live keys. An upload under way at
    ``t_end`` runs to its Complete, as any request under way runs to its
    reply."""
    s3 = S3(cfg)
    rng = np.random.default_rng(plan["rng"])
    bucket = plan["bucket"]
    live, sizes = [k for k, _ in plan["keys"]], dict(plan["keys"])
    deck, puts = list(plan["deck"]), plan["_puts"]
    min_live = plan.get("min_live", 2)
    late = time.monotonic() - plan["t_start"]
    if late > 0:
        return [{"op": "PLAN", "key": "sizes_loop", "status": -1, "t0": 0.0,
                 "t1": 0.0, "err": f"started {late:.3f} s after t_start"}]
    time.sleep(-late)
    out, n_put, order = [], 0, []
    while True:
        for i in rng.permutation(len(deck)):
            if time.monotonic() >= plan["t_end"]:
                s3.close()
                return out
            kind = deck[i]
            if kind == "DELETE" and len(live) <= min_live:
                kind = "PUT"      # never run the thread's keys dry
            if kind == "PUT":
                if not order:
                    order = [int(j) for j in rng.permutation(len(puts))]
                put = puts[order.pop()]
                key = f"{plan['new_prefix']}-{n_put:05d}"
                n_put += 1
                recs = sput(s3, dict(put, key=key))
                if recs[-1]["status"] == 200:
                    live.append(key)
                    sizes[key] = put["size"]
                out += recs
                continue
            at = int(rng.integers(len(live)))
            key = live[at]
            out += do(s3, {"op": kind, "bucket": bucket, "key": key,
                           "size": sizes[key]})
            if kind == "DELETE" and out[-1]["status"] in (200, 204):
                live.pop(at)


def prepare(cfg: dict, plans: list[dict]) -> None:
    """``mp_client.prepare`` for its plan types; the sized PUTs of this
    module's are made, hashed and referenced here."""
    theirs = [p for p in plans if p["type"] not in MINE]
    if theirs:
        _mp_prepare(cfg, theirs)
    puts = []
    for plan in plans:
        if plan["type"] == "sized":
            puts += [o for o in plan["ops"] if o["op"] == "SPUT"]
        elif plan["type"] == "sizes_loop":
            plan["_puts"] = [{"op": "SPUT", "bucket": plan["bucket"], **b}
                             for b in plan["bodies"]]
            puts += plan["_puts"]
    ready(cfg, puts)


_mp_prepare = client.prepare
client.prepare = prepare
client.RUNNERS.update(sized=run_sized, sizes_loop=run_sizes_loop)

if __name__ == "__main__":
    sys.exit(client.main())
