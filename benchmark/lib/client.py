#!/usr/bin/env python
"""One load-generating client process of the benchmark.

Started by the run process (``lib/procs.py``) as ``python client.py``; reads
one JSON command per line on stdin and answers with one JSON line on stdout.
It imports neither JAX nor the program: it signs SigV4 itself, speaks HTTP
to 127.0.0.1, makes every body from a seed, hashes every body it sends and
receives (SHA-256), and times every operation with ``time.monotonic`` (one
clock for all processes of a machine).

A ``run`` command carries one plan per thread; plans run concurrently and
the reply holds each thread's records in order. The measured window's plans
come under ``prepare`` (bodies made and hashed, ``ready`` answered) and
start at the ``go`` that fixes their times. Plan types:

``ops``   a fixed list of operations, run one after another at once.
``loop``  a closed loop from ``t_start`` (at once, where the plan has none)
          to ``t_end``: operations drawn from
          shuffled copies of ``deck`` over the thread's own live keys; a PUT
          writes a new key with the next of the thread's ``bodies``, which
          were made and hashed before ``t_start``.
``heal``  from ``t_start``: start an admin heal sequence of the bucket, poll
          it; when it ends before ``t_end`` empty the next drive and start
          the next sequence (a drive once emptied always gets its sequence).
          At ``t_end`` the counters are read once more.

Operations: MKBUCKET, PUT, GET, STAT, DELETE, EMPTY (remove an object's or a
whole bucket's files from one drive directory), HEALWAIT (poll a sequence to
its end). A record is a dict: op, key, t0, t1, status, and what the
operation read (n, sha, etag) or sent (size, sha, etag_ref).
"""
from __future__ import annotations

import hashlib
import http.client
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import hh_ref  # noqa: E402
import sigv4  # noqa: E402

PIECE = 1 << 20
EMPTY_SHA = hashlib.sha256(b"").hexdigest()


def make_body(spec: list[int], size: int) -> bytes:
    """The body named by ``spec`` ([seed, stream, index]): same spec, same
    bytes, in any process."""
    return np.random.default_rng(spec).bytes(size)


class S3:
    """One keep-alive connection, owned by one thread."""

    def __init__(self, cfg: dict):
        self.host = cfg["endpoint"].split("//", 1)[1]
        self.ak, self.sk = cfg["ak"], cfg["sk"]
        self.conn = None
        self.buf = memoryview(bytearray(PIECE))

    def call(self, method, path, query=None, body=None, admin=False):
        """One request. Returns (status, headers, n, sha256hex, head): the
        body is read and hashed piece by piece as it arrives; ``head`` is
        its first 64 KiB (error documents, admin JSON)."""
        target, headers = sigv4.sign(
            method, self.host, path, query or {}, self.ak, self.sk,
            EMPTY_SHA if admin else sigv4.UNSIGNED_PAYLOAD)
        if body is not None:
            headers["content-length"] = str(len(body))
        for attempt in (0, 1):
            try:
                if self.conn is None:
                    # a request that gets no answer is a failed record
                    # within the run's time, not a run that never ends
                    self.conn = http.client.HTTPConnection(self.host,
                                                           timeout=120)
                self.conn.request(method, target, body=body, headers=headers)
                r = self.conn.getresponse()
                break
            except (http.client.HTTPException, OSError):
                # a kept-alive connection the server closed: once anew
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

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def etag_of(headers: dict) -> str:
    return (headers.get("ETag") or headers.get("etag") or "").strip('"')


def empty(path: str) -> None:
    """Remove what ``path`` holds on a drive: one object's directory, or
    every object of a bucket directory (the bucket itself stays)."""
    def rm(p):
        # a background heal may be writing into the tree: once more after
        # it lands, then the error stands
        try:
            shutil.rmtree(p)
        except FileNotFoundError:
            pass
        except OSError:
            time.sleep(0.5)
            shutil.rmtree(p, ignore_errors=True)
    if path.endswith("/*"):
        base = path[:-2]
        for name in os.listdir(base):
            rm(os.path.join(base, name))
    else:
        rm(path)


def do_op(s3: S3, op: dict) -> dict:
    kind, bucket, key = op["op"], op.get("bucket", ""), op.get("key", "")
    rec = {"op": kind, "key": key}
    body = None
    if kind == "PUT":
        body = op["_body"]
        rec.update(size=len(body), body=op["body"], sha=op["_sha"],
                   etag_ref=op["_etag_ref"])
    rec["t0"] = time.monotonic()
    try:
        if kind == "EMPTY":
            for p in op["paths"]:
                empty(p)
            rec["status"] = 0
        elif kind == "HEALWAIT":
            rec.update(heal_wait(s3, bucket, op["token"], op["timeout_s"]))
        else:
            method, path = {
                "MKBUCKET": ("PUT", f"/{bucket}"),
                "PUT": ("PUT", f"/{bucket}/{key}"),
                "GET": ("GET", f"/{bucket}/{key}"),
                "STAT": ("HEAD", f"/{bucket}/{key}"),
                "DELETE": ("DELETE", f"/{bucket}/{key}")}[kind]
            status, headers, n, sha, head = s3.call(method, path, body=body)
            rec["status"] = status
            if kind == "GET":
                rec.update(n=n, sha=sha, etag=etag_of(headers))
            elif kind == "STAT":
                rec.update(n=int(headers.get("Content-Length", -1)),
                           etag=etag_of(headers))
            elif kind == "PUT":
                rec["etag"] = etag_of(headers)
            if status >= 300:
                rec["err"] = head[:200].decode("utf-8", "replace")
    except Exception as e:  # noqa: BLE001 — a failed operation is a record
        s3.close()
        rec.update(status=-1, err=f"{type(e).__name__}: {e}")
    rec["t1"] = time.monotonic()
    return rec


def prepare(cfg: dict, plans: list[dict]) -> None:
    """Make, hash and reference every body the plans will PUT, before any
    thread starts and in this one thread: numpy work spread over threads
    convoys on the interpreter lock (seen on the v5e host: 20 threads took
    over 30 s for what one takes 4 s)."""
    puts = []
    for plan in plans:
        if plan["type"] == "ops":
            puts += [o for o in plan["ops"] if o["op"] == "PUT"]
        elif plan["type"] == "loop":
            plan["_puts"] = [{"op": "PUT", "bucket": plan["bucket"],
                              "body": b, "size": plan["size"]}
                             for b in plan.get("bodies", [])]
            puts += plan["_puts"]
    for o in puts:
        o["_body"] = make_body(o["body"], o["size"])
        o["_sha"] = hashlib.sha256(o["_body"]).hexdigest()
    refs = hh_ref.reference_etags([o["_body"] for o in puts],
                                  cfg["geometry"])
    for o, ref in zip(puts, refs):
        o["_etag_ref"] = ref


def run_ops(cfg: dict, plan: dict) -> list[dict]:
    s3 = S3(cfg)
    out = [do_op(s3, o) for o in plan["ops"]]
    s3.close()
    return out


def run_loop(cfg: dict, plan: dict) -> list[dict]:
    """Closed loop: the next request goes out when the last has been read
    to its end. A loop that could not start by ``t_start`` says so in a
    record that fails the run; a plan without ``t_start`` (a warm-up, which
    nothing measures) starts at once."""
    s3 = S3(cfg)
    rng = np.random.default_rng(plan["rng"])
    bucket, live = plan["bucket"], list(plan["keys"])
    deck, puts = list(plan["deck"]), plan["_puts"]
    min_live = plan.get("min_live", 2)
    out, n_put = [], 0
    if "t_start" in plan:
        late = time.monotonic() - plan["t_start"]
        if late > 0:
            return [{"op": "PLAN", "key": "loop", "status": -1, "t0": 0.0,
                     "t1": 0.0,
                     "err": f"started {late:.3f} s after t_start"}]
        time.sleep(-late)
    while True:
        for i in rng.permutation(len(deck)):
            if time.monotonic() >= plan["t_end"]:
                s3.close()
                return out
            kind = deck[i]
            if kind == "DELETE" and len(live) <= min_live:
                kind = "PUT"      # never run the thread's keys dry
            if kind == "PUT":
                key = f"{plan['new_prefix']}-{n_put:05d}"
                rec = do_op(s3, dict(puts[n_put % len(puts)], key=key))
                n_put += 1
                if rec["status"] == 200:
                    live.append(key)
            else:
                at = int(rng.integers(len(live)))
                key = live[at]
                rec = do_op(s3, {"op": kind, "bucket": bucket, "key": key})
                if kind == "DELETE" and rec["status"] in (200, 204):
                    live.pop(at)
            out.append(rec)


def heal_call(s3: S3, bucket: str, token: str = "") -> dict:
    status, _, _, _, head = s3.call(
        "POST", f"/minio/admin/v3/heal/{bucket}",
        {"clientToken": token} if token else None, admin=True)
    if status != 200:
        raise RuntimeError(f"heal {bucket}: {status} {head[:200]!r}")
    seq = json.loads(head)
    seq.pop("items", None)
    return seq


def heal_wait(s3: S3, bucket: str, token: str, timeout_s: float) -> dict:
    deadline = time.monotonic() + timeout_s
    seq = heal_call(s3, bucket, token)
    while seq["status"] == "running" and time.monotonic() < deadline:
        time.sleep(0.25)
        seq = heal_call(s3, bucket, token)
    return {"status": 0, "seq": seq}


def restored(bucket_dir: str) -> int:
    """Objects whose ``xl.meta`` is on this drive: what has been rebuilt
    there since it was emptied, by whichever healer."""
    return sum(os.path.exists(os.path.join(bucket_dir, name, "xl.meta"))
               for name in os.listdir(bucket_dir))


def run_heal(cfg: dict, plan: dict) -> list[dict]:
    """Keep one drive being rebuilt from ``t_start`` to ``t_end``. One
    record per sequence (``seq`` is the server's own summary: scanned,
    healed, failed, status; ``restored`` counts the objects back on the
    drive); the one still running at ``t_end`` is read there and left
    running, its token in the record.

    Whether there is time for another drive is decided once, before that
    drive is emptied. A drive that has been emptied always gets its
    sequence and its record, whatever the clock says by then: when this
    returns, the last drive it emptied is the ``drive`` of its last record
    (a sequence started past ``t_end`` is read at once, ``running``, and
    whoever reads the records waits it out; its ``restored`` is what stood
    on the drive before it started)."""
    s3 = S3(cfg)
    bucket, drives = plan["bucket"], plan["drive_dirs"]
    drive, out = plan["first_drive"], []
    time.sleep(max(0.0, plan["t_start"] - time.monotonic()))
    while True:
        rec = {"op": "HEAL", "key": f"drive-{drive}", "drive": drive,
               "t0": time.monotonic(), "status": 0}
        on_drive = os.path.join(drives[drive], bucket)
        try:
            if rec["t0"] >= plan["t_end"]:
                # the window closed while the drive was being emptied: what
                # is back on it is counted now, not after the call that
                # starts its sequence (1-2 s under load)
                rec["restored"] = restored(on_drive)
            seq = heal_call(s3, bucket)
            while seq["status"] == "running" and \
                    time.monotonic() < plan["t_end"]:
                time.sleep(plan.get("poll_s", 0.1))
                seq = heal_call(s3, bucket, seq["clientToken"])
            rec["seq"] = seq
            if "restored" not in rec:
                rec["restored"] = restored(on_drive)
        except Exception as e:  # noqa: BLE001
            rec.update(status=-1, err=f"{type(e).__name__}: {e}")
        rec["t1"] = time.monotonic()
        out.append(rec)
        if rec["status"] or rec["seq"]["status"] != "done" \
                or time.monotonic() >= plan["t_end"]:
            break
        drive = (drive + 1) % len(drives)
        empty(os.path.join(drives[drive], bucket) + "/*")
    s3.close()
    return out


RUNNERS = {"ops": run_ops, "loop": run_loop, "heal": run_heal}


def run_plans(cfg: dict, plans: list[dict]) -> list:
    """Prepared plans, one thread each, concurrently; each thread's
    records in the plans' order."""
    results: list = [None] * len(plans)

    def work(i, plan):
        try:
            results[i] = RUNNERS[plan["type"]](cfg, plan)
        except Exception as e:  # noqa: BLE001 — reported, not lost
            results[i] = [{"op": "PLAN", "key": plan["type"],
                           "status": -1, "t0": 0.0, "t1": 0.0,
                           "err": f"{type(e).__name__}: {e}"}]
    ts = [threading.Thread(target=work, args=(i, p))
          for i, p in enumerate(plans)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return results


def main() -> int:
    """``run`` prepares its plans and runs them at once. ``prepare`` only
    prepares and answers ``ready``; the ``go`` that follows carries the
    ``t_start`` from which those plans' ``t_start`` and ``t_end`` count: the
    window's times are fixed once every process is ready, so however long
    the bodies took, no loop starts late."""
    cfg: dict = {}
    held: list[dict] = []
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "init":
            cfg = cmd["cfg"]
            reply = {"ok": True, "pid": os.getpid()}
        elif cmd["cmd"] == "run":
            prepare(cfg, cmd["threads"])
            reply = {"ok": True, "threads": run_plans(cfg, cmd["threads"])}
        elif cmd["cmd"] == "prepare":
            held = cmd["threads"]
            prepare(cfg, held)
            reply = {"ok": True, "ready": len(held)}
        elif cmd["cmd"] == "go":
            for plan in held:
                for at in ("t_start", "t_end"):
                    if at in plan:
                        plan[at] += cmd["t_start"]
            reply = {"ok": True, "threads": run_plans(cfg, held)}
            held = []
        elif cmd["cmd"] == "quit":
            return 0
        else:
            reply = {"ok": False, "err": f"unknown command {cmd['cmd']!r}"}
        sys.stdout.write(json.dumps(reply) + "\n")
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
