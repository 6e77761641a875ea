#!/usr/bin/env python
"""chip_smoke.py — the served S3 path, end to end, on one TPU chip, in one
process. The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: legs a-d through a live server
    python chip_smoke.py --chips 4  # four chips: mesh route + lanes only

Needs a TPU backend: on any other backend it says why and exits 2 without
running a leg. No option reaches the ok line off the chip; a CPU rehearsal
calls the leg functions below from a scratch script or a test with small
sizes passed in.

One-chip legs (each prints one ``LEG`` line: what it did, seconds, the
dispatch queue's stats() delta, salvage events, host hash fallbacks by
reason, the link profile, compile totals):

  a  as shipped (no routing env): PUT/GET/HEAD/LIST, two drives emptied,
     degraded GET, admin heal, two other drives emptied, GET. Correctness is
     asserted; where ``auto`` sent the work is REPORTED. The degraded GET's
     blocks take the one-call native route (``native_degraded``), none the
     queue.
  b  device route (MINIO_TPU_PUT_PATH=dispatch, MINIO_TPU_GET_PATH=dispatch,
     MINIO_TPU_DISPATCH_MODE=device): same sequence; asserts the chip did the work — every block
     through the queue, device flushes of PUT and rebuild ops, nothing on
     the CPU but counted QoS spills, zero salvages, probe ok, tail_block-
     only host hashing, ETags equal to leg a's — then reads everything
     back through the native C++ path.
  c  S3 Select scan (device vs the classic interpreter) and one ChaCha
     SSE-C object (device vs the host reference), each with device flushes
     of its own op and no salvage.
  d  16+4 / 1 MiB / 128 items straight through a DispatchQueue: encode,
     2-loss rebuild, fused verify+rebuild, encode_hashed vs host references.

Any failed check raises; nothing is caught to carry on. The last stdout line
of a passing run is ``{"ok": true, "device": {...}}`` and nothing else.
"""
from __future__ import annotations

import argparse
import base64
import hashlib
import io
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
AK, SK = "smokeadmin", "smokesecret1"
MIB = 1 << 20
ROUTE_ENV = ("MINIO_TPU_PUT_PATH", "MINIO_TPU_GET_PATH",
             "MINIO_TPU_DISPATCH_MODE")


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# --------------------------------------------------------------------------
# observation: everything a leg line reports, as one snapshot + delta


def observe() -> dict:
    from minio_tpu.obs import device as devobs
    from minio_tpu.obs import metrics as mx
    from minio_tpu.obs import timeline as tl
    from minio_tpu.runtime.dispatch import global_queue
    st = global_queue().stats()
    comp = devobs.compile_snapshot()
    fb = {}
    for key, v in mx.counters_snapshot().items():
        if key.startswith("minio_tpu_pipeline_host_fallback_total"):
            fb[key.split('reason="')[1].split('"')[0]] = int(v)
    return {
        "t": time.monotonic(),
        "device_items": st["device_items"], "cpu_items": st["cpu_items"],
        "device_batches": st["device_batches"],
        "spilled_items": st["spilled_items"],
        "spill_reasons": dict(st["spill_reasons"]),
        "interactive_items": st["interactive_lane"]["items"],
        "salvaged_items": dict(st["salvaged_items"]),
        "probe": st["probe"],
        "host_fallback": fb,
        "compiles": comp["compiles_total"],
        "compile_s": comp["compile_seconds_total"],
        "flushes": {op: r["flushes"]
                    for op, r in devobs.roofline_snapshot().items()},
        "tl_dropped": tl.dropped_total(),
    }


def get_block_routes() -> dict:
    """minio_tpu_pipeline_get_blocks_total by route: which path each GET
    block took (native_fd, native_degraded, native, fused, plain)."""
    from minio_tpu.obs import metrics as mx
    return {k.split('route="')[1].split('"')[0]: int(v)
            for k, v in mx.counters_snapshot().items()
            if k.startswith("minio_tpu_pipeline_get_blocks_total")}


def _ddict(a: dict, b: dict) -> dict:
    return {k: b.get(k, 0) - a.get(k, 0) for k in sorted(set(a) | set(b))
            if b.get(k, 0) != a.get(k, 0)}


def delta(before: dict, after: dict) -> dict:
    from minio_tpu.obs import timeline as tl
    salv = [e for e in tl.snapshot(since=before["t"])
            if e["type"] == "salvage"]
    out = {k: after[k] - before[k] for k in
           ("device_items", "cpu_items", "device_batches", "spilled_items",
            "interactive_items", "compiles")}
    out["compile_s"] = round(after["compile_s"] - before["compile_s"], 3)
    out["spill_reasons"] = _ddict(before["spill_reasons"],
                                  after["spill_reasons"])
    out["salvaged_items"] = _ddict(before["salvaged_items"],
                                   after["salvaged_items"])
    out["salvage_events"] = len(salv)
    out["host_fallback"] = _ddict(before["host_fallback"],
                                  after["host_fallback"])
    out["device_flushes"] = _ddict(before["flushes"], after["flushes"])
    out["timeline_dropped"] = after["tl_dropped"] - before["tl_dropped"]
    out["probe"] = {k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in after["probe"].items()}
    return out


def leg_line(name: str, did: dict, t0: float, d: dict) -> None:
    say(f"LEG {name} " + json.dumps(
        {"did": did, "seconds": round(time.monotonic() - t0, 2), **d},
        sort_keys=True))


def no_salvage(d: dict, leg: str) -> None:
    check(not d["salvaged_items"] and d["salvage_events"] == 0,
          f"leg {leg}: device work was salvaged on the CPU: "
          f"{d['salvaged_items']} ({d['salvage_events']} events)")


# --------------------------------------------------------------------------
# the server, as `python -m minio_tpu.server` builds it


class Served:
    """The CLI's own single-node launch (server/__main__.build_server:
    expand_endpoints -> pick_set_layout -> XLStorage -> ErasureObjects ->
    S3Server, background services on), serving on a thread."""

    def __init__(self, root: str, drives: int, parity: int):
        from minio_tpu.server import __main__ as cli
        os.environ["MINIO_TPU_ROOT_USER"] = AK
        os.environ["MINIO_TPU_ROOT_PASSWORD"] = SK
        self.dirs = [os.path.join(root, f"d{i:02d}") for i in range(drives)]
        args = cli.arg_parser().parse_args(
            [*self.dirs, "--address", "127.0.0.1:0",
             "--parity", str(parity)])
        self.srv, self.banner = cli.build_server(args)
        self.srv.start_background()
        self.obj = self.srv.obj
        self.k = drives - parity
        self.n = drives
        self._tls = threading.local()

    def client(self):
        c = getattr(self._tls, "c", None)
        if c is None:
            sys.path.insert(0, os.path.join(ROOT, "tests"))
            from s3client import S3Client
            c = self._tls.c = S3Client(self.srv.endpoint(), AK, SK)
        return c

    def admin(self):
        from minio_tpu.madmin import AdminClient
        return AdminClient(self.srv.endpoint(), AK, SK)

    def shutdown(self):
        self.srv.shutdown()


def make_bucket(sv: Served, bucket: str) -> None:
    r = sv.client().put_bucket(bucket)
    check(r.status_code == 200 or "BucketAlreadyOwnedByYou" in r.text,
          f"make bucket {bucket}: {r.status_code} {r.text[:200]}")


def make_bodies(seed: int, n_big: int, big: int, n_small: int,
                small: int) -> dict[str, bytes]:
    out = {}
    for i in range(n_big):
        out[f"big-{i:03d}"] = np.random.default_rng(
            [seed, 1, i]).integers(0, 256, big, dtype=np.uint8).tobytes()
    for i in range(n_small):
        out[f"small-{i:03d}"] = np.random.default_rng(
            [seed, 2, i]).integers(0, 256, small, dtype=np.uint8).tobytes()
    return out


def reference_etags(sv: Served, bodies: dict[str, bytes]) -> dict[str, str]:
    """Plain host ETag per body: the fused-pipeline reference for bodies at
    or over pipeline.etag_min_bytes (1 MiB), MD5 below it."""
    from minio_tpu.erasure.bitrot import native_algo_id, pick_bitrot_chunk
    from minio_tpu.erasure.codec import ceil_div
    from minio_tpu.utils.hashreader import pipeline_etag_reference
    chunk = pick_bitrot_chunk(ceil_div(sv.obj.block_size, sv.k))
    algo = native_algo_id(sv.obj.bitrot_algo)
    return {k: (pipeline_etag_reference(b, sv.k, sv.obj.block_size, chunk,
                                        algo)
                if len(b) >= MIB else hashlib.md5(b).hexdigest())
            for k, b in bodies.items()}


def n_blocks(sv: Served, size: int) -> int:
    return -(-size // sv.obj.block_size)


def par(clients: int, fn, items) -> list:
    with ThreadPoolExecutor(clients) as ex:
        return list(ex.map(fn, items))  # re-raises the first failure


def put_all(sv, bucket, bodies, etags, clients) -> None:
    def one(key):
        r = sv.client().put_object(bucket, key, bodies[key])
        check(r.status_code == 200, f"PUT {bucket}/{key}: {r.status_code} "
              f"{r.text[:200]}")
        got = r.headers["ETag"].strip('"')
        check(got == etags[key], f"PUT {bucket}/{key}: ETag {got} != host "
              f"reference {etags[key]}")
    par(clients, one, list(bodies))


def get_all(sv, bucket, bodies, etags, clients, what: str) -> None:
    def one(key):
        # stream + one raw read: requests' default 10 KiB content chunks
        # cost a GIL handoff each, and server and clients share this
        # process (seen on the v5e host: 64 MiB GETs at ~6 MB/s)
        r = sv.client().get_object(bucket, key, stream=True)
        got = r.raw.read()
        check(r.status_code == 200, f"{what} GET {bucket}/{key}: "
              f"{r.status_code} {got[:200]!r}")
        check(got == bodies[key],
              f"{what} GET {bucket}/{key}: body differs from what was PUT")
        check(r.headers["ETag"].strip('"') == etags[key],
              f"{what} GET {bucket}/{key}: ETag differs")
    par(clients, one, list(bodies))


def head_list(sv, bucket, bodies, etags, clients) -> None:
    def one(key):
        r = sv.client().head_object(bucket, key)
        check(r.status_code == 200
              and int(r.headers["Content-Length"]) == len(bodies[key])
              and r.headers["ETag"].strip('"') == etags[key],
              f"HEAD {bucket}/{key}: {r.status_code} {dict(r.headers)}")
    par(clients, one, list(bodies))
    r = sv.client().request("GET", f"/{bucket}", query={"list-type": "2"})
    check(r.status_code == 200, f"LIST {bucket}: {r.status_code}")
    listed = {s.split("</Key>")[0] for s in r.text.split("<Key>")[1:]}
    check(listed == set(bodies), f"LIST {bucket}: {len(listed)} keys, "
          f"expected {len(bodies)}")


def empty_drives(sv: Served, bucket: str, drives: tuple[int, ...],
                 bodies) -> dict:
    """Empty ``bucket``'s data on the given drives (every object's xl.meta
    and shard files there). Returns how the loss falls: placement is per
    object (hash_order), so an object may lose data shards (its GET
    reconstructs), parity only (its GET launches no kernel) or nothing."""
    from minio_tpu.objectlayer.metadata import hash_order
    for d in drives:
        bdir = os.path.join(sv.dirs[d], bucket)
        for name in os.listdir(bdir):
            # a background MRF heal may be writing into the tree: one
            # retry after it lands, then the error stands
            try:
                shutil.rmtree(os.path.join(bdir, name))
            except OSError:
                time.sleep(0.5)
                shutil.rmtree(os.path.join(bdir, name))
    lost = {"data": [], "parity_only": []}
    for key in bodies:
        dist = hash_order(f"{bucket}/{key}", sv.n)
        idx = [dist[d] for d in drives]
        (lost["data"] if any(i <= sv.k for i in idx)
         else lost["parity_only"]).append(key)
    return lost


def heal_bucket(sv: Served, bucket: str, bodies, timeout_s: float = 900.0):
    """Heal every object through the admin heal API (a heal sequence, token
    polled), then hold each object to 'all n shards present'."""
    adm = sv.admin()
    deadline = time.monotonic() + timeout_s
    seq = adm.heal(bucket)
    token = seq.get("clientToken", "")
    while token and seq.get("status") == "running":
        check(time.monotonic() < deadline, f"heal of {bucket} still "
              f"running after {timeout_s}s: {seq}")
        time.sleep(0.25)
        seq = adm.heal_status(token, bucket)
    seq.pop("items", None)
    check(seq.get("status") == "done" and seq.get("failed") == 0,
          f"heal sequence of {bucket} did not end clean: {seq}")
    missing = []
    for key in bodies:
        for d, base in enumerate(sv.dirs):
            if not os.path.exists(os.path.join(base, bucket, key,
                                               "xl.meta")):
                missing.append((key, d))
    check(not missing, f"heal left {len(missing)} shards missing, e.g. "
          f"{missing[:4]}; last status {seq}")


def drive_sequence(sv, bucket, bodies, etags, clients) -> dict:
    """PUT all, GET all, HEAD, LIST, empty drives 0+1, GET (degraded), heal
    through the admin API, empty drives 2+3, GET again (healed shards are
    among what is read). Returns what was done, in blocks."""
    phase_s = {}

    def phase(name, fn, *a):
        t = time.monotonic()
        out = fn(*a)
        phase_s[name] = round(time.monotonic() - t, 2)
        return out

    make_bucket(sv, bucket)
    phase("put", put_all, sv, bucket, bodies, etags, clients)
    phase("get", get_all, sv, bucket, bodies, etags, clients, "healthy")
    phase("head_list", head_list, sv, bucket, bodies, etags, clients)
    lost1 = empty_drives(sv, bucket, (0, 1), bodies)
    routes = get_block_routes()
    phase("get_degraded", get_all, sv, bucket, bodies, etags, clients,
          "degraded")
    routes = _ddict(routes, get_block_routes())
    phase("heal", heal_bucket, sv, bucket, bodies)
    lost2 = empty_drives(sv, bucket, (2, 3), bodies)
    phase("get_degraded_healed", get_all, sv, bucket, bodies, etags,
          clients, "post-heal degraded")

    def blocks(keys):
        return sum(n_blocks(sv, len(bodies[k])) for k in keys)
    return {
        "objects": len(bodies),
        "bytes": sum(len(b) for b in bodies.values()),
        "phase_seconds": phase_s,
        "get_degraded_block_routes": routes,
        "blocks_written": blocks(bodies),
        "blocks_rebuilt_get": blocks(lost1["data"]) + blocks(lost2["data"]),
        "blocks_rebuilt_heal": blocks(lost1["data"] + lost1["parity_only"]),
        "objects_lost_data": [len(lost1["data"]), len(lost2["data"])],
        "objects_lost_parity_only": [len(lost1["parity_only"]),
                                     len(lost2["parity_only"])],
    }


# --------------------------------------------------------------------------
# legs a-d


def leg_a(sv, bodies, etags, clients) -> None:
    check(not any(e in os.environ for e in ROUTE_ENV),
          f"leg a runs as shipped; unset {ROUTE_ENV}")
    t0, before = time.monotonic(), observe()
    did = drive_sequence(sv, "smoke-a", bodies, etags, clients)
    d = delta(before, observe())
    # correctness was asserted above; the routing is the finding
    leg_line("a", did, t0, d)
    # as shipped a degraded GET of local shard files is one native call a
    # block and never reaches the queue (leg b forces the queued route)
    routes = did["get_degraded_block_routes"]
    check(routes.get("native_degraded", 0) > 0 and not routes.get("fused"),
          f"leg a: degraded GET blocks by route {routes}: none took "
          "native_degraded, or some took fused")


def leg_b(sv, bodies, etags, clients) -> None:
    os.environ["MINIO_TPU_PUT_PATH"] = "dispatch"
    # the queued GET route, which sources without an fd (RPC) take by
    # themselves: degraded blocks ride fused verify+rebuild on the chip
    os.environ["MINIO_TPU_GET_PATH"] = "dispatch"
    os.environ["MINIO_TPU_DISPATCH_MODE"] = "device"
    try:
        # both snapshots sit INSIDE the env window (leg a's background
        # MRF heals are still flushing: one planned under `auto` must
        # not land in this leg's delta)
        time.sleep(0.2)
        t0, before = time.monotonic(), observe()
        did = drive_sequence(sv, "smoke-b", bodies, etags, clients)
        d = delta(before, observe())
    finally:
        for e in ROUTE_ENV:
            os.environ.pop(e, None)
    want = did["blocks_written"] + did["blocks_rebuilt_get"] + \
        did["blocks_rebuilt_heal"]
    # the issue's bound, reported: the QoS scheduler may spill items to
    # the CPU even in device mode (64 MiB queued-bytes cap, class
    # budgets) — product behaviour, printed with its reasons; what is
    # HELD is that every block reached the queue in device mode, that
    # nothing got to the CPU except as a counted spill, and that each op
    # family really launched on the chip
    did["device_items_ge_blocks"] = d["device_items"] >= want
    leg_line("b", did, t0, d)
    check(d["device_items"] + d["spilled_items"] >= want,
          f"leg b: {d['device_items']} device + {d['spilled_items']} "
          f"spilled items < {want} blocks written+rebuilt")
    check(d["cpu_items"] == d["spilled_items"],
          f"leg b: {d['cpu_items']} items ran on the CPU but only "
          f"{d['spilled_items']} are accounted spills")
    check(d["device_items"] > 0, "leg b: every item spilled: "
          f"{d['spill_reasons']}")
    fl = d["device_flushes"]
    check(fl.get("encode_hashed", 0) > 0, f"leg b: no device flush of "
          f"encode_hashed (PUT): {fl}")
    check(fl.get("fused", 0) + fl.get("reconstruct", 0) > 0,
          f"leg b: no device flush of a rebuild (GET/heal): {fl}")
    no_salvage(d, "b")
    check(d["probe"]["state"] == "ok", f"leg b: link probe {d['probe']}")
    odd = set(d["host_fallback"]) - {"tail_block"}
    check(not odd, f"leg b: host hash fallbacks for {sorted(odd)}: "
          f"{d['host_fallback']}")
    # the cross-check between the two implementations: with the routing
    # env gone every healthy read rides the native block path, whose C++
    # HighwayHash verifies every bitrot digest the device computed
    t1, before = time.monotonic(), observe()
    heal_bucket(sv, "smoke-b", bodies)
    get_all(sv, "smoke-b", bodies, etags, clients, "native read-back")
    d2 = delta(before, observe())
    leg_line("b.native_readback", {"objects": len(bodies)}, t1, d2)


def select_csv(seed: int, nbytes: int) -> tuple[bytes, bytes]:
    """(csv, expected Records) for the smoke's query: fixed-width rows
    ``id,val,name`` built in bulk; ``expected`` is the plain host answer
    (numpy) to SELECT id, name ... WHERE val > 9990."""
    row = 8 + 1 + 4 + 1 + 6 + 1
    n = nbytes // row + 1
    rng = np.random.default_rng([seed, 3])
    ids = 10_000_000 + np.arange(n, dtype=np.int64)
    vals = rng.integers(1000, 10_000, n, dtype=np.int64)
    names = rng.integers(97, 123, (n, 6), dtype=np.uint8)
    a = np.empty((n, row), np.uint8)
    for j in range(8):
        a[:, j] = 48 + (ids // 10 ** (7 - j)) % 10
    a[:, 8] = 44
    for j in range(4):
        a[:, 9 + j] = 48 + (vals // 10 ** (3 - j)) % 10
    a[:, 13] = 44
    a[:, 14:20] = names
    a[:, 20] = 10
    hit = a[vals > 9990]
    expected = np.concatenate([hit[:, :8], hit[:, 13:]], axis=1).tobytes()
    return b"id,val,name\n" + a.tobytes(), expected


SELECT_SQL = "SELECT id, name FROM S3Object WHERE val > 9990"
SELECT_XML = f"""<SelectObjectContentRequest>
 <Expression>{SELECT_SQL.replace('>', '&gt;')}</Expression>
 <ExpressionType>SQL</ExpressionType>
 <InputSerialization><CSV><FileHeaderInfo>USE</FileHeaderInfo></CSV>
 </InputSerialization>
 <OutputSerialization><CSV/></OutputSerialization>
</SelectObjectContentRequest>"""


def classic_select(csv: bytes) -> bytes:
    """The classic interpreter's Records for SELECT_SQL over ``csv``, run
    in-process with the device scan off — the reference leg c holds the
    served (device-scanned) answer to."""
    from minio_tpu.s3select import S3SelectRequest, run_select
    from minio_tpu.s3select.message import decode_messages
    check("MINIO_TPU_SCAN" not in os.environ, "MINIO_TPU_SCAN is set")
    os.environ["MINIO_TPU_SCAN"] = "off"
    try:
        req = S3SelectRequest()
        req.expression = SELECT_SQL
        req.csv_header = "USE"
        out = io.BytesIO()
        run_select(req, csv, out)
    finally:
        del os.environ["MINIO_TPU_SCAN"]
    return b"".join(p for h, p in decode_messages(out.getvalue())
                    if h.get(":event-type") == "Records")


def leg_c_select(sv, seed: int, nbytes: int) -> None:
    from minio_tpu.s3select.message import decode_messages
    t0, before = time.monotonic(), observe()
    csv, expected = select_csv(seed, nbytes)
    c = sv.client()
    make_bucket(sv, "smoke-c")
    check(c.put_object("smoke-c", "rows.csv", csv).status_code == 200,
          "PUT rows.csv")
    r = c.request("POST", "/smoke-c/rows.csv",
                  query={"select": "", "select-type": "2"},
                  body=SELECT_XML.encode())
    check(r.status_code == 200, f"select: {r.status_code} {r.text[:300]}")
    msgs = decode_messages(r.content)
    got = b"".join(p for h, p in msgs if h.get(":event-type") == "Records")
    check(msgs[-1][0][":event-type"] == "End", "select: no End event")
    t_ref = time.monotonic()
    ref = classic_select(csv)
    ref_s = time.monotonic() - t_ref
    check(got == ref, f"select: served rows ({len(got)} B) differ from the "
          f"classic interpreter's ({len(ref)} B)")
    check(got == expected, "select: rows differ from the plain host answer")
    d = delta(before, observe())
    leg_line("c.select", {"csv_bytes": len(csv), "rows_out":
                          got.count(b"\n"), "classic_reference_s":
                          round(ref_s, 2)}, t0, d)
    check(d["device_flushes"].get("select_scan", 0) > 0,
          f"leg c: no device flush of select_scan: {d['device_flushes']}")
    no_salvage(d, "c.select")


def leg_c_sse(sv, seed: int, nbytes: int) -> None:
    from minio_tpu.crypto import chacha20poly1305 as ccp
    from minio_tpu.crypto import sse
    t0, before = time.monotonic(), observe()
    key = hashlib.sha256(b"chip-smoke-ssec-%d" % seed).digest()
    hdrs = {
        "x-amz-server-side-encryption-customer-algorithm": "AES256",
        "x-amz-server-side-encryption-customer-key":
            base64.b64encode(key).decode(),
        "x-amz-server-side-encryption-customer-key-md5":
            base64.b64encode(hashlib.md5(key).digest()).decode(),
    }
    body = np.random.default_rng([seed, 4]).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()
    c = sv.client()
    make_bucket(sv, "smoke-c")
    check("MINIO_TPU_SSE_CIPHER" not in os.environ,
          "MINIO_TPU_SSE_CIPHER is set")
    os.environ["MINIO_TPU_SSE_CIPHER"] = "chacha20"
    try:
        r = c.request("PUT", "/smoke-c/sealed", body=body, headers=hdrs)
    finally:
        del os.environ["MINIO_TPU_SSE_CIPHER"]
    check(r.status_code == 200, f"SSE PUT: {r.status_code} {r.text[:300]}")
    r = c.request("GET", "/smoke-c/sealed", headers=hdrs)
    check(r.status_code == 200 and r.content == body,
          f"SSE GET: {r.status_code}, round trip not bit-exact")
    # the stored ciphertext opens with the host reference, package by
    # package (numpy ChaCha20 keystream + Poly1305 tags)
    oi = sv.obj.get_object_info("smoke-c", "sealed")
    meta = oi.internal
    check(meta[sse.META_CIPHER] == sse.CIPHER_CHACHA20,
          f"SSE object cipher is {meta.get(sse.META_CIPHER)}")
    oek = sse.unseal_object_key(
        base64.b64decode(meta[sse.META_SEALED]), key, "smoke-c", "sealed",
        sse.CIPHER_CHACHA20)
    base_iv = base64.b64decode(meta[sse.META_IV])
    stored = sv.obj.get_object_bytes("smoke-c", "sealed")
    check(len(stored) == sse.enc_size(len(body)), "SSE stored size")
    full = len(body) // sse.PKG_SIZE
    sealed = np.frombuffer(stored, np.uint8,
                           full * (sse.PKG_SIZE + sse.TAG)).reshape(
                               full, sse.PKG_SIZE + sse.TAG)
    plain = np.frombuffer(body, np.uint8, full * sse.PKG_SIZE).reshape(
        full, sse.PKG_SIZE)
    for s0 in range(0, full, 64):
        blk = sealed[s0:s0 + 64]
        ct = np.ascontiguousarray(blk[:, :sse.PKG_SIZE])
        nonces = np.stack([ccp.nonce_words(sse._nonce(base_iv, s0 + i))
                           for i in range(len(blk))])
        pt, pk = ccp.keystream_xor(oek, nonces, ct)
        check(np.array_equal(pt, plain[s0:s0 + 64]),
              f"SSE: host reference decrypts packages {s0}.. differently")
        tags = ccp.poly1305_tags(pk, ccp.mac_datas(
            [sse._aad(s0 + i) for i in range(len(blk))], ct))
        check(np.array_equal(tags, blk[:, sse.PKG_SIZE:]),
              f"SSE: host reference tags differ at packages {s0}..")
    tail = stored[full * (sse.PKG_SIZE + sse.TAG):]
    if tail:
        check(ccp.open_one(oek, sse._nonce(base_iv, full), sse._aad(full),
                           tail) == body[full * sse.PKG_SIZE:],
              "SSE: tail package")
    d = delta(before, observe())
    leg_line("c.sse", {"bytes": nbytes, "packages": full + bool(tail)},
             t0, d)
    check(d["device_flushes"].get("sse_xor", 0) > 0,
          f"leg c: no device flush of sse_xor: {d['device_flushes']}")
    no_salvage(d, "c.sse")


def _geometry_items(k: int, m: int, block: int, items: int, seed: int):
    """Seeded (codec, data [items, k, S] uint8, packed words, lost shard
    ids, surviving shard ids, rebuild masks) for one geometry: the inputs
    legs d and --chips 4 submit."""
    from minio_tpu.ops import rs_jax
    codec = rs_jax.get_codec(k, m)
    shard = block // k
    data = np.random.default_rng([seed, 5, k]).integers(
        0, 256, (items, k, shard), dtype=np.uint8)
    words = [rs_jax.pack_shards(data[i]) for i in range(items)]
    lost = (3, k + 1)  # one data shard, one parity shard
    present = tuple(i for i in range(k + m) if i not in lost)[:k]
    return codec, data, words, lost, present, \
        codec.target_masks_np(present, lost)


def queue_ops(q, k: int, m: int, block: int, items: int, seed: int,
              chunk: int, ops=("encode", "masked", "fused",
                               "encode_hashed"), serial: bool = False
              ) -> dict:
    """Submit ``items`` blocks of each op to ``q`` and hold every result to
    the host references (gf256.gf_matmul_ref, bitrot.shard_chunk_digests).
    ``serial`` awaits each item before submitting the next (one-item
    flushes: nothing queues, so nothing diverts to a sibling lane).
    Returns the raw results per op (the four-chip phase compares them
    across routes)."""
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY, shard_chunk_digests
    from minio_tpu.ops import gf256, rs_jax
    codec, data, words, lost, present, masks = _geometry_items(
        k, m, block, items, seed)
    enc = codec.matrix
    out: dict = {}

    def run(submits):
        if serial:
            return [s_().result(600) for s_ in submits]
        return [f.result(600) for f in [s_() for s_ in submits]]

    parity_ref = [gf256.gf_matmul_ref(enc[k:], data[i])
                  for i in range(items)]
    if "encode" in ops:
        res = run([lambda w=w: q.encode(codec, w) for w in words])
        for i, r in enumerate(res):
            check(np.array_equal(rs_jax.unpack_shards(r), parity_ref[i]),
                  f"encode {k}+{m} item {i} differs from gf_matmul_ref")
        out["encode"] = res
    if "masked" in ops or "fused" in ops:
        # survivors (in ``present`` order) as the rebuild's input rows
        full = [np.concatenate([data[i], parity_ref[i]]) for i in
                range(items)]
        src = [rs_jax.pack_shards(np.ascontiguousarray(f[list(present)]))
               for f in full]
        want = [f[list(lost)] for f in full]
    if "masked" in ops:
        res = run([lambda s_=s_: q.masked(codec, s_, masks)
                   for s_ in src])
        for i, r in enumerate(res):
            check(np.array_equal(rs_jax.unpack_shards(r), want[i]),
                  f"masked rebuild {k}+{m} item {i} differs")
        out["masked"] = res
    if "fused" in ops:
        sdig = [np.ascontiguousarray(shard_chunk_digests(
            np.ascontiguousarray(f[list(present)]), chunk, 0))
            .view(np.uint32).reshape(k, -1) for f in full]
        res = run([lambda s_=s_, dg=dg: q.fused(
            codec, s_, masks, dg, HIGHWAY_KEY, chunk, 0)
            for s_, dg in zip(src, sdig)])
        for i, (r, valid) in enumerate(res):
            check(np.array_equal(rs_jax.unpack_shards(r), want[i]),
                  f"fused rebuild {k}+{m} item {i} differs")
            check(bool(np.all(valid)), f"fused verify {k}+{m} item {i} "
                  "rejected clean shards")
        out["fused"] = [r for r, _ in res]
    if "encode_hashed" in ops:
        res = run([lambda w=w: q.encode_hashed(
            codec, w, HIGHWAY_KEY, chunk, 0) for w in words])
        for i, (par_w, dg) in enumerate(res):
            par8 = rs_jax.unpack_shards(par_w)
            check(np.array_equal(par8, parity_ref[i]),
                  f"encode_hashed {k}+{m} item {i}: parity differs")
            ref = shard_chunk_digests(
                np.concatenate([data[i], par8]), chunk, 0)
            check(np.array_equal(
                np.ascontiguousarray(dg).view(np.uint8).reshape(-1),
                np.ascontiguousarray(ref).view(np.uint8).reshape(-1)),
                f"encode_hashed {k}+{m} item {i}: digests differ from "
                "shard_chunk_digests")
        out["encode_hashed"] = [np.concatenate(
            [np.ascontiguousarray(p).reshape(-1),
             np.ascontiguousarray(dg).view(np.uint32).reshape(-1)])
            for p, dg in res]
    return out


def leg_d(k: int, m: int, block: int, items: int, seed: int,
          chunk: int) -> None:
    from minio_tpu.runtime.dispatch import DispatchQueue
    t0 = time.monotonic()
    os.environ["MINIO_TPU_DISPATCH_MODE"] = "device"
    q = DispatchQueue()
    try:
        s0 = q.stats()
        queue_ops(q, k, m, block, items, seed, chunk)
        s1 = q.stats()
    finally:
        q.stop()
        del os.environ["MINIO_TPU_DISPATCH_MODE"]
    dev = s1["device_items"] - s0["device_items"]
    say("LEG d " + json.dumps({
        "did": {"geometry": f"{k}+{m}", "block": block, "items": items,
                "ops": 4},
        "seconds": round(time.monotonic() - t0, 2),
        "device_items": dev, "cpu_items": s1["cpu_items"],
        "spilled_items": s1["spilled_items"],
        "spill_reasons": s1["spill_reasons"],
        "salvaged_items": s1["salvaged_items"],
        "probe": {k_: (round(v, 6) if isinstance(v, float) else v)
                  for k_, v in s1["probe"].items()}}, sort_keys=True))
    check(not s1["salvaged_items"], f"leg d: salvaged {s1['salvaged_items']}")
    check(dev > 0, "leg d: nothing ran on the device")


# --------------------------------------------------------------------------
# --chips 4: the mesh route and the per-device lanes, nothing else


def four_chip_phase(k: int, m: int, block: int, items: int, seed: int,
                    chunk: int, n_dev: int = 4) -> None:
    import jax
    from minio_tpu import qos
    from minio_tpu.obs import timeline as tl
    from minio_tpu.ops import gf256, rs_jax
    from minio_tpu.runtime.dispatch import DispatchQueue
    from minio_tpu.runtime.mesh import (build_sharded_step, mesh_device,
                                        mesh_size, object_mesh)
    t0 = time.monotonic()
    check(mesh_size() == n_dev, f"object_mesh() spans {mesh_size()} "
          f"devices, want {n_dev}")
    ops = ("encode", "masked", "encode_hashed")
    os.environ["MINIO_TPU_DISPATCH_MODE"] = "device"
    try:
        # 1) SPMD: no affinity -> every bulk flush shards over the mesh
        # (the rebuild is pinned to the bulk lane so its 128 items are
        # one coalesced flush like the others, not interactive 8s)
        tl.reset()
        q = DispatchQueue()
        try:
            with qos.device_stream(qos.STREAM_BULK):
                spmd = queue_ops(q, k, m, block, items, seed, chunk, ops)
            st_spmd = q.stats()
        finally:
            q.stop()
        lanes_spmd = sorted(
            ln for ln, s in tl.utilization()["lanes"].items()
            if ln.startswith("dev") and s["flushes"] > 0)
        check(len(lanes_spmd) == n_dev, f"SPMD flushes occupied lanes "
              f"{lanes_spmd}, want {n_dev}")
        check(not st_spmd["salvaged_items"],
              f"SPMD: salvaged {st_spmd['salvaged_items']}")
        # 2) the same items on a single device: pinned to lane 0 by an
        # affinity, one item in flight at a time so the scheduler never
        # diverts a flush to a sibling lane
        tl.reset()
        q = DispatchQueue()
        try:
            with qos.lane_affinity(0), qos.device_stream(qos.STREAM_BULK):
                single = queue_ops(q, k, m, block, items, seed, chunk, ops,
                                   serial=True)
            st_single = q.stats()
        finally:
            q.stop()
        lanes_single = sorted(
            ln for ln, s in tl.utilization()["lanes"].items()
            if ln.startswith("dev") and s["flushes"] > 0)
        check(len(lanes_single) == 1, f"single-device flushes occupied "
              f"{lanes_single}")
        for op in ops:
            for i, (a, b) in enumerate(zip(spmd[op], single[op])):
                check(np.array_equal(np.asarray(a), np.asarray(b)),
                      f"{op} item {i}: SPMD result differs from the "
                      "single-device result")
        # 3) per-device lanes: four erasure-set affinities, one lane each
        tl.reset()
        q = DispatchQueue()
        # look at each pinned flush's DEVICE arrays before the readback
        # turns them into numpy: where does a lane's output live?
        homes: dict[int, set] = {}
        tail = q._account_and_complete

        def spy(b, out_dev, flushed, *a, lane=None, **kw):
            outs = out_dev if isinstance(out_dev, tuple) else (out_dev,)
            for o in outs:
                homes.setdefault(lane, set()).update(o.devices())
            return tail(b, out_dev, flushed, *a, lane=lane, **kw)

        q._account_and_complete = spy
        try:
            per_lane = {}
            for a in range(n_dev):
                with qos.lane_affinity(a):
                    per_lane[a] = queue_ops(q, k, m, block, items // n_dev,
                                            seed, chunk, ("encode",))
            st_lanes = q.stats()
        finally:
            q.stop()
        util = tl.utilization()["lanes"]
        lanes_aff = sorted(ln for ln, s in util.items()
                           if ln.startswith("dev") and s["flushes"] > 0)
        check(len(lanes_aff) == n_dev, f"affinity flushes occupied lanes "
              f"{lanes_aff}, want {n_dev} distinct")
        for a in range(n_dev):
            for i, r in enumerate(per_lane[a]["encode"]):
                check(np.array_equal(np.asarray(r),
                                     np.asarray(single["encode"][i])),
                      f"lane {a} encode item {i} differs from the "
                      "single-device result")
        # each lane's output lives on its own device
        check(sorted(homes, key=str) == list(range(n_dev)),
              f"affinity flushes ran on lanes {sorted(homes, key=str)}")
        for a in range(n_dev):
            check(homes[a] == {mesh_device(a)}, f"lane {a}: outputs on "
                  f"{homes[a]}, pinned to {mesh_device(a)}")
        check(len({d for h in homes.values() for d in h}) == n_dev,
              f"lane devices not distinct: {homes}")
    finally:
        del os.environ["MINIO_TPU_DISPATCH_MODE"]
    # 4) the ("objects","shards") step with the all-gather XOR combine
    stepped, mesh = build_sharded_step(k, m, n_dev)
    dp = mesh.shape["objects"]
    W, B = 512, dp * 2
    data = np.random.default_rng([seed, 6]).integers(
        0, 256, (B, k, W * 4), dtype=np.uint8)
    enc = gf256.build_matrix(k, m)
    chosen = tuple(i for i in range(k + m) if i not in (1, 3))[:k]
    parity, decoded = jax.device_get(stepped(
        gf256.coeff_masks(enc[k:]),
        gf256.coeff_masks(gf256.decode_matrix(enc, k, chosen)),
        rs_jax.pack_shards(data)))
    for i in range(B):
        check(np.array_equal(rs_jax.unpack_shards(np.asarray(parity[i])),
                             gf256.gf_matmul_ref(enc[k:], data[i])),
              f"build_sharded_step({k},{m},{n_dev}): parity of item {i} "
              "differs from gf_matmul_ref")
    say("PHASE chips4 " + json.dumps({
        "did": {"geometry": f"{k}+{m}", "block": block, "items": items,
                "ops": list(ops), "mesh": dict(object_mesh().shape),
                "sharded_step_mesh": dict(mesh.shape)},
        "seconds": round(time.monotonic() - t0, 2),
        "spmd": {"lanes": lanes_spmd,
                 "device_items": st_spmd["device_items"],
                 "cpu_items": st_spmd["cpu_items"],
                 "spill_reasons": st_spmd["spill_reasons"]},
        "single": {"lanes": lanes_single,
                   "device_items": st_single["device_items"],
                   "cpu_items": st_single["cpu_items"],
                   "spill_reasons": st_single["spill_reasons"]},
        "affinity": {"lanes": lanes_aff,
                     "flushes": {ln: util[ln]["flushes"]
                                 for ln in lanes_aff},
                     "device_items": st_lanes["device_items"],
                     "cpu_items": st_lanes["cpu_items"],
                     "lane_diverts": st_lanes["lane_diverts"],
                     "spill_reasons": st_lanes["spill_reasons"]},
        "lane_output_devices": {str(a): sorted(str(d) for d in h)
                                for a, h in homes.items()}},
        sort_keys=True))
    for st, what in ((st_spmd, "SPMD"), (st_single, "single"),
                     (st_lanes, "affinity")):
        check(st["device_items"] > 0 and not st["salvaged_items"],
              f"{what}: device_items={st['device_items']} "
              f"salvaged={st['salvaged_items']}")


# --------------------------------------------------------------------------


def require_tpu(chips: int):
    """The device as JAX reports it, or None (with the reason printed):
    no CPU fallback, no interpret mode."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        say(f"chip_smoke: refusing to run: jax.devices()[0].platform is "
            f"{d0.platform!r}, not 'tpu' (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')}); this script "
            "has no CPU route")
        return None
    if len(devs) < chips:
        say(f"chip_smoke: refusing to run: --chips {chips} but JAX sees "
            f"{len(devs)} device(s)")
        return None
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def require_native() -> None:
    from minio_tpu import native
    from minio_tpu.erasure.bitrot import (DEFAULT_BITROT_ALGO,
                                          BitrotAlgorithm)
    check(native.available(), "native library failed to build or load "
          "(see the ERROR log above)")
    check(DEFAULT_BITROT_ALGO is BitrotAlgorithm.HIGHWAYHASH256S,
          f"default bitrot algorithm is {DEFAULT_BITROT_ALGO.value}, not "
          "highwayhash256S")
    say("native: g++ " + " ".join(native.BUILD_FLAGS) + f"; build_key="
        f"{native.build_key()[:16]}; gf256_has_avx2="
        f"{native.load_native().gf256_has_avx2()}")


def scratch_root() -> str:
    """Drives sit on a real file system, in a directory this script makes
    and removes, outside whatever the chip tool copies back.
    MINIO_TPU_BENCH_DIR overrides the parent, as in bench.py."""
    base = os.environ.get("MINIO_TPU_BENCH_DIR")
    if base:
        os.makedirs(base, exist_ok=True)
    return tempfile.mkdtemp(prefix="chip-smoke-", dir=base or None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh route + per-device lanes")
    args = ap.parse_args(argv)
    t_all = time.monotonic()
    device = require_tpu(args.chips)
    if device is None:
        return 2
    import jax

    import minio_tpu
    from minio_tpu import ops
    from minio_tpu.obs import device as devobs
    say(f"device: {json.dumps(device)}; jax {jax.__version__}; "
        f"compile cache: {ops.COMPILE_CACHE_DIR}")
    require_native()
    try:
        if args.chips == 4:
            four_chip_phase(16, 4, MIB, 128, args.seed, 16384)
        else:
            root = scratch_root()
            try:
                sv = Served(root, drives=12, parity=4)
                say(f"server: {sv.banner}; {sv.srv.endpoint()}; drives "
                    f"under {root}; block {sv.obj.block_size}; bitrot "
                    f"{sv.obj.bitrot_algo.value}")
                try:
                    bodies = make_bodies(args.seed, 16, 64 * MIB, 64, MIB)
                    etags = reference_etags(sv, bodies)
                    leg_a(sv, bodies, etags, clients=8)
                    leg_b(sv, bodies, etags, clients=8)
                    leg_c_select(sv, args.seed, 64 * MIB)
                    leg_c_sse(sv, args.seed, 64 * MIB)
                finally:
                    sv.shutdown()
            finally:
                shutil.rmtree(root, ignore_errors=True)
            leg_d(16, 4, MIB, 128, args.seed, 16384)
    finally:
        minio_tpu.shutdown()
    comp = devobs.compile_snapshot()
    say("compile: " + json.dumps({
        "cache_dir": ops.COMPILE_CACHE_DIR,
        "compiles_total": comp["compiles_total"],
        "compile_seconds_total": comp["compile_seconds_total"],
        "slowest": [{"op": r["op"], "signature": r["signature"][:80],
                     "seconds": r["seconds"]} for r in comp["table"][:8]],
        "wall_seconds": round(time.monotonic() - t_all, 2)}))
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
