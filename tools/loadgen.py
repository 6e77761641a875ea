"""Mixed-workload scale harness — closed+open-loop GET/PUT/LIST/DELETE
load against a LIVE server, reported as SLO evidence (ROADMAP item 5:
"thousands of concurrent mixed clients ... scanner/heal cycles provably
never stalling the hot path, reported as evidence rather than vibes").

Two load shapes compose:

* **Closed loop** — ``clients`` worker threads, each a keep-alive
  SigV4-signing session issuing one request after another (think-time
  zero). Concurrency is the control variable; throughput is measured.
* **Open loop** — a Poisson-ish arrival generator ramping from 0 to
  ``open_rps`` over ``ramp_s`` and dispatching one-shot requests onto a
  bounded executor. Arrival rate is the control variable; queueing is
  measured. Thousands of *virtual clients* are modeled by the arrival
  process, not by a thread each.

Mid-run the harness forces one data-scanner cycle (always QoS class
``background`` — the scanner applies it internally) and, after the
measured phase, runs a small deliberate **overload probe** (admission
capacity pinched to 1 for a burst) so the 503 SlowDown + ``Retry-After``
contract is exercised on every run, not only on lucky ones.

The report is the deliverable: per-op/per-class achieved throughput and
latency percentiles, every 503's Retry-After compliance, the scanner
window's hot-path impact vs the surrounding baseline (plus the QoS
class-counter and lockrank evidence), the server's standing SLO verdict
(``obs/slo.py``), the cluster health snapshot, and a ``verdicts`` block
whose ``passed`` gates CI. ``bench.py`` embeds a run as the
``scale_slo`` extra for BENCH_r07+; ``tests/test_loadgen.py`` runs the
scaled-down tier-1 profile from ISSUE 10's acceptance criteria.

``--degraded`` (ISSUE 13) kills one disk's shard READS through the
fault registry for the whole measured phase: GETs whose data shards
touched it serve through reconstruct on the dispatch plane's
interactive device lane while a heal worker thread continuously
rebuilds toward the dead disk — the interactive class's availability
and burn-rate verdicts then judge the latency tier under real
degraded traffic (``degraded_reconstructs_served``,
``degraded_heal_mix_ran``, ``degraded_interactive_availability_ok``).

A continuous-profiler window (ISSUE 14, docs/observability.md
"Continuous profiling") rides every run: the report's ``host_profile``
section carries whole-run subsystem shares + the top contended lock
sites, and a second window over EXACTLY the forced scanner cycle
yields its scanner-subsystem CPU share — the
``scanner_cpu_share_ok`` verdict (bound: ``--scanner-share-max``,
default 0.5) makes the item-3 "scanner never stalls the hot path"
claim machine-checked instead of inferred.

``--buckets N`` (ISSUE 18) spreads the same key space across N
buckets: the per-bucket analytics registry (``obs/bucketstats.py``)
sees real multi-tenant traffic, and two verdicts gate on it —
``bucket_metrics_bounded_ok`` (the scrape's bucket-label value set
stays at ``top_n``+1 however many tenants hit the server) and
``slo_breach_names_bucket_ok`` (any breached class/window carries burn
attribution naming the offending buckets). A dead-webhook probe rides
every single-node run unless ``--no-notifier-probe``: a webhook target
nothing listens on gets a tiny persistent queue and every load
bucket's object events, and ``notifier_bounded_ok`` proves the queue
caps at its limit with every overflow counted — never a stalled PUT,
never a silent drop.

``--topology N`` stands the same load on a real N-node in-process
cluster (``dist.harness.LocalCluster``: separate listeners, storage
REST RPC, dsync locks) and ``--chaos-kill <idx>`` runs the node-chaos
phase (ISSUE 12): a ledger writer records every acknowledged PUT while
the node is killed mid-run and restarted later; after the heal backlog
drains, every acked key is re-verified — the ``no_acked_write_loss``,
``node_unreachable_detected``, ``heal_backlog_drained`` and
``background_slo_availability_ok`` verdicts gate the run.

Run standalone::

    python -m tools.loadgen --objects 1000 --clients 64 --duration 6
    python -m tools.loadgen --topology 4 --chaos-kill 3 --duration 12
"""
from __future__ import annotations

import argparse
import io
import json
import random
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

DEFAULT_MIX = {"get": 0.60, "put": 0.25, "list": 0.10, "delete": 0.05}

#: op -> the QoS class the admission plane files it under
OP_CLASS = {"get": "interactive", "put": "interactive",
            "delete": "interactive", "list": "control"}


@dataclass
class Profile:
    """One workload shape. The tier-1 profile (ISSUE 10 acceptance:
    >=1k objects, >=64 concurrent mixed clients, one scanner cycle
    forced mid-run) is ``Profile.tier1()``."""
    objects: int = 1000
    clients: int = 64
    duration_s: float = 6.0
    mix: dict = field(default_factory=lambda: dict(DEFAULT_MIX))
    value_bytes: int = 4096
    open_rps: float = 50.0      # open-loop arrival rate after the ramp
    ramp_s: float = 2.0
    bucket: str = "loadgen"
    #: per-bucket analytics spread (ISSUE 18): >1 fans the SAME key
    #: space across ``bucket-0000..bucket-NNNN`` so the bounded-
    #: cardinality registry sees real multi-tenant traffic — the
    #: ``bucket_metrics_bounded_ok`` verdict then proves the scrape
    #: stays at top_n+1 label values however many tenants hit it
    buckets: int = 1
    seed: int = 7
    scanner_mid_run: bool = True
    overload_probe: bool = True
    #: arm a dead webhook target with a tiny queue limit and route the
    #: load buckets' object events at it: the measured phase proves the
    #: event queue caps at its limit with every overflow counted, and
    #: PUT availability holds through the full queue (ISSUE 18)
    notifier_probe: bool = True
    preload_threads: int = 16
    #: "the scanner never stalls the hot path" made machine-checked
    #: (ISSUE 14 / ROADMAP item 3): the scanner-cycle window's
    #: scanner-subsystem CPU share (continuous profiler, high-rate
    #: window over exactly the cycle) must stay under this bound or
    #: the ``scanner_cpu_share_ok`` verdict fails the run
    scanner_share_max: float = 0.5
    #: node-chaos phase (needs a LoadGen.cluster topology): kill this
    #: node index mid-run, restart it later in the run, then hold the
    #: run open until the heal backlog drains — the ledger writer
    #: proves zero acknowledged-write loss across the kill
    chaos_kill_node: int | None = None
    chaos_kill_at_frac: float = 0.35
    chaos_restart_at_frac: float = 0.7
    heal_drain_timeout_s: float = 90.0
    #: degraded-GET + heal interactive mix (ISSUE 13): kill one disk's
    #: shard reads via the fault registry for the whole measured phase
    #: — GETs whose data shards touched it serve through reconstruct
    #: (the interactive device lane), while a heal worker thread
    #: continuously rebuilds toward the dead disk. The interactive
    #: class's burn rates then judge the latency tier under real
    #: degraded traffic. Requires value_bytes above the 128 KiB inline
    #: threshold (inlined objects never read shards).
    degraded: bool = False
    #: async-replication chaos phase (ISSUE 19, needs a LoadGen.cluster
    #: topology): a replication rule points a source bucket at THIS
    #: node index's endpoint, a writer streams unique PUTs at the
    #: source, and mid-stream the TARGET is killed (or partitioned)
    #: and later rejoined — the settle phase proves no replica
    #: obligation was lost (every acked source key re-reads bit-exact
    #: from the replica bucket on the rejoined target) and the
    #: replication backlog drained to zero. Kill/rejoin timing reuses
    #: chaos_kill_at_frac / chaos_restart_at_frac.
    replication_target_node: int | None = None
    #: partition the target's RPC plane instead of killing the process
    #: — the ship path sees refused calls while the node stays up
    replication_partition: bool = False
    replication_drain_timeout_s: float = 90.0

    @classmethod
    def tier1(cls) -> "Profile":
        return cls()

    def bucket_name(self, i: int) -> str:
        """Bucket for object index ``i``: the single configured bucket,
        or a deterministic spread across ``buckets`` names — preload and
        the op mix map indexes the same way, so every GET finds its
        key."""
        if self.buckets <= 1:
            return self.bucket
        return f"{self.bucket}-{i % self.buckets:04d}"


class _SigClient:
    """Minimal SigV4 keep-alive client (one per worker thread)."""

    def __init__(self, endpoint: str, ak: str, sk: str,
                 region: str = "us-east-1"):
        import requests
        from minio_tpu.server.auth import UNSIGNED_PAYLOAD, SigV4Verifier
        self.endpoint = endpoint.rstrip("/")
        self.host = self.endpoint.split("//", 1)[1]
        self.ak, self.sk = ak, sk
        self.signer = SigV4Verifier(lambda a: None, region)
        self.http = requests.Session()
        self._unsigned = UNSIGNED_PAYLOAD

    def request(self, method: str, path: str,
                query: dict[str, str] | None = None, body: bytes = b""):
        q = {k: [v] for k, v in (query or {}).items()}
        h = {"host": self.host}
        h["authorization"] = self.signer.sign_request(
            self.ak, self.sk, method, path, q, h, self._unsigned)
        qs = urllib.parse.urlencode({k: v for k, v in
                                     (query or {}).items()})
        url = self.endpoint + urllib.parse.quote(path) + \
            (f"?{qs}" if qs else "")
        return self.http.request(method, url, data=body or None,
                                 headers=h, timeout=30)


class _Recorder:
    """Thread-safe sample sink: (rel_ts, op, status, dur_s,
    retry_after_present) rows + running totals."""

    def __init__(self, t0: float):
        self.t0 = t0
        self._lock = threading.Lock()
        self.rows: list[tuple[float, str, int, float, bool]] = []

    def note(self, op: str, status: int, dur_s: float,
             retry_after: bool) -> None:
        row = (time.monotonic() - self.t0, op, status, dur_s,
               retry_after)
        with self._lock:
            self.rows.append(row)

    def snapshot(self) -> list[tuple[float, str, int, float, bool]]:
        with self._lock:
            return list(self.rows)


def _pcts(vals: list[float]) -> dict:
    if not vals:
        return {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                "max_ms": 0.0}
    vs = sorted(vals)
    def at(q: float) -> float:
        return vs[min(len(vs) - 1, int(q * len(vs)))] * 1e3
    return {"p50_ms": round(at(0.5), 3), "p95_ms": round(at(0.95), 3),
            "p99_ms": round(at(0.99), 3),
            "max_ms": round(vs[-1] * 1e3, 3)}


def _op_rollup(rows, window: tuple[float, float] | None = None) -> dict:
    """Per-op + per-class stats over ``rows``, optionally restricted to
    a [t_lo, t_hi) relative-time window."""
    per_op: dict[str, dict] = {}
    per_cls: dict[str, dict] = {}
    for ts, op, status, dur, ra in rows:
        if window is not None and not (window[0] <= ts < window[1]):
            continue
        o = per_op.setdefault(op, {"count": 0, "err5xx": 0, "s503": 0,
                                   "s503_retry_after": 0, "lat": []})
        o["count"] += 1
        o["lat"].append(dur)
        if status >= 500:
            o["err5xx"] += 1
        if status == 503:
            o["s503"] += 1
            if ra:
                o["s503_retry_after"] += 1
        c = per_cls.setdefault(OP_CLASS.get(op, "control"),
                               {"count": 0, "err5xx": 0, "lat": []})
        c["count"] += 1
        c["lat"].append(dur)
        if status >= 500:
            c["err5xx"] += 1
    for o in per_op.values():
        o.update(_pcts(o.pop("lat")))
    for c in per_cls.values():
        lat = c.pop("lat")
        c.update(_pcts(lat))
        c["availability"] = round(
            1.0 - c["err5xx"] / c["count"], 6) if c["count"] else 1.0
    return {"ops": per_op, "classes": per_cls}


class LoadGen:
    """Drives one profile against a server. Build with ``inprocess()``
    for the self-contained form (own ErasureObjects + S3Server over
    temp dirs) or pass an endpoint + credentials for a remote target
    (scanner forcing and the overload probe then need ``server``-less
    fallbacks and are skipped)."""

    def __init__(self, endpoint: str, access_key: str, secret_key: str,
                 server=None, objlayer=None):
        self.endpoint = endpoint
        self.ak, self.sk = access_key, secret_key
        self.server = server          # in-process S3Server (or None)
        self.obj = objlayer
        self._owned = False

    # -- construction ---------------------------------------------------------

    @classmethod
    def inprocess(cls, root: str, disks: int = 6, parity: int = 2,
                  access_key: str = "loadgen",
                  secret_key: str = "loadgen-secret") -> "LoadGen":
        import os

        from minio_tpu.objectlayer import ErasureObjects
        from minio_tpu.server import S3Server
        from minio_tpu.storage import XLStorage
        dd = [XLStorage(os.path.join(root, f"d{i}"))
              for i in range(disks)]
        obj = ErasureObjects(dd, default_parity=parity)
        srv = S3Server(obj, "127.0.0.1", 0, access_key=access_key,
                       secret_key=secret_key)
        srv.start_background()
        # background services with an effectively-infinite scan
        # interval: cycles run only when the harness forces them
        srv.start_background_services(scan_interval_s=1e9)
        # forced cycles should spend their time walking, not sleeping:
        # the throttle exists to protect production hot paths over
        # minutes-long crawls, and the harness measures contention, not
        # the sleep
        srv.scanner.sleep_per_object = 0.0
        lg = cls(srv.endpoint(), access_key, secret_key, server=srv,
                 objlayer=obj)
        lg._owned = True
        return lg

    @classmethod
    def cluster(cls, root: str, nodes: int = 4, disks_per_node: int = 2,
                parity: int = 2) -> "LoadGen":
        """The distributed form (``--topology N``, ROADMAP item 4): an
        in-process N-node cluster (separate HTTP listeners, storage
        REST RPC, dsync locks — dist.harness.LocalCluster) with the
        load driven at node 0 and the cluster handle exposed for the
        node-chaos phase. Scanner forcing targets node 0's scanner."""
        from minio_tpu.dist.harness import LocalCluster
        lc = LocalCluster(root, nodes=nodes,
                          disks_per_node=disks_per_node, parity=parity)
        node0 = lc.nodes[0]
        if getattr(node0.server, "scanner", None) is not None:
            node0.server.scanner.sleep_per_object = 0.0
        lg = cls(lc.endpoint(0), lc.access_key, lc.secret_key,
                 server=node0.server, objlayer=node0.obj)
        lg.topology = lc
        lg._owned = True
        return lg

    def close(self) -> None:
        if not self._owned:
            return
        lc = getattr(self, "topology", None)
        if lc is not None:
            lc.shutdown()
        elif self.server is not None:
            self.server.shutdown()

    # -- phases ---------------------------------------------------------------

    def preload(self, profile: Profile) -> float:
        """Populate the namespace (``objects`` keys) through the object
        layer directly — setup, not measured workload. Returns wall
        seconds."""
        if self.obj is None:
            raise RuntimeError("preload needs an in-process layer")
        body = random.Random(profile.seed).randbytes(profile.value_bytes)
        for bi in range(max(1, profile.buckets)):
            try:
                self.obj.make_bucket(profile.bucket_name(bi))
            except Exception:  # noqa: BLE001 — exists from a prior phase
                pass
        t0 = time.monotonic()

        def put_range(lo: int, hi: int) -> None:
            for j in range(lo, hi):
                self.obj.put_object(profile.bucket_name(j), f"o{j:07d}",
                                    io.BytesIO(body), len(body))

        nthreads = max(1, profile.preload_threads)
        step = (profile.objects + nthreads - 1) // nthreads
        with ThreadPoolExecutor(max_workers=nthreads) as ex:
            futs = [ex.submit(put_range, lo, min(lo + step,
                                                 profile.objects))
                    for lo in range(0, profile.objects, step)]
            for f in futs:
                f.result()
        return time.monotonic() - t0

    def _one_op(self, cl: _SigClient, rng: random.Random,
                profile: Profile, rec: _Recorder, body: bytes) -> None:
        r = rng.random()
        acc = 0.0
        op = "get"
        for name, w in profile.mix.items():
            acc += w
            if r <= acc:
                op = name
                break
        t0 = time.perf_counter()
        try:
            if op == "get":
                i = rng.randrange(profile.objects)
                resp = cl.request(
                    "GET", f"/{profile.bucket_name(i)}/o{i:07d}")
            elif op == "put":
                # churn range: PUT/DELETE share keys ABOVE the stable
                # GET namespace so deletes never starve readers
                i = rng.randrange(max(1, profile.objects // 4))
                resp = cl.request(
                    "PUT", f"/{profile.bucket_name(i)}/c{i:07d}",
                    body=body)
            elif op == "delete":
                i = rng.randrange(max(1, profile.objects // 4))
                resp = cl.request(
                    "DELETE", f"/{profile.bucket_name(i)}/c{i:07d}")
            else:  # list
                b = profile.bucket_name(
                    rng.randrange(max(1, profile.buckets)))
                resp = cl.request(
                    "GET", f"/{b}",
                    query={"max-keys": "64",
                           "prefix": f"o{rng.randrange(10)}"})
            status = resp.status_code
            ra = "Retry-After" in resp.headers
            resp.content  # drain keep-alive
        except Exception:  # noqa: BLE001 — a transport error is an
            status, ra = 599, False  # availability failure, not a crash
        rec.note(op, status, time.perf_counter() - t0, ra)

    def _closed_loop(self, profile: Profile, rec: _Recorder,
                     deadline: float, body: bytes) -> list[threading.Thread]:
        def worker(wid: int) -> None:
            cl = _SigClient(self.endpoint, self.ak, self.sk)
            rng = random.Random(profile.seed * 1000 + wid)
            while time.monotonic() < deadline:
                self._one_op(cl, rng, profile, rec, body)

        ths = [threading.Thread(target=worker, args=(i,), daemon=True,
                                name=f"loadgen-{i}")
               for i in range(profile.clients)]
        for t in ths:
            t.start()
        return ths

    def _open_loop(self, profile: Profile, rec: _Recorder,
                   deadline: float, body: bytes
                   ) -> threading.Thread | None:
        """Arrival generator: rate ramps 0 -> open_rps over ramp_s,
        then holds; each arrival is one one-shot op on a bounded
        executor (a saturated executor sheds arrivals — open-loop
        overload shows up as queueing/shed, exactly as intended).
        None when the profile disables the open loop."""
        if profile.open_rps <= 0:
            return None

        ex = ThreadPoolExecutor(max_workers=min(32, profile.clients))
        local = threading.local()

        def one(rng_seed: int) -> None:
            # open-loop arrivals that are still queued when the run
            # ends are SHED, not drained: the backlog beyond the
            # deadline is the overload signal, and draining it would
            # stretch the run unboundedly on a saturated host
            if time.monotonic() >= deadline:
                return
            cl = getattr(local, "cl", None)
            if cl is None:
                cl = local.cl = _SigClient(self.endpoint, self.ak,
                                           self.sk)
            self._one_op(cl, random.Random(rng_seed), profile, rec,
                         body)

        def gen() -> None:
            rng = random.Random(profile.seed ^ 0xA77)
            t_start = time.monotonic()
            n = 0
            while True:
                now = time.monotonic()
                if now >= deadline:
                    break
                frac = 1.0 if profile.ramp_s <= 0 else \
                    min(1.0, (now - t_start) / profile.ramp_s)
                rate = max(0.5, profile.open_rps * frac)
                time.sleep(rng.expovariate(rate))
                try:
                    ex.submit(one, profile.seed * 7919 + n)
                except RuntimeError:
                    break
                n += 1
            ex.shutdown(wait=True)

        t = threading.Thread(target=gen, daemon=True,
                             name="loadgen-openloop")
        t.start()
        return t

    def _force_scanner(self, rec_t0: float, out: dict,
                       at: float | None = None) -> None:
        """One scanner cycle mid-run (QoS background class applied by
        the scanner itself); records its relative-time window into
        ``out``. Runs on its own thread — on a saturated host the
        cycle being CPU-starved by interactive traffic is the desired
        outcome, and the run must not stretch to wait for it. ``at``
        (absolute monotonic time) delays the cycle from INSIDE the
        thread: the caller spawns it before the client storm, because
        Thread.start plus the profiler snapshot under a full GIL convoy
        has been observed to lag seconds — enough to push the cycle
        past the measured window entirely."""
        scanner = getattr(self.server, "scanner", None)
        if scanner is None:
            return
        if at is not None:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
        # profiler window over EXACTLY the cycle (ISSUE 14): the
        # scanner-subsystem CPU share inside it is the evidence behind
        # the scanner_cpu_share_ok verdict. A base-aggregate DELTA, so
        # the window and the surrounding baseline carry the identical
        # sampling tax — an attached high-rate capture here once made
        # "during the cycle" measurably slower than "before" and the
        # attribution blamed the scanner for the profiler's own load
        from minio_tpu.obs import profiler as prof
        out["start_s"] = round(time.monotonic() - rec_t0, 3)
        snap = prof.agg_snapshot()
        try:
            scanner.scan_cycle()
        finally:
            out["end_s"] = round(time.monotonic() - rec_t0, 3)
        d = prof.delta_report(snap, n=8)
        out["cycle"] = scanner.cycle
        out["profile"] = {
            "samples": d["samples"],
            "scanner_cpu_share": d["subsystems"].get("scanner", 0.0),
            "subsystems": d["subsystems"],
        }

    def _chaos_phase(self, profile: Profile, rec_t0: float,
                     deadline: float, out: dict) -> None:
        """Node-chaos driver (its own thread): a LEDGER WRITER puts
        unique keys continuously while the target node is killed and
        later restarted; every 200-acked key is recorded and verified
        AFTER the run — the zero-acknowledged-write-loss proof. The
        health snapshot is sampled right after the kill (unreachable
        detection) and the heal backlog is watched to zero after
        rejoin (cross-node repair drains)."""
        import hashlib
        lc = self.topology
        idx = profile.chaos_kill_node
        kill_at = rec_t0 + profile.duration_s * profile.chaos_kill_at_frac
        restart_at = rec_t0 + profile.duration_s * \
            profile.chaos_restart_at_frac
        cl = _SigClient(self.endpoint, self.ak, self.sk)
        acked: dict[str, str] = {}
        seq = 0
        killed = restarted = False
        while time.monotonic() < deadline or (killed and not restarted):
            now = time.monotonic()
            if not killed and now >= kill_at:
                lc.kill(idx)
                out["killed_at_s"] = round(now - rec_t0, 3)
                killed = True
                # unreachable detection: ONE aggregation right after
                # the kill must already report the node gone
                from minio_tpu.obs.health import cluster_snapshot
                snap = cluster_snapshot(self.server)["cluster"]
                out["detected_unreachable"] = (
                    snap["nodes_offline"] > 0 or
                    snap["peers_unreachable"] > 0)
                continue
            if killed and not restarted and now >= restart_at:
                lc.restart(idx)
                out["restarted_at_s"] = round(
                    time.monotonic() - rec_t0, 3)
                restarted = True
                continue
            body = hashlib.sha256(f"ledger{seq}".encode()).digest() * 64
            key = f"ledger/k{seq:06d}"
            try:
                r = cl.request("PUT", f"/{profile.bucket}/{key}",
                               body=body)
                if r.status_code == 200:
                    acked[key] = hashlib.md5(body).hexdigest()
            except Exception:  # noqa: BLE001 — unacked: not in ledger
                out["unacked_writes"] = out.get("unacked_writes", 0) + 1
            seq += 1
        out["acked_writes"] = len(acked)
        out["_acked"] = acked

    def _chaos_settle(self, profile: Profile, out: dict) -> None:
        """Post-run: wait for every live node's heal backlog to drain,
        then re-read every acknowledged ledger key."""
        import hashlib
        lc = self.topology
        t0 = time.monotonic()
        deadline = t0 + profile.heal_drain_timeout_s
        drained = False
        while time.monotonic() < deadline:
            backlog = 0
            for node in lc.nodes:
                srv = node.server
                mrf = getattr(srv, "mrf", None) if srv else None
                if mrf is not None:
                    backlog += mrf.stats()["queued"]
            if backlog == 0:
                drained = True
                break
            time.sleep(0.25)
        out["heal_drain_s"] = round(time.monotonic() - t0, 3)
        out["heal_drained"] = drained
        acked = out.pop("_acked", {})
        cl = _SigClient(self.endpoint, self.ak, self.sk)
        lost: list[str] = []
        for key, md5 in acked.items():
            try:
                r = cl.request("GET", f"/{profile.bucket}/{key}")
                ok = r.status_code == 200 and \
                    hashlib.md5(r.content).hexdigest() == md5
            except Exception:  # noqa: BLE001
                ok = False
            if not ok:
                lost.append(key)
        out["lost_writes"] = lost[:16]
        out["lost_count"] = len(lost)

    def _replication_phase(self, profile: Profile, rec_t0: float,
                           deadline: float, out: dict) -> None:
        """Replication-chaos driver (ISSUE 19, its own thread): point a
        replication rule at the target node through the S3 surface
        (PutBucketReplication), stream unique PUTs at the source
        bucket, and mid-stream kill — or partition — the TARGET;
        rejoin it later in the run. Every 200-acked source key is
        recorded; the settle phase re-reads each one from the replica
        bucket on the rejoined target, the no-replica-obligation-lost
        proof."""
        import hashlib
        lc = self.topology
        idx = profile.replication_target_node
        src = f"{profile.bucket}-replsrc"
        dst = f"{profile.bucket}-replica"
        out["src"], out["dst"], out["target_node"] = src, dst, idx
        out["mode"] = ("partition" if profile.replication_partition
                       else "kill")
        cl = _SigClient(self.endpoint, self.ak, self.sk)
        cl.request("PUT", f"/{src}")
        xml = (
            "<ReplicationConfiguration><Rule><ID>loadgen</ID>"
            "<Status>Enabled</Status><Priority>1</Priority>"
            "<DeleteMarkerReplication><Status>Enabled</Status>"
            "</DeleteMarkerReplication><Destination>"
            f"<Bucket>{dst}</Bucket><Endpoint>{lc.urls[idx]}"
            "</Endpoint></Destination></Rule>"
            "</ReplicationConfiguration>")
        r = cl.request("PUT", f"/{src}", query={"replication": ""},
                       body=xml.encode())
        out["rule_set"] = r.status_code == 200
        kill_at = rec_t0 + profile.duration_s * profile.chaos_kill_at_frac
        restart_at = rec_t0 + profile.duration_s * \
            profile.chaos_restart_at_frac
        acked: dict[str, str] = {}
        seq = 0
        killed = restarted = False
        part_rule: str | None = None
        while time.monotonic() < deadline or (killed and not restarted):
            now = time.monotonic()
            if not killed and now >= kill_at:
                if profile.replication_partition:
                    from minio_tpu.fault import node as fault_node
                    part_rule = fault_node.partition(lc.urls[idx])
                else:
                    lc.kill(idx)
                out["target_down_at_s"] = round(now - rec_t0, 3)
                killed = True
                continue
            if killed and not restarted and now >= restart_at:
                if part_rule is not None:
                    from minio_tpu import fault
                    fault.disarm(part_rule)
                else:
                    lc.restart(idx)
                out["target_rejoined_at_s"] = round(
                    time.monotonic() - rec_t0, 3)
                restarted = True
                continue
            body = hashlib.sha256(f"replica{seq}".encode()).digest() * 32
            key = f"repl/k{seq:06d}"
            try:
                r = cl.request("PUT", f"/{src}/{key}", body=body)
                if r.status_code == 200:
                    acked[key] = hashlib.md5(body).hexdigest()
            except Exception:  # noqa: BLE001 — unacked: no obligation
                out["unacked_writes"] = out.get("unacked_writes", 0) + 1
            seq += 1
        out["acked_writes"] = len(acked)
        out["_acked"] = acked

    def _replication_settle(self, profile: Profile, out: dict) -> None:
        """Post-run: wait for every live node's replication backlog
        (queued + retry-parked) to drain to zero, snapshot the lag
        report, then re-read every acknowledged source key from the
        replica bucket on the rejoined target — bit-exact."""
        import hashlib
        lc = self.topology
        idx = out.get("target_node") or 0
        t0 = time.monotonic()
        deadline = t0 + profile.replication_drain_timeout_s
        drained = False
        # rejoin normally kicks the parked debt via _on_peer_reconnect;
        # the backoff promoter drains it regardless, so this poll only
        # decides WHEN the settle moves on, never whether debt survives
        while time.monotonic() < deadline:
            backlog = 0
            for node in lc.nodes:
                srv = getattr(node, "server", None)
                rs = getattr(srv, "replication_sys", None) if srv \
                    else None
                if rs is not None:
                    st = rs.stats()
                    backlog += st["queued"] + st["retry_pending"]
            if backlog == 0:
                drained = True
                break
            time.sleep(0.25)
        out["drain_s"] = round(time.monotonic() - t0, 3)
        out["drained"] = drained
        rs0 = getattr(self.server, "replication_sys", None)
        if rs0 is not None:
            out["lag"] = rs0.lag_report()
            out["stats"] = rs0.stats()
        acked = out.pop("_acked", {})
        dst = out.get("dst", "")
        cl = _SigClient(lc.urls[idx], self.ak, self.sk)
        lost: list[str] = []
        for key, md5 in acked.items():
            try:
                r = cl.request("GET", f"/{dst}/{key}")
                ok = r.status_code == 200 and \
                    hashlib.md5(r.content).hexdigest() == md5
            except Exception:  # noqa: BLE001
                ok = False
            if not ok:
                lost.append(key)
        out["lost_replicas"] = lost[:16]
        out["lost_count"] = len(lost)

    def _arm_degraded(self) -> tuple[str, str]:
        """Kill one disk's shard READS through the production fault
        registry (writes stay healthy, so heals make progress and new
        PUTs land): every GET whose data shards touch it reconstructs
        through the dispatch plane's interactive lane. Returns
        (rule_id, disk endpoint)."""
        from minio_tpu import fault
        disks = [d for d in getattr(self.obj, "disks", []) if d is not None]
        if not disks:
            raise RuntimeError("degraded mix needs an in-process "
                               "single-set object layer")
        target = disks[-1].endpoint()
        rid = fault.arm(f"disk:{target}:read_at:error(FaultyDisk)")
        return rid, target

    def _degraded_heal_worker(self, profile: Profile, deadline: float,
                              out: dict) -> None:
        """The heal half of the interactive mix: continuously heal
        sampled preloaded keys toward the dead disk while the GET load
        reconstructs around it — both ride the interactive device
        lane."""
        rng = random.Random(profile.seed ^ 0x4EA1)
        heals = errors = 0
        while time.monotonic() < deadline:
            key = f"o{rng.randrange(profile.objects):07d}"
            try:
                self.obj.heal_object(profile.bucket, key)
                heals += 1
            except Exception:  # noqa: BLE001 — a failed heal under an
                errors += 1    # armed fault is data, not a crash
            time.sleep(0.02)
        out["heals"] = heals
        out["heal_errors"] = errors

    def _overload_probe(self, profile: Profile) -> dict:
        """Deliberately pinch the admission gate to capacity 1 and fire
        a concurrent burst so the 503 SlowDown + Retry-After contract is
        exercised every run. The handful of 503s burns a sliver of the
        interactive error budget — by design: the SLO report must show
        availability holding ABOVE target even with shedding active."""
        import os
        adm = getattr(self.server, "qos_admission", None)
        if adm is None:
            return {}
        saved = adm.max_requests
        saved_wait = os.environ.get("MINIO_TPU_QOS_MAX_WAIT_MS")
        out = {"bursts": 8, "s503": 0, "retry_after_ok": True}
        try:
            adm.reconfigure(1)
            # near-zero admission wait: with capacity 1 an 8-wide burst
            # must shed ~7 requests instead of queueing them politely
            # behind the bounded wait (in-process server reads the env
            # per admit, so this applies immediately)
            os.environ["MINIO_TPU_QOS_MAX_WAIT_MS"] = "1"
            barrier = threading.Barrier(8)

            lock = threading.Lock()

            def burst(i: int) -> None:
                cl = _SigClient(self.endpoint, self.ak, self.sk)
                barrier.wait()
                r = cl.request("GET",
                               f"/{profile.bucket}/o{i:07d}")
                if r.status_code == 503:
                    with lock:
                        out["s503"] += 1
                        if "Retry-After" not in r.headers:
                            out["retry_after_ok"] = False

            ths = [threading.Thread(target=burst, args=(i,))
                   for i in range(8)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=30)
        finally:
            if saved_wait is None:
                os.environ.pop("MINIO_TPU_QOS_MAX_WAIT_MS", None)
            else:
                os.environ["MINIO_TPU_QOS_MAX_WAIT_MS"] = saved_wait
            adm.reconfigure(saved)
        return out

    def _arm_notifier_probe(self, profile: Profile) -> dict:
        """Dead-letter event probe (ISSUE 18 satellite): register a
        webhook target nothing listens on, give its persistent queue a
        deliberately tiny limit, and route every load bucket's object
        events at it. The measured phase then proves the queue-full
        contract under real traffic: depth caps at the limit, every
        overflow increments ``failed_puts`` plus the exported drop
        counter, and the PUT path never blocks on the full queue."""
        import os
        import tempfile

        from minio_tpu.event.queuestore import QueueStore
        from minio_tpu.event.targets import WebhookTarget
        n = self.server.ensure_notifier()
        region = getattr(self.server, "region", "us-east-1")
        t = WebhookTarget("loadgen-dead", "http://127.0.0.1:9/dead",
                          timeout_s=0.2, region=region)
        limit = 64
        qroot = tempfile.mkdtemp(prefix="loadgen-notify-")
        # built directly (not add_targets) for the non-default limit; a
        # long retry base keeps the doomed sender quiet during the run
        store = QueueStore(os.path.join(qroot, t.KIND, t.id), t.send,
                           limit=limit, retry_base_s=5.0).start()
        n.targets[t.arn] = t
        n.stores[t.arn] = store
        xml = (
            "<NotificationConfiguration><QueueConfiguration>"
            f"<Queue>{t.arn}</Queue><Event>s3:ObjectCreated:*</Event>"
            "<Event>s3:ObjectRemoved:*</Event>"
            "</QueueConfiguration></NotificationConfiguration>").encode()
        for bi in range(max(1, profile.buckets)):
            b = profile.bucket_name(bi)
            self.server.bucket_meta.update(b, notification_xml=xml)
            n.invalidate(b)
        return {"arn": t.arn, "limit": limit, "store": store}

    # -- the run --------------------------------------------------------------

    def run(self, profile: Profile) -> dict:
        from minio_tpu.obs import slo
        if profile.chaos_kill_node is not None and \
                getattr(self, "topology", None) is not None:
            n_nodes = len(self.topology.nodes)
            if not 0 < profile.chaos_kill_node < n_nodes:
                # node 0 serves ALL the load (ledger writer, health
                # sampling, settle-phase verification) — killing it, or
                # a node that doesn't exist, would produce misleading
                # red verdicts instead of an operator error
                raise ValueError(
                    f"chaos_kill_node must be 1..{n_nodes - 1} "
                    "(node 0 is the load endpoint)")
        if profile.replication_target_node is not None:
            if getattr(self, "topology", None) is None:
                raise ValueError(
                    "the replication phase needs --topology > 1 "
                    "(a real target node to ship to)")
            n_nodes = len(self.topology.nodes)
            if not 0 < profile.replication_target_node < n_nodes:
                raise ValueError(
                    f"replication_target_node must be 1..{n_nodes - 1} "
                    "(node 0 serves the source load)")
        body = random.Random(profile.seed + 1).randbytes(
            profile.value_bytes)
        preload_s = self.preload(profile)
        # the overload probe runs BEFORE the measured phase and the SLO
        # reset: its ~7 deliberately-induced 503s prove the SlowDown +
        # Retry-After contract without burning the measured run's
        # availability (operators pinching capacity on purpose is not
        # an SLO incident)
        probe: dict = {}
        if profile.overload_probe and self.server is not None:
            probe = self._overload_probe(profile)
        # degraded-GET + heal interactive mix (ISSUE 13): armed AFTER
        # the probe, measured by the run — the SLO reset below means
        # the interactive class's burn rates judge the latency tier
        # under reconstruct traffic, not setup noise
        degraded: dict = {}
        degraded_rule = None
        if profile.degraded:
            if getattr(self, "topology", None) is not None:
                raise ValueError(
                    "the degraded mix runs on the single-node form "
                    "(node-level faults are --chaos-kill's job)")
            from minio_tpu.storage.xlmeta import SMALL_FILE_THRESHOLD
            if profile.value_bytes <= SMALL_FILE_THRESHOLD:
                raise ValueError(
                    "degraded mix needs value_bytes > "
                    f"{SMALL_FILE_THRESHOLD} (inlined objects never "
                    "read shards, so nothing would reconstruct)")
            degraded_rule, degraded["disk"] = self._arm_degraded()
            from minio_tpu.runtime import dispatch as dp
            degraded["_ia0"] = dp._global.stats()[
                "interactive_lane"]["items"] if dp._global else 0
        # bounded event fan-out under load (ISSUE 18): armed after the
        # overload probe so only measured-phase traffic hits the dead
        # target's tiny queue
        notifier_arm: dict = {}
        if profile.notifier_probe and self.server is not None and \
                getattr(self, "topology", None) is None:
            notifier_arm = self._arm_notifier_probe(profile)
        try:
            slo.reset()                  # measure THIS run, not setup
            lockrank_before = self._lockrank_count()
            rec = _Recorder(time.monotonic())
            # whole-run profiler window (ISSUE 14): subsystem shares +
            # top contended locks ride the report as `host_profile`.
            # A DELTA over the always-on base aggregate, not an
            # attached capture — the measured run must pay nothing
            # beyond the standing base rate (a 97 Hz attached capture
            # once stretched the scanner cycle ~10x on a saturated
            # 1-core host)
            from minio_tpu.obs import profiler as _prof
            run_snap = _prof.agg_snapshot()
            # steady-state compile oracle (ISSUE 16): every kernel the
            # measured phase needs must already be compiled — preload
            # plus the probe are the warm-up, so any compile counted
            # past this point is a shape leak on the hot path
            from minio_tpu.obs import device as _dev
            compiles0 = _dev.compiles_total()
            deadline = rec.t0 + profile.duration_s
            scanner_win: dict = {}
            scan_t: threading.Thread | None = None
            if profile.scanner_mid_run and self.server is not None:
                # spawned BEFORE the client storm, waking itself at the
                # halfway mark — see _force_scanner on why
                scan_t = threading.Thread(
                    target=self._force_scanner,
                    args=(rec.t0, scanner_win,
                          rec.t0 + profile.duration_s / 2),
                    daemon=True, name="loadgen-scanner")
                scan_t.start()
            ths = self._closed_loop(profile, rec, deadline, body)
            open_t = self._open_loop(profile, rec, deadline, body)
            heal_t: threading.Thread | None = None
            if profile.degraded:
                heal_t = threading.Thread(
                    target=self._degraded_heal_worker,
                    args=(profile, deadline, degraded),
                    daemon=True, name="loadgen-degraded-heal")
                heal_t.start()
            chaos: dict = {}
            chaos_t: threading.Thread | None = None
            if profile.chaos_kill_node is not None and \
                    getattr(self, "topology", None) is not None:
                chaos_t = threading.Thread(
                    target=self._chaos_phase,
                    args=(profile, rec.t0, deadline, chaos),
                    daemon=True, name="loadgen-chaos")
                chaos_t.start()
            repl: dict = {}
            repl_t: threading.Thread | None = None
            if profile.replication_target_node is not None and \
                    getattr(self, "topology", None) is not None:
                repl_t = threading.Thread(
                    target=self._replication_phase,
                    args=(profile, rec.t0, deadline, repl),
                    daemon=True, name="loadgen-replication")
                repl_t.start()
            for t in ths:
                t.join(timeout=profile.duration_s + 60)
            if open_t is not None:
                open_t.join(timeout=profile.duration_s + 60)
            wall_s = time.monotonic() - rec.t0
            if scan_t is not None:
                scan_t.join(timeout=180)
            if chaos_t is not None:
                chaos_t.join(timeout=profile.duration_s + 120)
                self._chaos_settle(profile, chaos)
            if repl_t is not None:
                repl_t.join(timeout=profile.duration_s + 120)
                self._replication_settle(profile, repl)
            if heal_t is not None:
                heal_t.join(timeout=profile.duration_s + 60)
            if degraded_rule is not None:
                from minio_tpu.runtime import dispatch as dp
                ia_now = dp._global.stats()[
                    "interactive_lane"]["items"] if dp._global else 0
                degraded["interactive_lane_items"] = \
                    ia_now - degraded.pop("_ia0", 0)
            notifier: dict = {}
            if notifier_arm:
                st = notifier_arm["store"]
                notifier = {
                    "arn": notifier_arm["arn"],
                    "limit": notifier_arm["limit"],
                    "queue_count": st._count,
                    "delivered": st.delivered,
                    "failed_puts": st.failed_puts,
                    "send_failures": st.send_failures,
                }
            return self._report(profile, rec, wall_s, preload_s,
                                scanner_win, probe, lockrank_before,
                                chaos, degraded,
                                _prof.delta_report(run_snap),
                                compiles0, notifier, repl)
        finally:
            # the armed disk-kill rule is PROCESS-WIDE state: a failure
            # anywhere in the measured phase must not leave every later
            # GET in this process hitting FaultyDisk
            if degraded_rule is not None:
                from minio_tpu import fault
                fault.disarm(degraded_rule)
            if notifier_arm:
                # detach the dead target so nothing keeps retrying it
                # (and a later phase on this server starts clean)
                n = self.server._notifier
                if n is not None:
                    n.targets.pop(notifier_arm["arn"], None)
                    n.stores.pop(notifier_arm["arn"], None)
                notifier_arm["store"].stop()

    @staticmethod
    def _lockrank_count() -> int | None:
        try:
            from minio_tpu.obs import lockrank
            return len(lockrank.reports())
        except Exception:  # noqa: BLE001 — lockrank not installed
            return None

    def _scrape_metrics(self) -> str:
        try:
            import requests
            return requests.get(self.endpoint + "/minio/v2/metrics",
                                timeout=10).text
        except Exception:  # noqa: BLE001
            return ""

    def _report(self, profile: Profile, rec: _Recorder, wall_s: float,
                preload_s: float, scanner_win: dict, probe: dict,
                lockrank_before: int | None,
                chaos: dict | None = None,
                degraded: dict | None = None,
                run_prof=None,
                compiles0: int | None = None,
                notifier: dict | None = None,
                repl: dict | None = None) -> dict:
        from minio_tpu.obs import slo
        from minio_tpu.obs.health import cluster_snapshot
        rows = rec.snapshot()
        overall = _op_rollup(rows)
        total = sum(o["count"] for o in overall["ops"].values())
        s503 = sum(o["s503"] for o in overall["ops"].values())
        s503_ra = sum(o["s503_retry_after"]
                      for o in overall["ops"].values())
        # scanner attribution: the cycle window vs the surrounding
        # baseline — a breach is "attributable" only when the hot path
        # got materially worse INSIDE the window
        scanner_impact: dict = {}
        if scanner_win.get("start_s") is not None:
            last_ts = max((r[0] for r in rows), default=0.0)
            # clamp to the measured phase: a cycle that outlives it
            # (CPU-starved behind interactive traffic — the desired
            # priority) is judged on its in-run overlap. Not to the last
            # row: the clients stop issuing at duration_s, and a LIST
            # that began inside the cycle can end with it, many seconds
            # later — a window stretched to that row divides the
            # completions by seconds in which no client was running and
            # reads as a collapsed rate in every run
            win = (scanner_win["start_s"],
                   min(scanner_win.get("end_s", last_ts), last_ts,
                       profile.duration_s))
            during = _op_rollup(rows, win)["classes"].get(
                "interactive", {})
            # baseline = the STEADY half of the pre-scanner phase: the
            # first seconds of a closed loop are queue ramp-up (64
            # clients fire at once, latency climbs toward steady
            # state), and comparing the scanner window against the
            # ramp would misattribute that climb to the scanner
            before = _op_rollup(
                rows, (win[0] / 2, win[0]))["classes"].get(
                "interactive", {})
            thresh = slo.objective("interactive")["latency_threshold_s"]
            d_avail = during.get("availability", 1.0)
            # p50-based attribution: a scanner genuinely stalling the
            # hot path (holding a namespace lock, hogging the dispatch
            # queue) shifts the MEDIAN, while p99 on a contended CI
            # host is pure tail noise at these sample counts
            d_p50 = during.get("p50_ms", 0.0) / 1e3
            b_p50 = before.get("p50_ms", 0.0) / 1e3
            # ... corroborated by throughput: under a closed loop the
            # median tracks queue depth, which climbs with time on a
            # saturated host whether or not the scanner runs (Little's
            # law: p50 ~= clients/rps) — but a scanner really stalling
            # the path collapses the in-window completion rate, while
            # queueing drift leaves it flat. Both signals or no blame.
            d_rps = during.get("count", 0) / max(win[1] - win[0], 1e-9)
            b_rps = before.get("count", 0) / max(win[0] / 2, 1e-9)
            attributable = (
                during.get("count", 0) >= 10 and (
                    d_avail < min(0.99,
                                  before.get("availability", 1.0)) or
                    (d_p50 > max(thresh, 4.0 * b_p50) and
                     d_rps < 0.7 * b_rps)))
            scanner_impact = {
                "window": scanner_win,
                "during": during, "before": before,
                "during_rps": round(d_rps, 1),
                "before_rps": round(b_rps, 1),
                "latency_threshold_s": thresh,
                "attributable_breach": attributable,
            }
        lockrank_after = self._lockrank_count()
        # class evidence: the admission plane's per-class admit counts
        # (interactive traffic WAS classed and gated), the scanner
        # cycle counter (the background work DID run — scan_cycle
        # itself applies qos.background()), and — when the payload size
        # engages the dispatch queue — the scheduler's per-class item
        # and spill counters
        qos_evidence: dict = {}
        if self.server is not None:
            adm = getattr(self.server, "qos_admission", None)
            if adm is not None:
                qos_evidence["admitted"] = adm.stats().get("admitted", {})
            from minio_tpu.obs.metrics import counters_snapshot
            qos_evidence["scanner_cycles"] = {
                k: v for k, v in counters_snapshot().items()
                if k.startswith("minio_tpu_scanner_cycles_total")}
            from minio_tpu.runtime import dispatch as dp
            if dp._global is not None:
                st = dp._global.qos.stats()
                qos_evidence["class_items"] = st.get("class_items", {})
                qos_evidence["spill_reasons"] = st.get(
                    "spill_reasons", {})
        metrics_text = self._scrape_metrics()
        slo_rep = slo.report()
        inter = overall["classes"].get("interactive", {})
        # whole-run profile summary (ISSUE 14): subsystem shares + top
        # contended lock sites — where the run's host CPU actually
        # went (a delta report over the always-on base sampler)
        host_profile: dict = {}
        if run_prof is not None:
            host_profile = {
                **run_prof,
                "scanner_cpu_share": scanner_win.get(
                    "profile", {}).get("scanner_cpu_share", 0.0),
                "scanner_share_max": profile.scanner_share_max,
            }
        verdicts = {
            "interactive_availability_ok":
                inter.get("availability", 1.0) >= 0.99,
            "retry_after_on_503": s503 == 0 or s503_ra == s503,
            "overload_probe_fired": not probe or probe.get("s503", 0) > 0,
            "scanner_no_hot_path_breach":
                not scanner_impact or
                not scanner_impact["attributable_breach"],
            # the item-3 claim made machine-checked (ISSUE 14): the
            # scanner-cycle window's scanner-subsystem CPU share stays
            # under the configured bound (trivially green when the
            # cycle was too fast to sample)
            "scanner_cpu_share_ok":
                scanner_win.get("profile", {}).get(
                    "scanner_cpu_share", 0.0) <=
                profile.scanner_share_max,
            "lockrank_clean": lockrank_before is None or
                lockrank_after == lockrank_before,
            "burn_rate_metrics_live":
                "minio_tpu_slo_burn_rate" in metrics_text,
        }
        # per-bucket analytics acceptance (ISSUE 18): however many
        # tenants the spread drove, the scrape's bucket-label value set
        # stays within top_n tracked rows plus the `_overflow_` fold.
        # The bandwidth family is excluded: its rows are config-derived
        # (one per operator-configured replication limit — the global
        # monitor outlives any one server in-process), bounded by
        # configuration rather than tenant traffic
        from minio_tpu.obs import bucketstats as _bstats
        bucket_labels: set[str] = set()
        for line in metrics_text.splitlines():
            if line.startswith("minio_tpu_bucket_") and \
                    not line.startswith("minio_tpu_bucket_bandwidth_") \
                    and 'bucket="' in line:
                bucket_labels.add(
                    line.split('bucket="', 1)[1].split('"', 1)[0])
        verdicts["bucket_metrics_bounded_ok"] = \
            len(bucket_labels) <= _bstats.top_n() + 1
        # every breached (class, window-kind) must carry burn
        # attribution naming an offending bucket — vacuously green on a
        # clean run, red the moment a breach fires with an empty
        # top_buckets list
        breach_named = True
        for ent in slo_rep.get("classes", {}).values():
            for kind, hit in ent.get("breach", {}).items():
                if hit and not ent.get("top_buckets", {}).get(kind):
                    breach_named = False
        verdicts["slo_breach_names_bucket_ok"] = breach_named
        if notifier:
            # bounded event fan-out: events really routed at the dead
            # target, the queue never grew past its limit, and any
            # overflow was counted (store counter + exported metric),
            # never silently dropped
            routed = (notifier["queue_count"] + notifier["delivered"] +
                      notifier["failed_puts"])
            verdicts["notifier_bounded_ok"] = (
                routed > 0 and
                notifier["queue_count"] <= notifier["limit"] and
                (notifier["failed_puts"] == 0 or
                 "minio_tpu_notify_events_dropped_total" in metrics_text))
        if degraded:
            # the degraded-mix acceptance set (ISSUE 13): GETs really
            # served through reconstruct on the interactive device
            # lane, the heal mix really ran concurrently, and the
            # interactive class held its availability through it —
            # the latency tier judged by its own burn rates
            verdicts["degraded_reconstructs_served"] = \
                degraded.get("interactive_lane_items", 0) > 0
            verdicts["degraded_heal_mix_ran"] = \
                degraded.get("heals", 0) > 0
            verdicts["degraded_interactive_availability_ok"] = \
                inter.get("availability", 1.0) >= 0.99
        if chaos:
            # the node-chaos acceptance set (ISSUE 12): the kill was
            # DETECTED, nothing acknowledged was lost, the heal
            # backlog drained after rejoin, and the background class
            # kept its availability SLO through the whole run
            bg_breach = slo_rep.get("classes", {}).get(
                "background", {}).get("breach", {})
            verdicts["node_unreachable_detected"] = \
                chaos.get("detected_unreachable", False)
            verdicts["no_acked_write_loss"] = (
                chaos.get("acked_writes", 0) > 0 and
                chaos.get("lost_count", 1) == 0)
            verdicts["heal_backlog_drained"] = \
                chaos.get("heal_drained", False)
            verdicts["background_slo_availability_ok"] = \
                not bg_breach.get("availability", False)
        if repl:
            # the replication-chaos acceptance set (ISSUE 19): every
            # acknowledged source write survived the target outage as
            # a bit-exact replica (the obligation parked in the retry
            # journal and shipped after rejoin — never dropped), the
            # replication backlog really drained to zero, and the
            # replication-lag SLO (obs.slo async probe) held at p99
            verdicts["no_replica_obligation_lost"] = (
                repl.get("acked_writes", 0) > 0 and
                repl.get("lost_count", 1) == 0)
            verdicts["replication_backlog_drained"] = \
                repl.get("drained", False)
            verdicts["replication_lag_slo_ok"] = \
                repl.get("lag", {}).get("ok", False)
        if compiles0 is not None and not degraded and not chaos \
                and not repl:
            # steady-state compile oracle (ISSUE 16): zero compiles in
            # the measured phase — a positive delta means a kernel
            # shape the warm-up never saw landed on the hot path.
            # Skipped for degraded/chaos/replication runs: their
            # mid-run fault pivots (first reconstruct, rejoin heal,
            # post-rejoin backlog ship) legitimately compile fresh
            # kernels
            from minio_tpu.obs import device as _dev
            steady_compiles = _dev.compiles_total() - compiles0
            verdicts["no_steady_state_compiles"] = steady_compiles == 0
        verdicts["passed"] = all(verdicts.values())
        return {
            "profile": {
                "objects": profile.objects,
                "clients": profile.clients,
                "duration_s": profile.duration_s,
                "mix": profile.mix,
                "value_bytes": profile.value_bytes,
                "open_rps": profile.open_rps,
                "ramp_s": profile.ramp_s,
                "buckets": profile.buckets,
            },
            "wall_s": round(wall_s, 3),
            "preload_s": round(preload_s, 3),
            "requests_total": total,
            "rps": round(total / wall_s, 1) if wall_s else 0.0,
            "s503_total": s503,
            "s503_with_retry_after": s503_ra,
            "per_op": overall["ops"],
            "per_class": overall["classes"],
            "scanner": scanner_impact,
            "overload_probe": probe,
            "node_chaos": chaos or {},
            "replication": repl or {},
            "degraded": degraded or {},
            "qos_evidence": qos_evidence,
            "host_profile": host_profile,
            "notifier_probe": notifier or {},
            "bucket_stats": {
                "series_label_values": len(bucket_labels),
                "top_n": _bstats.top_n(),
                "tracked": _bstats.report().get("tracked", 0),
                "folds_total": _bstats.report().get("folds", 0),
            },
            "slo": slo_rep,
            "health": cluster_snapshot(self.server, peers=False)
            if self.server is not None else {},
            "verdicts": verdicts,
        }


def run_tier1_profile(root: str, profile: Profile | None = None) -> dict:
    """The ISSUE 10 acceptance profile: in-process server, >=1k objects,
    >=64 concurrent mixed clients, one scanner cycle forced mid-run.
    Returns the report (``report["verdicts"]["passed"]`` is the
    gate)."""
    lg = LoadGen.inprocess(root)
    try:
        return lg.run(profile or Profile.tier1())
    finally:
        lg.close()


def run_topology_profile(root: str, profile: Profile | None = None,
                         nodes: int = 4, disks_per_node: int = 2,
                         parity: int = 2) -> dict:
    """The ISSUE 12 node-chaos profile (``--topology N``): mixed load
    against a real N-node in-process cluster; with
    ``profile.chaos_kill_node`` set, one node is killed mid-run and
    restarted later, and the verdicts block gates on unreachable
    detection, zero acknowledged-write loss, heal-backlog drain and
    the background availability SLO."""
    lg = LoadGen.cluster(root, nodes=nodes,
                         disks_per_node=disks_per_node, parity=parity)
    try:
        return lg.run(profile or Profile.tier1())
    finally:
        lg.close()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="mixed-workload SLO scale harness")
    ap.add_argument("--objects", type=int, default=1000)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--value-bytes", type=int, default=4096)
    ap.add_argument("--open-rps", type=float, default=50.0)
    ap.add_argument("--ramp", type=float, default=2.0)
    ap.add_argument("--no-scanner", action="store_true")
    ap.add_argument("--scanner-share-max", type=float, default=0.5,
                    help="max scanner-subsystem CPU share inside the "
                    "forced cycle window (profiler evidence; the "
                    "scanner_cpu_share_ok verdict gates on it)")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--buckets", type=int, default=1,
                    help="spread the key space across N buckets "
                    "(per-bucket analytics plane under multi-tenant "
                    "load; the bucket_metrics_bounded_ok verdict "
                    "proves the scrape stays at top_n+1 labels)")
    ap.add_argument("--no-notifier-probe", action="store_true",
                    help="skip the dead-webhook bounded-queue probe")
    ap.add_argument("--degraded", action="store_true",
                    help="kill one disk's shard reads for the measured "
                    "phase: GETs reconstruct on the interactive device "
                    "lane while a heal worker rebuilds concurrently "
                    "(needs --value-bytes > 131072)")
    ap.add_argument("--topology", type=int, default=1,
                    help="run against an in-process N-node cluster")
    ap.add_argument("--disks-per-node", type=int, default=2)
    ap.add_argument("--chaos-kill", type=int, default=-1, metavar="NODE",
                    help="kill this node index mid-run and restart it "
                    "(needs --topology > 1)")
    ap.add_argument("--replicate-to", type=int, default=-1,
                    metavar="NODE",
                    help="replication-chaos phase: replicate a source "
                    "bucket to this node index and kill it mid-stream, "
                    "then prove no replica obligation was lost after "
                    "rejoin (needs --topology > 1)")
    ap.add_argument("--replication-partition", action="store_true",
                    help="partition the replication target's RPC plane "
                    "instead of killing the process")
    ap.add_argument("--out", default="", help="write the report JSON")
    args = ap.parse_args(argv)
    import tempfile

    profile = Profile(
        objects=args.objects, clients=args.clients,
        duration_s=args.duration, value_bytes=args.value_bytes,
        open_rps=args.open_rps, ramp_s=args.ramp,
        scanner_mid_run=not args.no_scanner,
        scanner_share_max=args.scanner_share_max,
        overload_probe=not args.no_probe,
        buckets=args.buckets,
        notifier_probe=not args.no_notifier_probe,
        degraded=args.degraded,
        chaos_kill_node=args.chaos_kill if args.chaos_kill >= 0
        else None,
        replication_target_node=args.replicate_to
        if args.replicate_to >= 0 else None,
        replication_partition=args.replication_partition)
    with tempfile.TemporaryDirectory(prefix="loadgen-") as root:
        if args.topology > 1:
            report = run_topology_profile(
                root, profile, nodes=args.topology,
                disks_per_node=args.disks_per_node)
        else:
            report = run_tier1_profile(root, profile)
    blob = json.dumps(report, indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(blob + "\n")
    print(blob)
    return 0 if report["verdicts"]["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
