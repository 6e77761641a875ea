"""Data scanner (reference cmd/data-scanner.go:65): periodic namespace
crawl with per-object throttling; refreshes data-usage accounting, applies
lifecycle rules, probabilistically verifies object health (every
``deep_every``-th cycle runs a deep bitrot scan — dataScannerCompactLeastObject
/ healDeepScanCycleMultiplier analogue) and queues degraded objects for
heal."""
from __future__ import annotations

import threading
import time

from . import usage as usage_mod

DEEP_SCAN_EVERY = 16  # healDeepScanCycleMultiplier (cmd/data-scanner.go:48)


class DataScanner:
    def __init__(self, objlayer, interval_s: float = 60.0,
                 mrf=None, lifecycle=None, sleep_per_object: float = 0.001,
                 compact_least: int | None = None, replication=None):
        self.obj = objlayer
        self.interval = interval_s
        self.mrf = mrf
        self.lifecycle = lifecycle
        #: optional bucket.replicate.ReplicationSys — the cycle
        #: re-charges objects stuck PENDING/FAILED (missed charge,
        #: exhausted retries, debt shed under queue overflow)
        self.replication = replication
        self.sleep_per_object = sleep_per_object
        self.compact_least = usage_mod.COMPACT_LEAST \
            if compact_least is None else compact_least
        self.compact_max_nodes = usage_mod.MAX_NODES
        self.cycle = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.last_usage: dict = {}
        # persist the update tracker beside the first local disk's system
        # state so skip-state survives restarts (reference
        # cmd/data-update-tracker.go periodic save + load); _all_disks
        # resolves every layer shape (single set, sets, pools, FS)
        from ..obs.metrics import _all_disks
        from .tracker import global_tracker
        try:
            import os as _os
            disk = next(d for d in _all_disks(objlayer)
                        if getattr(d, "base", ""))
            from ..storage.xlstorage import META_BUCKET
            global_tracker().attach_persistence(
                _os.path.join(disk.base, META_BUCKET, "tracker.bin"))
        except StopIteration:
            pass
        # crash-residue janitor (docs/durability.md): aged tmp + stale
        # multipart every cycle, namespace reconcile on deep cycles
        from .janitor import DurabilityJanitor
        self.janitor = DurabilityJanitor(objlayer)

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="data-scanner")
        self._thread.start()
        return self

    def _loop(self):
        while not self._stop.wait(self.interval):
            try:
                self.scan_cycle()
            except Exception as e:  # noqa: BLE001 — scanner must never
                # die, but also never fail silently (graftlint GL007)
                from ..obs.logger import log_sys
                log_sys().log_once(
                    f"scanner:{type(e).__name__}", "warning", "scanner",
                    f"scan cycle failed: {e!r}")

    def scan_cycle(self) -> dict:
        """One crawl; returns the usage snapshot (also persisted). Buckets
        untouched since the last sweep (per the update tracker) reuse their
        previous stats instead of re-walking — the bloom-filter skip of
        cmd/data-update-tracker.go. Deep-scan cycles always walk.

        Always runs as QoS class ``background`` — applied HERE rather
        than in the periodic loop so a directly-forced cycle (admin
        trigger, tests) gets the same
        spill-first dispatch treatment as a scheduled one and can never
        stall interactive traffic by omission."""
        from .. import qos
        with qos.background():
            return self._scan_cycle_inner()

    def _scan_cycle_inner(self) -> dict:
        from ..obs import metrics as mx
        from ..obs import trace as trc
        from .tracker import global_tracker
        self.cycle += 1
        deep = (self.cycle % DEEP_SCAN_EVERY == 0)
        mx.inc("minio_tpu_scanner_cycles_total",
               deep=str(deep).lower())
        t_cycle = time.perf_counter()
        try:
            # cheap jobs (aged tmp sweep + stale multipart expiry) every
            # cycle; the O(namespace) ddir/quarantine reconcile only on
            # deep cycles — the same cadence as the bitrot verify walk
            self.janitor.sweep(reconcile=deep)
        except Exception as e:  # noqa: BLE001 — best-effort, but a
            # janitor failing every cycle must be visible (GL007 spirit)
            from ..obs.logger import log_sys
            log_sys().log_once(
                f"janitor:{type(e).__name__}", "warning", "scanner",
                f"durability sweep failed: {e!r}")
        tracker = global_tracker()
        gen = tracker.begin_cycle()
        prev_buckets = self.last_usage.get("buckets", {}) \
            if self.last_usage else usage_mod.load_usage(
                self.obj).get("buckets", {})
        buckets = {}
        total_objects = total_size = 0
        for b in self.obj.list_buckets():
            prev = prev_buckets.get(b.name)
            # the skip is only legal when no time-based actions are
            # configured — lifecycle rules must evaluate every cycle even
            # with zero writes (expiry/transition trigger on age)
            has_lifecycle = self.lifecycle is not None and \
                bool(self.lifecycle.rules_for(b.name))
            # same rule for replication: PENDING/FAILED debt must be
            # re-found even when the bucket saw zero new writes
            has_replication = self.replication is not None and \
                bool(self.replication.rules_for(b.name))
            if prev is not None and not deep and not has_lifecycle and \
                    not has_replication and \
                    not tracker.bucket_dirty(b.name):
                buckets[b.name] = prev
                total_objects += prev.get("objects", 0)
                total_size += prev.get("size", 0)
                continue
            count = size = versions = 0
            tree = usage_mod.UsageTree()
            # one streaming metacache pass per bucket — no paging restarts
            # (cmd/data-scanner.go crawls the disks directly the same way)
            for oi in self.obj.iter_objects(b.name):
                if self._stop.is_set():
                    return self.last_usage
                nv = max(1, oi.num_versions)
                count += 1
                size += oi.size
                versions += nv
                # hierarchical per-folder tree (cmd/data-usage-cache.go),
                # compacted + persisted below
                tree.add(oi.name, oi.size, nv)
                mx.inc("minio_tpu_scanner_objects_scanned_total")
                mx.inc("minio_tpu_scanner_bytes_scanned_total", oi.size)
                self._check_object(b.name, oi, deep)
                if self.sleep_per_object:
                    time.sleep(self.sleep_per_object)
            tree.compact(self.compact_least, self.compact_max_nodes)
            try:
                usage_mod.save_tree(self.obj, b.name, tree)
            except Exception:  # noqa: BLE001 — accounting is best-effort
                pass
            buckets[b.name] = {"objects": count, "size": size,
                               "versions": versions,
                               "prefixes": tree.prefixes(1),
                               "histogram": tree.histogram()}
            total_objects += count
            total_size += size
        tracker.end_cycle(gen)
        snapshot = {"last_update": time.time(),
                    "objects_total": total_objects,
                    "size_total": total_size, "buckets": buckets,
                    "cycle": self.cycle, "deep": deep}
        try:
            usage_mod.save_usage(self.obj, snapshot)
        except Exception:  # noqa: BLE001
            pass
        try:
            # snap the per-bucket live usage deltas back to this
            # authoritative tree (drift measured + zeroed) and feed the
            # capacity-projection history (obs/bucketstats)
            from ..obs import bucketstats
            bucketstats.reconcile(snapshot, objlayer=self.obj)
        except Exception:  # noqa: BLE001 — accounting is best-effort
            pass
        trc.publish_scanner(func="scanner.cycle",
                            path=f"cycle={self.cycle} deep={deep}",
                            duration_s=time.perf_counter() - t_cycle,
                            input_bytes=total_size)
        self.last_usage = snapshot
        return snapshot

    def _check_object(self, bucket: str, oi, deep: bool):
        # lifecycle first: expired objects need no heal
        if self.lifecycle is not None:
            try:
                if self.lifecycle.apply(bucket, oi):
                    return
            except Exception:  # noqa: BLE001
                pass
        # replication sweep: anything still PENDING/FAILED re-charges
        # (the safety net under the journal — reference the scanner's
        # queueReplicationHeal pass in cmd/data-scanner.go)
        if self.replication is not None:
            try:
                self.replication.sweep(bucket, oi)
            except Exception:  # noqa: BLE001
                pass
        if deep and self.mrf is not None:
            try:
                res = self.obj.heal_object(bucket, oi.name, dry_run=True,
                                           scan_mode="deep")
                if any(s != "ok" for s in res.before_state):
                    self.mrf.add_partial(bucket, oi.name, "",
                                         scan_mode="deep")
            except Exception:  # noqa: BLE001
                pass

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
