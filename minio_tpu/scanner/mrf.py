"""MRF — "most recently failed" heal queue (reference cmd/erasure.go:74
mrfOpCh + addPartial, cmd/erasure-object.go:1132): operations that detect a
partial/degraded write or read enqueue the object here; a background worker
heals them. Queue is bounded and drop-oldest (heal is best-effort; the
scanner sweeps anything missed).

PR 6: the queue optionally persists to a small journal
(``attach_persistence``) committed through ``durable_replace``, so heal
debt recorded before a crash is re-enqueued after reconstruction instead
of waiting for the next deep scanner cycle to rediscover it.

ISSUE 19: the queue + backoff-park + journal machinery is the shared
``scanner.park.DebtQueue`` — the replication plane
(``bucket/replicate.py``) runs the SAME implementation for replication
debt, so drop-oldest, forget-on-delete and kick-on-peer-reconnect can
never diverge between the two async planes. This module keeps the heal
worker (what "paying the debt" means for heal) and the MRF-specific
retry policy knobs."""
from __future__ import annotations

import os
import threading

from ..obs import metrics as mx
from ..obs import trace as trc
from .park import FLUSH_INTERVAL_S, DebtQueue  # noqa: F401 — re-export

#: a heal that left a shard missing or corrupt on a drive that answers
#: re-enqueues with exponential backoff instead of being forgotten. A
#: heal whose only unpaid drives are OFFLINE (a dead drive, a whole
#: node down) is not retried against them: it waits in the offline
#: park, with no timer and no attempt count, until the drive's
#: re-online (``release``) or the peer's reconnect (``kick``)
RETRY_MAX = 8
RETRY_BASE_S = float(os.environ.get("MINIO_TPU_MRF_RETRY_BASE_S", "1.0"))
RETRY_CAP_S = 30.0


class _IncompleteHeal(Exception):
    """A heal pass finished but a drive that answers stayed missing or
    corrupt — the debt is unpaid (routes the result into the retry
    park)."""


def _endpoint(disk) -> str:
    try:
        return disk.endpoint() if disk is not None else ""
    except Exception:  # noqa: BLE001 — a dying RPC proxy
        return ""


def _debt_moot(e: BaseException) -> bool:
    """The object/bucket no longer exists: nothing to heal, retrying
    would only ladder through the full backoff for a churn-deleted
    key. (Typed object errors from objectlayer.datatypes.)"""
    return type(e).__name__ in ("ObjectNotFound", "VersionNotFound",
                                "BucketNotFound")


class MRFHealer:
    def __init__(self, objlayer, max_queue: int = 10_000):
        self.obj = objlayer
        self.dq = DebtQueue(max_queue=max_queue, mode_field="scan_mode",
                            sticky_modes=("deep",),
                            dropped_metric="minio_tpu_mrf_dropped_total")
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.healed = 0
        self.failed = 0

    # the queue internals stay addressable where they always were —
    # chaos tests and the heal metrics group reach through these
    @property
    def q(self):
        return self.dq.q

    @property
    def dropped(self) -> int:
        return self.dq.dropped

    @property
    def _persist_path(self):
        return self.dq._persist_path

    @_persist_path.setter
    def _persist_path(self, path):
        self.dq._persist_path = path

    def add_partial(self, bucket: str, object: str, version_id: str = "",
                    scan_mode: str = "normal", missed=None) -> str:
        """scan_mode='deep' when the enqueuer saw bitrot (a normal heal's
        size-only check would classify the disk as healthy). Overflow is
        drop-oldest; every lost entry counts in
        ``minio_tpu_mrf_dropped_total`` and ``stats()['dropped']``.

        ``missed``: the drives (None for an empty slot) a write did not
        reach. If every one of them is offline now there is nothing a
        heal pass could do, so the debt is parked against them at once,
        one entry a key. Returns what became of the charge: ``queued``
        or ``parked_known``."""
        if missed and self._wait_for(
                (bucket, object, version_id, scan_mode),
                [_endpoint(d) for d in missed]):
            return "parked_known"
        self.dq.add(bucket, object, version_id, mode=scan_mode)
        return "queued"

    def _wait_for(self, item: tuple, endpoints) -> bool:
        """Park ``item`` until the drives at ``endpoints`` ("" = an empty
        slot) are back. False, and nothing parked, if the health tracker
        has one of them online: a drive that answers and still fails is
        not one to wait for (its debt keeps the queue and the ladder)."""
        waits = sorted(e for e in endpoints if e)
        disks = {_endpoint(d): d for d in mx._all_disks(self.obj)}
        back = lambda: [e for e in waits  # noqa: E731
                        if e in disks and disks[e].is_online()]
        if back():
            return False
        if self.dq.park_offline(item, waits):
            try:
                trc.publish_storage(node=",".join(waits), op="mrf.park",
                                    path=f"{item[0]}/{item[1]}",
                                    duration_s=0.0,
                                    error="heal debt waits for the drive")
            except Exception:  # noqa: BLE001
                pass
        # a drive that came back between the look and the park has had
        # its release already: this entry gets its own
        for e in back():
            self.release(e)
        return True

    def release(self, endpoint: str) -> int:
        """The drive at ``endpoint`` is online again: what was parked
        against it is runnable now (the health tracker's listener calls
        this beside the auto-heal kick)."""
        return self._released(self.dq.release(endpoint), endpoint)

    def _released(self, n: int, endpoint: str) -> int:
        if n:
            mx.inc("minio_tpu_mrf_released_total", n, reason="reonline")
            try:
                trc.publish_storage(node=endpoint, op="mrf.release",
                                    path="", duration_s=0.0,
                                    error=f"{n} parked heals released")
            except Exception:  # noqa: BLE001
                pass
        return n

    def attach_persistence(self, path: str, load: bool = True) -> int:
        """Point the heal queue at its on-disk journal; an existing
        file's entries are re-enqueued (restart recovery). Returns the
        number of entries recovered."""
        return self.dq.attach_persistence(path, load=load)

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="mrf-healer")
        self._thread.start()
        return self

    def stats(self) -> dict:
        return {"healed": self.healed, "failed": self.failed,
                **self.dq.stats()}

    def kick(self) -> None:
        """Promote every parked entry to runnable NOW — called when a
        peer node rejoins (rpc on_reconnect): the heal debt its absence
        created should drain immediately, whether it waited for the
        node's drives or for a backoff timer."""
        self._released(self.dq.kick(), "")

    def _loop(self):
        while not self._stop.is_set():
            entry = self.dq.pop(timeout=0.5, repark_s=RETRY_BASE_S)
            if entry is None:
                continue
            # queue entries are 4-tuples; retry promotions carry a 5th
            # element with the attempt count
            bucket, object, version_id, scan_mode = entry[:4]
            attempt = entry[4] if len(entry) > 4 else 0
            try:
                from .. import qos
                # MRF heals are background-class dispatch work;
                # remove_dangling: an object deleted while a node was
                # down leaves quorum-lost junk that can never heal —
                # purging it IS paying the debt (reference healObject
                # dangling handling)
                with qos.background():
                    res = self.obj.heal_object(bucket, object, version_id,
                                               scan_mode=scan_mode,
                                               remove_dangling=True)
                # a heal that left any drive not ok did NOT pay the
                # debt. Where every such drive is offline (a dead drive,
                # a dead node) there is nowhere to heal to: the entry
                # waits for the drive. Missing or corrupt left on a
                # drive that answers goes up the backoff ladder
                after = getattr(res, "after_state", None) or []
                unpaid = [i for i, s in enumerate(after) if s != "ok"]
                eps = getattr(res, "endpoints", None) or []
                if unpaid and all(after[i] == "offline" for i in unpaid) \
                        and self._wait_for(
                            (bucket, object, version_id, scan_mode),
                            [eps[i] for i in unpaid if i < len(eps)]):
                    mx.inc("minio_tpu_mrf_heal_attempts_total",
                           outcome="offline")
                    self.dq.flush()
                    continue
                if unpaid:
                    raise _IncompleteHeal([after[i] for i in unpaid])
                self.healed += 1
                mx.inc("minio_tpu_mrf_heal_attempts_total",
                       outcome="healed")
            except Exception as e:  # noqa: BLE001
                self.failed += 1
                moot = _debt_moot(e)
                mx.inc("minio_tpu_mrf_heal_attempts_total",
                       outcome="moot" if moot else "incomplete")
                if attempt + 1 <= RETRY_MAX and not moot:
                    # park with backoff, KEEP the journal entry: the
                    # debt must survive until the shard can be written
                    self.dq.park((bucket, object, version_id, scan_mode),
                                 attempt + 1, RETRY_BASE_S, RETRY_CAP_S)
                    self.dq.flush()
                    continue
                # retries exhausted (or the object is gone): the deep
                # scanner cycle re-finds anything still genuinely
                # degraded
            self.dq.settle((bucket, object, version_id))

    def flush_journal(self) -> None:
        """Force the persistence journal onto disk (tests/shutdown)."""
        self.dq.flush(force=True)

    def drain(self, timeout: float = 30.0):
        """Block until the queue AND the retry park are empty
        (tests / shutdown)."""
        self.dq.drain(timeout)

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self.dq.flush(force=True)
