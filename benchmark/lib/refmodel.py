"""The reference model of the store and the comparison that decides
``correct``. The model is what an S3 store must hold after the operations the
clients' records list: key -> (size, SHA-256, ETag) or deleted. Every number
compared is a count of answers that differ from the model, and every limit is
0: the guarantees are exact (bit-exact read-back, ETag equal to the host
reference, a deleted key is 404 and nothing else is). Imports nothing of the
program."""
from __future__ import annotations

import os

OK_DELETE = (200, 204)


class Model:
    def __init__(self):
        self.live: dict[str, tuple[int, str, str]] = {}
        self.deleted: set[str] = set()
        self.counts = {
            "ops_attempted": 0, "ops_errored": 0, "ops_refused_503": 0,
            "get_bodies_wrong": 0, "get_etags_wrong": 0,
            "put_etags_wrong": 0, "stat_answers_wrong": 0,
            "deleted_keys_served": 0, "live_keys_missing": 0,
            "heal_objects_failed": 0, "shards_missing_after_heal": 0,
            "salvaged_items": 0}
        self.faults: list[str] = []

    def fault(self, name: str, rec: dict, why: str) -> None:
        self.counts[name] += 1
        if len(self.faults) < 20:
            self.faults.append(f"{name}: {rec.get('op')} {rec.get('key')} "
                               f"status {rec.get('status')}: {why}")

    def replay(self, records: list[dict]) -> None:
        """One thread's records, in the order it made them. A key is owned
        by one thread, so this order is the key's history."""
        for r in records:
            self.counts["ops_attempted"] += 1
            op, key, status = r["op"], r["key"], r["status"]
            if status == 503:
                self.fault("ops_refused_503", r, r.get("err", ""))
                continue
            if status < 0:
                self.fault("ops_errored", r, r.get("err", ""))
                continue
            if op == "PUT":
                if status != 200:
                    self.fault("ops_errored", r, r.get("err", ""))
                elif r["etag"] != r["etag_ref"]:
                    self.fault("put_etags_wrong", r, f"{r['etag']} != host "
                               f"reference {r['etag_ref']}")
                if status == 200:   # acknowledged: it has to read back
                    self.live[key] = (r["size"], r["sha"], r["etag_ref"])
                    self.deleted.discard(key)
            elif op in ("GET", "STAT"):
                want = self.live.get(key)
                if want is None:
                    if status != 404:
                        self.fault("deleted_keys_served", r,
                                   "a key the model does not hold")
                elif status == 404:
                    self.fault("live_keys_missing", r,
                               "an acknowledged key is gone")
                elif status != 200:
                    self.fault("ops_errored", r, r.get("err", ""))
                elif op == "STAT":
                    if (r["n"], r["etag"]) != (want[0], want[2]):
                        self.fault("stat_answers_wrong", r,
                                   f"{r['n']} B etag {r['etag']}")
                else:
                    if (r["n"], r["sha"]) != want[:2]:
                        self.fault("get_bodies_wrong", r, f"{r['n']} B, "
                                   "SHA-256 differs from what was PUT")
                    if r["etag"] != want[2]:
                        self.fault("get_etags_wrong", r, r["etag"])
            elif op == "DELETE":
                if status not in OK_DELETE:
                    self.fault("ops_errored", r, r.get("err", ""))
                else:
                    self.live.pop(key, None)
                    self.deleted.add(key)
            elif op in ("HEAL", "HEALWAIT"):
                seq = r["seq"]
                ends = ("done",)
                if op == "HEAL":    # may still run when the window closes
                    ends += ("running",)
                    # each scanned object is an operation of the window
                    self.counts["ops_attempted"] += seq["scanned"]
                if seq["failed"] or seq["status"] not in ends:
                    self.counts["heal_objects_failed"] += max(
                        1, seq["failed"])
                    self.faults.append(f"heal sequence: {seq}")
            elif op == "MKBUCKET":
                if status not in (200, 409):
                    self.fault("ops_errored", r, r.get("err", ""))
            elif status != 0:   # EMPTY and whatever a later kind adds
                self.fault("ops_errored", r, r.get("err", ""))

    def shards_present(self, drive_dirs: list[str], bucket: str) -> None:
        """Every live key has its ``xl.meta`` on every drive."""
        for key in self.live:
            for d in drive_dirs:
                if not os.path.exists(os.path.join(d, bucket, key,
                                                   "xl.meta")):
                    self.fault("shards_missing_after_heal",
                               {"op": "HEAL", "key": key, "status": 0},
                               f"no xl.meta on {os.path.basename(d)}")

    @property
    def failed(self) -> int:
        return sum(v for k, v in self.counts.items()
                   if k != "ops_attempted")

    def verdict(self, say) -> bool:
        """Print every number compared beside its limit; all limits are 0."""
        for name, v in self.counts.items():
            if name != "ops_attempted":
                say(f"CHECK {name} {v} limit 0")
        for f in self.faults:
            say(f"FAULT {f}")
        return self.failed == 0
