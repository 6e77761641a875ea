"""``refmodel.Model`` for the multipart kinds: the same model (key -> size,
SHA-256, ETag) and the same rule (every number compared is a count of wrong
answers, every limit is 0), taught the records of ``lib/mp_client.py``. An
object exists once its Complete is acknowledged; its ETag has to be the fold
of the part ETags the server returned (MD5 over their binary MD5s, ``-N``),
its size and SHA-256 those of the parts' plaintext. Imports nothing of the
program."""
from __future__ import annotations

import hashlib

import refmodel


def fold(etags: list[str]) -> str:
    h = hashlib.md5()
    for e in etags:
        h.update(bytes.fromhex(e.split("-")[0]))
    return f"{h.hexdigest()}-{len(etags)}"


class Model(refmodel.Model):
    def __init__(self, sse: str | None):
        super().__init__()
        self.sse = sse or ""
        self.counts.update({
            "uploads_not_encrypted": 0, "complete_etags_wrong": 0,
            "range_bodies_wrong": 0, "plaintext_runs_at_rest": 0,
            "packages_unopened_at_rest": 0, "memory_bound_exceeded": 0})

    def fault_n(self, name: str, rec: dict, why: str, n: int) -> None:
        """``n`` wrong answers of one kind with one cause: counted each,
        listed once."""
        self.counts[name] += n - 1
        self.fault(name, rec, why)

    def replay(self, records: list[dict]) -> None:
        for r in records:
            handler = getattr(self, "_" + r["op"].lower(), None)
            if handler is None:
                super().replay([r])
                continue
            self.counts["ops_attempted"] += 1
            if r["status"] == 503:
                self.fault("ops_refused_503", r, r.get("err", ""))
            elif r["status"] < 0:
                self.fault("ops_errored", r, r.get("err", ""))
            else:
                handler(r)

    def _create(self, r):
        if r["status"] != 200:
            self.fault("ops_errored", r, r.get("err", ""))
        elif r["sse"] != self.sse:
            self.fault("uploads_not_encrypted", r,
                       f"the response names {r['sse']!r}, not {self.sse!r}")

    def _put(self, r):      # one part; it becomes visible at Complete
        if r["status"] != 200:
            self.fault("ops_errored", r, r.get("err", ""))

    def _abort(self, r):
        if r["status"] != 204:
            self.fault("ops_errored", r, r.get("err", ""))

    def _complete(self, r):
        if r["status"] != 200:
            self.fault("ops_errored", r, r.get("err", ""))
            return
        want = fold(r["parts"])
        if r["etag"] != want:
            self.fault("complete_etags_wrong", r,
                       f"{r['etag']} != fold of the part ETags {want}")
        # acknowledged: it has to read back, under the folded ETag
        self.live[r["key"]] = (r["size"], r["sha"], want)
        self.deleted.discard(r["key"])

    def _rget(self, r):
        n = r["hi"] - r["lo"] + 1
        if r["status"] != 206:
            self.fault("ops_errored", r, r.get("err", ""))
        elif (r["n"], r["sha"]) != (n, r["sha_ref"]) or not \
                r["content_range"].startswith(f"bytes {r['lo']}-{r['hi']}/"):
            self.fault("range_bodies_wrong", r,
                       f"{r['n']} B of [{r['lo']}, {r['hi']}], "
                       f"{r['content_range']!r}: not the body's slice")

    def _stage(self, r):
        if r["status"] != 200 or r["found"] < 0:
            self.fault("ops_errored", r, r.get("err", "") or
                       f"{r.get('files')} staged part files")
        elif r["found"]:
            self.fault_n("plaintext_runs_at_rest", r, "64-byte runs of the "
                         "part in its staged files", r["found"])
