"""A set that serves with a drive DEAD (its directory gone, a regular file
at its path, so every call on it fails as on an unmounted disk): reads and
writes go on on the native path, heal debt waits for the drive instead of
retrying against it, and the drive's return (with its old contents, or
empty) pays the debt. Every answer is held to a plain in-memory model
(key -> bytes, or deleted) and to the benchmark's plain ETag reference
(``benchmark/lib/hh_ref.py``), over real HTTP on an 8+4 set."""
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark", "lib"))
import hh_ref  # noqa: E402
from s3client import S3Client  # noqa: E402

from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.objectlayer.metadata import hash_order  # noqa: E402
from minio_tpu.obs import metrics as mx  # noqa: E402
from minio_tpu.scanner import mrf as mrf_mod  # noqa: E402
from minio_tpu.server import S3Server  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402

AK, SK = "offlineak", "offlinesk1"
BUCKET = "b"
DRIVES, PARITY = 12, 4
SIZE = (1 << 20) + 4097
with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                       "xl-8p4-12d.json")) as _f:
    GEOMETRY = json.load(_f)["geometry"]
HEALS = 'minio_tpu_heal_objects_total{dry="false",mode="normal"}'
ROUTES = "minio_tpu_pipeline_get_blocks_total"


def counters(prefix: str) -> dict:
    return {k: v for k, v in mx.counters_snapshot().items()
            if k.startswith(prefix)}


def moved(before: dict, prefix: str) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in counters(prefix).items()
            if v != before.get(k, 0.0)}


def wait_until(cond, timeout=60.0, step=0.05, msg=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(step)
    raise AssertionError(f"timed out: {msg}")


class Set:
    """One served 8+4 set, its client, and the model of what it holds."""

    def __init__(self, root: str):
        self.dirs = [os.path.join(root, f"d{i:02d}") for i in range(DRIVES)]
        self.obj = ErasureObjects([XLStorage(d) for d in self.dirs],
                                  default_parity=PARITY)
        self.srv = S3Server(self.obj, "127.0.0.1", 0, access_key=AK,
                            secret_key=SK)
        self.srv.start_background()
        self.srv.start_background_services(scan_interval_s=3600.0)
        self.mrf = self.srv.mrf
        self.c = S3Client(self.srv.endpoint(), AK, SK)
        assert self.c.put_bucket(BUCKET).status_code == 200
        self.model: dict[str, bytes | None] = {}

    def close(self):
        self.srv.shutdown()

    # -- traffic, recorded in the model --------------------------------------

    def put(self, key: str, seed: int) -> None:
        body = np.random.default_rng([31, seed]).bytes(SIZE)
        r = self.c.put_object(BUCKET, key, body)
        assert r.status_code == 200, r.text
        assert r.headers["ETag"].strip('"') == \
            hh_ref.reference_etags([body], GEOMETRY)[0]
        self.model[key] = body

    def delete(self, key: str) -> None:
        assert self.c.delete_object(BUCKET, key).status_code == 204
        self.model[key] = None

    def check(self, keys=None) -> None:
        """Status, size, ETag and body of every key are the model's."""
        for key in keys or sorted(self.model):
            body = self.model[key]
            r = self.c.get_object(BUCKET, key)
            if body is None:
                assert r.status_code == 404, (key, r.status_code)
                continue
            assert r.status_code == 200, (key, r.status_code, r.text[:200])
            assert r.content == body, key
            assert r.headers["ETag"].strip('"') == \
                hh_ref.reference_etags([body], GEOMETRY)[0], key
            h = self.c.head_object(BUCKET, key)
            assert h.status_code == 200
            assert int(h.headers["Content-Length"]) == len(body)

    # -- the drive ------------------------------------------------------------

    def kill(self, i: int) -> None:
        """The drive is gone: its directory aside, a regular file in its
        place. Then the health tracker is shown enough failures to fence
        it (by direct calls, so that no request charges anything on the
        way)."""
        os.rename(self.dirs[i], self.dirs[i] + ".aside")
        open(self.dirs[i], "w").close()
        d = self.obj.disks[i]
        for _ in range(16):
            if d.health_state() == "faulty":
                break
            with pytest.raises(Exception):
                d.list_vols()
        assert d.health_state() == "faulty"

    def revive(self, i: int, stale: bool) -> None:
        """The drive is back: remounted with what it held when it died
        (``stale``), or replaced by an empty one (empty but for the
        system volume every drive of this store is mounted with: the
        probe writes there, so a bare directory never comes online)."""
        os.remove(self.dirs[i])
        if stale:
            os.rename(self.dirs[i] + ".aside", self.dirs[i])
        else:
            shutil.rmtree(self.dirs[i] + ".aside")
            XLStorage(self.dirs[i])
        wait_until(lambda: self.obj.disks[i].health_state() == "ok",
                   timeout=20, msg="the probe re-onlines the drive")

    def on_drive(self, i: int, key: str) -> bool:
        """``key``'s xl.meta and a shard file are on drive ``i``."""
        base = os.path.join(self.dirs[i], BUCKET, key)
        if not os.path.exists(os.path.join(base, "xl.meta")):
            return False
        return any(name.startswith("part.") for _d, _s, names in
                   os.walk(base) for name in names)

    def data_drive(self, key: str, shard: int = 1) -> int:
        return hash_order(f"{BUCKET}/{key}", DRIVES).index(shard)


@pytest.fixture
def served(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_HEALTH_COOLDOWN_S", "0.3")
    monkeypatch.setattr(mrf_mod, "RETRY_BASE_S", 0.2)
    s = Set(str(tmp_path))
    yield s
    s.close()


def test_reads_with_the_drive_dead_charge_nothing(served):
    """(a) k GETs an object with the drive dead: no heal pass is made,
    every block takes the native path, and the reads say why they charged
    nothing."""
    s = served
    for i in range(6):
        s.put(f"o{i}", i)
    # a drive that holds a DATA shard of o0, so its GETs rebuild
    dead = s.data_drive("o0")
    s.kill(dead)
    c0, r0 = counters("minio_tpu_mrf"), counters(ROUTES)
    heals = counters(HEALS)
    for _ in range(GEOMETRY["data"]):
        s.check()
    time.sleep(1.0)     # five backoff periods: a timed retry would show
    assert moved(heals, HEALS) == {}
    assert s.mrf.stats()["queued"] == 0
    routes = {k.split('route="')[1].split('"')[0]: v
              for k, v in moved(r0, ROUTES).items()}
    assert set(routes) <= {"native_fd", "native_degraded"}, routes
    assert routes.get("native_degraded", 0) >= GEOMETRY["data"]
    charges = moved(c0, "minio_tpu_mrf_charges_total")
    assert set(charges) == {'minio_tpu_mrf_charges_total{outcome='
                            '"skipped_offline",source="read"}'}, charges


def test_writes_with_the_drive_dead_park_once_a_key(served):
    """(b) PUT, overwrite and DELETE with the drive dead: each charges,
    no heal pass is made, the park holds ONE entry a key, and the journal
    carries them to the next MRFHealer."""
    s = served
    for i in range(4):
        s.put(f"o{i}", i)
    dead = 5
    s.kill(dead)
    c0, heals = counters("minio_tpu_mrf"), counters(HEALS)
    s.put("new", 10)
    s.put("new", 11)
    s.put("new", 12)
    s.put("o0", 13)
    s.delete("o1")
    s.delete("o2")
    s.put("o2", 14)
    time.sleep(1.0)
    assert moved(heals, HEALS) == {}
    charges = moved(c0, "minio_tpu_mrf_charges_total")
    assert charges == {
        'minio_tpu_mrf_charges_total{outcome="parked_known",source="write"}':
            5.0,
        'minio_tpu_mrf_charges_total{outcome="parked_known",source="delete"}':
            2.0}, charges
    st = s.mrf.stats()
    assert st["parked_offline"] == 4 and st["queued"] == 4, st
    assert st["retry_pending"] == 4     # nothing of it is runnable now
    s.check()
    # the journal holds them across a restart of the healer
    s.mrf.flush_journal()
    with open(s.mrf._persist_path) as f:
        entries = json.load(f)["entries"]
    assert sorted(e["object"] for e in entries) == ["new", "o0", "o1", "o2"]
    again = mrf_mod.MRFHealer(s.obj)
    assert again.attach_persistence(s.mrf._persist_path) == 4
    assert again.stats()["queued"] == 4


@pytest.mark.parametrize("stale", [True, False],
                         ids=["remounted-stale", "replaced-empty"])
def test_the_drive_returns_and_the_debt_is_paid(served, stale):
    """(c) The drive comes back, with what it held when it died or empty:
    the tracker's listener releases the park (no timer is waited for),
    every live object has shard and xl.meta on it, every deleted one is
    gone from all drives and answers 404, and every object reads back
    bit-exact with ``parity`` OTHER drives' shards removed."""
    s = served
    for i in range(5):
        s.put(f"o{i}", i)
    dead = s.data_drive("o0")
    s.kill(dead)
    s.put("new-a", 20)
    s.put("new-b", 21)
    s.put("o0", 22)         # overwritten: stale on the drive
    s.delete("o1")          # deleted: still on the drive
    s.put("gone", 23)
    s.delete("gone")        # written and deleted meanwhile
    parked = s.mrf.stats()["parked_offline"]
    assert parked == 5
    r0 = counters("minio_tpu_mrf_released_total")
    s.revive(dead, stale)
    wait_until(lambda: s.mrf.stats()["parked_offline"] == 0, timeout=5,
               msg="the listener releases the park")
    assert moved(r0, "minio_tpu_mrf_released_total") == {
        'minio_tpu_mrf_released_total{reason="reonline"}': float(parked)}
    live = [k for k, v in s.model.items() if v is not None]
    gone = [k for k, v in s.model.items() if v is None]
    wait_until(lambda: all(s.on_drive(dead, k) for k in live)
               and s.mrf.stats()["queued"] == 0, timeout=90, step=0.2,
               msg=f"every live object back on the drive: "
                   f"{[k for k in live if not s.on_drive(dead, k)]}")
    wait_until(lambda: not any(
        os.path.exists(os.path.join(d, BUCKET, k, "xl.meta"))
        for d in s.dirs for k in gone), timeout=30, step=0.2,
        msg="a deleted object is gone from every drive")
    s.check()
    # what the drive got back is good: PARITY other drives lose their
    # shards and every object still reads bit-exact
    others = [i for i in range(DRIVES) if i != dead][:PARITY]
    for i in others:
        for k in live:
            shutil.rmtree(os.path.join(s.dirs[i], BUCKET, k))
    s.check()


def _lose_shard(s, key):
    shutil.rmtree(os.path.join(s.dirs[s.data_drive(key)], BUCKET, key))


def _rot_shard(s, key):
    base = os.path.join(s.dirs[s.data_drive(key)], BUCKET, key)
    part = next(os.path.join(d, n) for d, _s, names in os.walk(base)
                for n in names if n.startswith("part."))
    with open(part, "r+b") as f:
        f.seek(os.path.getsize(part) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))


def _outdate(s, key):
    """One drive keeps the version before the overwrite."""
    base = os.path.join(s.dirs[s.data_drive(key)], BUCKET, key)
    shutil.copytree(base, base + ".old")
    s.put(key, 99)
    shutil.rmtree(base)
    os.rename(base + ".old", base)


@pytest.mark.parametrize("harm,mode", [
    (_lose_shard, "normal"), (_rot_shard, "deep"), (_outdate, "normal")],
    ids=["file-not-found", "file-corrupt", "outdated"])
def test_a_drive_that_answers_still_charges(served, harm, mode):
    """(d) Trouble on a drive that ANSWERS is heal debt as before:
    a missing or outdated shard a normal heal, a corrupt one a deep
    one, and the healer pays it."""
    s = served
    s.put("k", 1)
    harm(s, "k")
    c0 = counters("minio_tpu_mrf")
    s.check()
    wait_until(lambda: moved(c0, "minio_tpu_mrf_heal_attempts_total").get(
        'minio_tpu_mrf_heal_attempts_total{outcome="healed"}', 0) >= 1,
        timeout=30, msg="heal-on-read heals it")
    assert moved(c0, "minio_tpu_mrf_charges_total").get(
        'minio_tpu_mrf_charges_total{outcome="queued",source="read"}',
        0) >= 1
    assert any(f'mode="{mode}"' in k for k in moved(
        {}, "minio_tpu_heal_objects_total"))
    assert s.mrf.stats()["parked_offline"] == 0
    assert s.on_drive(s.data_drive("k"), "k")
    s.check()


def test_two_drives_dead_still_serve_and_park(served):
    """(e) 8+4 with two drives dead: reads and writes go on, and the debt
    waits for both."""
    s = served
    s.put("o0", 1)
    for i in (3, 7):
        s.kill(i)
    s.put("o1", 2)
    s.put("o0", 3)
    s.delete("o1")
    s.check()
    assert s.mrf.stats()["parked_offline"] == 2
    assert {eps for _m, eps in s.mrf.dq._offline.values()} == {
        frozenset({s.dirs[3], s.dirs[7]})}
    # one of the two returns: the debt is tried, and waits for the other
    s.revive(3, stale=True)
    wait_until(lambda: s.on_drive(3, "o0"), timeout=30,
               msg="the returned drive gets its shard")
    wait_until(lambda: {eps for _m, eps in s.mrf.dq._offline.values()}
               == {frozenset({s.dirs[7]})}, timeout=30,
               msg="what is left waits for the drive still dead")
    s.check()


def test_five_drives_dead_refuse_at_quorum(served):
    """(e) Five of twelve dead leaves 7 < k = 8: a PUT and a GET are
    refused with the quorum errors (503) they were refused with before,
    and nothing is parked for them."""
    s = served
    s.put("o0", 1)
    for i in range(5):
        s.kill(i)
    body = np.random.default_rng([31, 2]).bytes(SIZE)
    r = s.c.put_object(BUCKET, "o1", body)
    assert r.status_code == 503 and "<Code>SlowDownWrite</Code>" in r.text, \
        (r.status_code, r.text[:300])
    g = s.c.get_object(BUCKET, "o0")
    assert g.status_code == 503 and "<Code>SlowDownRead</Code>" in g.text, \
        (g.status_code, g.text[:300])
    assert s.mrf.stats()["parked_offline"] == 0


# --- the park itself ---------------------------------------------------------


def test_offline_park_is_keyed_bounded_and_released_by_drive(tmp_path):
    """One entry a key (drives add up, ``deep`` sticks), drop-oldest at
    the queue's bound with its ``dropped`` count, the journal follows, and
    only the named drive's return (or a kick) makes an entry runnable."""
    from minio_tpu.scanner.park import DebtQueue
    dq = DebtQueue(max_queue=3, sticky_modes=("deep",))
    dq.attach_persistence(str(tmp_path / "j.json"))
    assert dq.park_offline(("b", "o1", "", "normal"), ["A"]) is True
    assert dq.park_offline(("b", "o1", "", "deep"), ["B"]) is False
    assert dq.park_offline(("b", "o1", "", "normal"), ["A"]) is False
    assert dq._offline[("b", "o1", "")] == ("deep", frozenset("AB"))
    dq.park_offline(("b", "o2", "", "normal"), ["B"])
    dq.park_offline(("b", "o3", "", "normal"), [])      # an empty slot
    dq.park_offline(("b", "o4", "", "normal"), ["A"])   # evicts o1
    st = dq.stats()
    assert (st["parked_offline"], st["dropped"]) == (3, 1)
    assert st["queued"] == st["retry_pending"] == 3
    dq.flush(force=True)
    with open(tmp_path / "j.json") as f:
        assert sorted(e["object"] for e in json.load(f)["entries"]) == \
            ["o2", "o3", "o4"]
    assert dq.pop(timeout=0.01) is None                 # nothing runnable
    assert dq.release("A") == 2                         # o4, and o3
    got = {dq.pop(timeout=0.01)[1] for _ in range(2)}
    assert got == {"o3", "o4"} and dq.pop(timeout=0.01) is None
    assert dq.kick() == 1 and dq.pop(timeout=0.01)[:4] == \
        ("b", "o2", "", "normal")
    assert dq.stats()["parked_offline"] == 0
