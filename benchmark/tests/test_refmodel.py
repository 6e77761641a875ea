"""The reference model's self-test: a clean history is correct; one flipped
byte in one body, one missing healed shard, one acknowledged PUT that is not
there (a phantom), one wrong ETag, one deleted key that is still served each
make ``correct`` false."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib"))
import refmodel  # noqa: E402

SHA, ETAG = "a" * 64, "e" * 32


def history():
    return [
        {"op": "PUT", "key": "k1", "status": 200, "size": 10, "sha": SHA,
         "etag": ETAG, "etag_ref": ETAG, "t0": 0, "t1": 1},
        {"op": "GET", "key": "k1", "status": 200, "n": 10, "sha": SHA,
         "etag": ETAG, "t0": 1, "t1": 2},
        {"op": "STAT", "key": "k1", "status": 200, "n": 10, "etag": ETAG,
         "t0": 2, "t1": 3},
        {"op": "DELETE", "key": "k1", "status": 204, "t0": 3, "t1": 4},
        {"op": "GET", "key": "k1", "status": 404, "n": 0, "sha": "",
         "etag": "", "t0": 4, "t1": 5},
        {"op": "PUT", "key": "k2", "status": 200, "size": 10, "sha": SHA,
         "etag": ETAG, "etag_ref": ETAG, "t0": 5, "t1": 6},
        {"op": "HEAL", "key": "drive-0", "status": 0, "t0": 0, "t1": 6,
         "seq": {"status": "done", "scanned": 2, "healed": 2, "failed": 0}},
    ]


def verdict(recs, drives=None):
    m = refmodel.Model()
    m.replay(recs)
    if drives:
        m.shards_present(drives, "b")
    return m.verdict(lambda s: None), m


def test_clean_history_is_correct(tmp_path):
    drives = []
    for i in range(3):
        d = tmp_path / f"d{i}"
        (d / "b" / "k2").mkdir(parents=True)
        (d / "b" / "k2" / "xl.meta").write_text("x")
        drives.append(str(d))
    ok, m = verdict(history(), drives)
    assert ok and m.failed == 0
    assert m.counts["ops_attempted"] == 9      # 7 records + 2 scanned


def test_one_missing_healed_shard(tmp_path):
    drives = []
    for i in range(3):
        d = tmp_path / f"d{i}"
        (d / "b" / "k2").mkdir(parents=True)
        if i != 1:
            (d / "b" / "k2" / "xl.meta").write_text("x")
        drives.append(str(d))
    ok, m = verdict(history(), drives)
    assert not ok and m.counts["shards_missing_after_heal"] == 1


def flipped_byte(h):
    h[1]["sha"] = "b" + SHA[1:]


def phantom_put(h):
    # acknowledged and counted, but the store does not hold it
    h.append({"op": "STAT", "key": "k2", "status": 404, "n": -1, "etag": "",
              "t0": 7, "t1": 8})


def wrong_etag(h):
    h[0]["etag"] = "f" * 32


def deleted_served(h):
    h[4].update(status=200, n=10, sha=SHA, etag=ETAG)


def refused(h):
    h[1].update(status=503, err="SlowDown")


def heal_failed(h):
    h[6]["seq"]["failed"] = 1


def short_body(h):
    h[1]["n"] = 9


@pytest.mark.parametrize("tamper,counter", [
    (flipped_byte, "get_bodies_wrong"), (phantom_put, "live_keys_missing"),
    (wrong_etag, "put_etags_wrong"), (deleted_served, "deleted_keys_served"),
    (refused, "ops_refused_503"), (heal_failed, "heal_objects_failed"),
    (short_body, "get_bodies_wrong")])
def test_one_fault_is_not_correct(tamper, counter):
    h = history()
    tamper(h)
    ok, m = verdict(h)
    assert not ok and m.counts[counter] == 1 and m.failed >= 1
