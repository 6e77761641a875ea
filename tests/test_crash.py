"""Crash-point chaos matrix (ISSUE 6 tentpole, docs/durability.md): for
every registered write step (``xlstorage.WRITE_STEPS``), simulate a
process death there during a PUT and a multipart complete (``crash``
fault rules raise ``SimulatedCrash``, a BaseException no cleanup handler
catches), then "reboot" — rebuild the object layer over the same disk
dirs, run the recovery janitor — and assert:

* all-or-nothing visibility: the object reads fully (old or new body)
  or is absent; never torn, never a mix,
* ``.minio.sys/tmp`` is empty (startup recovery reclaimed the staging),
* partially committed sets (crash after a minority of journal writes)
  enqueue a heal, and healing converges every disk.

Plus the ``torn`` half: a power-cut truncated xl.meta is rejected by the
trailing checksum, quarantined to ``xl.meta.corrupt`` on first read, and
healed back from quorum."""
import io
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from minio_tpu import fault  # noqa: E402
from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.objectlayer import datatypes as dt  # noqa: E402
from minio_tpu.scanner.janitor import DurabilityJanitor  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402
from minio_tpu.storage.xlstorage import (META_TMP,  # noqa: E402
                                         WRITE_STEPS)

N, PARITY = 6, 2
OBJ = 384 << 10  # > inline threshold, single erasure block

#: steps exercised by a plain PUT commit (pre_rename_file is multipart-
#: only, pre_append has no object-commit role)
PUT_STEPS = ("pre_replace", "post_replace", "pre_data_rename",
             "post_data_rename", "pre_meta_write", "post_meta_write")
MP_STEPS = PUT_STEPS + ("pre_rename_file",)


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()


def _body(seed):
    return np.random.default_rng(seed).integers(
        0, 256, OBJ, dtype=np.uint8).tobytes()


def _layer(root):
    # zero-padded dirs: fault targets match by substring
    disks = [XLStorage(os.path.join(root, f"d{i:02d}")) for i in range(N)]
    return ErasureObjects(disks, default_parity=PARITY)


def _settle():
    """Let in-flight meta-pool workers hit their (still armed) crash
    rule before the test clears faults and rebuilds — the first future
    to raise unwinds the caller while siblings are mid-commit."""
    time.sleep(0.3)


def _restart(root):
    """The 'reboot': fresh XLStorage + ErasureObjects instances over the
    same dirs (init runs startup recovery), then a zero-age janitor
    sweep — the post-restart recovery the acceptance criteria describe."""
    ol = _layer(root)
    kicks = []
    ol.on_partial = lambda b, o, v="", scan_mode="normal": \
        kicks.append((b, o, scan_mode))
    DurabilityJanitor(ol).sweep(tmp_age_s=0.0, reconcile=True,
                                ddir_age_s=0.0)
    return ol, kicks


def _assert_tmp_clean(ol):
    for d in ol.disks:
        names = [n for n in d.list_dir(META_TMP, "")]
        assert names == [], f"META_TMP orphans on {d.endpoint()}: {names}"


def _read_or_absent(ol, bucket, obj):
    try:
        return ol.get_object_bytes(bucket, obj)
    except (dt.ObjectNotFound, dt.InsufficientReadQuorum):
        return None


# --- registry sanity --------------------------------------------------------


def test_crash_step_registry():
    assert len(WRITE_STEPS) >= 6
    assert set(PUT_STEPS) <= set(WRITE_STEPS)
    r = fault.parse_rule("disk:*:pre_replace:crash@count=1")
    assert r.action == "crash" and r.count == 1
    assert fault.parse_rule("disk:*:pre_replace:torn").action == "torn"
    # a crash must NOT be catchable by the tree's cleanup handlers
    assert issubclass(fault.SimulatedCrash, BaseException)
    assert not issubclass(fault.SimulatedCrash, Exception)


# --- the matrix: uniform crash (all disks die at the step) ------------------


@pytest.mark.parametrize("step", PUT_STEPS)
def test_crash_matrix_put(tmp_path, step):
    root = str(tmp_path)
    body1, body2 = _body(1), _body(2)
    ol = _layer(root)
    ol.make_bucket("b")
    ol.put_object("b", "o", io.BytesIO(body1), OBJ)  # committed baseline

    fault.arm(f"disk:*:{step}:crash")
    with pytest.raises(fault.SimulatedCrash):
        ol.put_object("b", "o", io.BytesIO(body2), OBJ)
    _settle()
    fault.clear()

    ol2, _kicks = _restart(root)
    data = _read_or_absent(ol2, "b", "o")
    assert data in (body1, body2), "torn/mixed object visible after crash"
    _assert_tmp_clean(ol2)
    # converge and re-verify: a heal pass must leave the same winner
    ol2.heal_object("b", "o")
    assert ol2.get_object_bytes("b", "o") == data


#: the steps an inline version's commit passes (ISSUE 39): no data
#: directory moves, so the journal's write is the whole commit
INLINE_STEPS = ("pre_meta_write", "pre_replace", "post_replace",
                "post_meta_write")


@pytest.mark.parametrize("step", INLINE_STEPS)
def test_crash_matrix_put_inline(tmp_path, step):
    """A 64 KiB object lives in its drives' xl.meta, a shard a drive. A
    crash at any step of its commit leaves the old version or the new,
    never neither and never a mix: xl.meta changes by one rename."""
    root = str(tmp_path)
    small = 64 << 10
    body1, body2 = _body(3)[:small], _body(4)[:small]
    ol = _layer(root)
    ol.make_bucket("b")
    ol.put_object("b", "o", io.BytesIO(body1), small)
    for d in ol.disks:
        assert d.list_dir("b", "o") == ["xl.meta"]

    fault.arm(f"disk:*:{step}:crash")
    with pytest.raises(fault.SimulatedCrash):
        ol.put_object("b", "o", io.BytesIO(body2), small)
    _settle()
    fault.clear()

    ol2, _kicks = _restart(root)
    data = _read_or_absent(ol2, "b", "o")
    assert data in (body1, body2), "neither version after the crash"
    if step.startswith("pre_"):
        assert data == body1  # no drive's journal had been renamed over
    _assert_tmp_clean(ol2)
    ol2.heal_object("b", "o")
    assert ol2.get_object_bytes("b", "o") == data
    for d in ol2.disks:
        assert d.list_dir("b", "o") == ["xl.meta"]


@pytest.mark.parametrize("step", MP_STEPS)
def test_crash_matrix_multipart_complete(tmp_path, step):
    root = str(tmp_path)
    body = _body(3)
    ol = _layer(root)
    ol.make_bucket("b")
    uid = ol.new_multipart_upload("b", "m")
    part = ol.put_object_part("b", "m", uid, 1, io.BytesIO(body), OBJ)

    fault.arm(f"disk:*:{step}:crash")
    with pytest.raises(fault.SimulatedCrash):
        ol.complete_multipart_upload("b", "m", uid, [part])
    _settle()
    fault.clear()

    ol2, _kicks = _restart(root)
    data = _read_or_absent(ol2, "b", "m")
    assert data in (None, body), "torn multipart object visible"
    _assert_tmp_clean(ol2)
    if data is None:
        # all-or-nothing's 'nothing' half: the upload either survived
        # for a client retry or was fully reaped — but the object
        # namespace must not carry a phantom
        infos = ol2.list_objects("b").objects
        assert all(oi.name != "m" for oi in infos)
    else:
        ol2.heal_object("b", "m")
        assert ol2.get_object_bytes("b", "m") == data


def test_fresh_put_crash_residue_reclaimed(tmp_path):
    """Crash after the dataDir rename but before the FIRST journal
    write of a brand-new object: no xl.meta exists anywhere, so the
    residue is invisible to walk_dir — walk_unjournaled + the janitor
    must still reclaim every disk's shards."""
    root = str(tmp_path)
    body = _body(7)
    ol = _layer(root)
    ol.make_bucket("b")
    fault.arm("disk:*:post_data_rename:crash")
    with pytest.raises(fault.SimulatedCrash):
        ol.put_object("b", "fresh", io.BytesIO(body), OBJ)
    _settle()
    fault.clear()
    ol2, _kicks = _restart(root)
    assert _read_or_absent(ol2, "b", "fresh") is None
    for d in ol2.disks:
        assert not os.path.exists(os.path.join(d.base, "b", "fresh")), \
            f"journal-less shard residue leaked on {d.endpoint()}"
    _assert_tmp_clean(ol2)


# --- partial commit: a minority dies before its journal write ---------------


def test_partial_commit_kicks_heal_and_converges(tmp_path):
    root = str(tmp_path)
    body = _body(4)
    ol = _layer(root)
    ol.make_bucket("b")
    # fresh object, crash the FIRST TWO journal writes: 4/6 disks commit
    # (>= write quorum of 4), 2 carry only the moved dataDir
    fault.arm("disk:*:pre_meta_write:crash@count=2")
    try:
        ol.put_object("b", "p", io.BytesIO(body), OBJ)
    except fault.SimulatedCrash:
        pass  # whether the caller 'died' depends on future ordering
    _settle()
    fault.clear()

    ol2, kicks = _restart(root)
    # readable at quorum (4 committed journals >= read quorum 4)
    assert ol2.get_object_bytes("b", "p") == body
    _assert_tmp_clean(ol2)
    # the janitor saw the journal-less minority and enqueued a heal
    assert any(b == "b" and o == "p" for b, o, _ in kicks)
    res = ol2.heal_object("b", "p")
    assert all(s == "ok" for s in res.after_state)
    # every disk now carries the journal: a second sweep kicks nothing
    kicks.clear()
    DurabilityJanitor(ol2).sweep(tmp_age_s=0.0, reconcile=True,
                                 ddir_age_s=0.0)
    assert not kicks


# --- torn writes: checksum rejects, quarantine + heal recover ---------------


def test_torn_meta_quarantined_and_healed(tmp_path):
    root = str(tmp_path)
    body1, body2 = _body(5), _body(6)
    ol = _layer(root)
    ol.make_bucket("b")
    ol.put_object("b", "t", io.BytesIO(body1), OBJ)
    # tear the journal commit on two specific disks during an overwrite
    torn_eps = [d.endpoint() for d in ol.disks[:2]]
    for ep in torn_eps:
        fault.arm(f"disk:{ep}:pre_replace:torn")
    ol.put_object("b", "t", io.BytesIO(body2), OBJ)  # write 'succeeds'
    fault.clear()

    ol2, _kicks = _restart(root)
    # quorum serves v2; first read quarantines the torn journals
    assert ol2.get_object_bytes("b", "t") == body2
    quarantined = 0
    for d, ep in zip(ol2.disks, [d.endpoint() for d in ol2.disks]):
        odir = os.path.join(d.base, "b", "t")
        if os.path.exists(os.path.join(odir, "xl.meta.corrupt")):
            quarantined += 1
            assert not os.path.exists(os.path.join(odir, "xl.meta"))
    assert quarantined == 2
    # heal rebuilds the quarantined disks' journal + shards from quorum
    res = ol2.heal_object("b", "t")
    assert all(s == "ok" for s in res.after_state)
    assert ol2.get_object_bytes("b", "t") == body2
