"""A multipart part commits on all its drives at once, one storage call a
drive (ISSUE 43: ``StorageAPI.commit_part``, fanned out by
``put_object_part`` over ``meta_pool()``), and no guarantee moved:

* a part is acknowledged only at the upload's write quorum: a commit that
  fails on more drives than that allows is refused, and the part is on no
  drive afterwards (nothing a ListParts shows, nothing staged);
* with one drive failing the part is acknowledged, listed, completes and
  reads back; the drive that missed it keeps nothing staged;
* a crash at ``pre_rename_file`` on one drive (a fault armed: the Python
  sequence) ends the request as a dead process would; after the restart the
  staging is reclaimed and the part can be sent again;
* the request's record shows the fan-out: ``commit.pool`` once a drive;
* through the health and disk-id wrappers the call is scored and gated as
  ``rename_data`` is."""
import io
import os
import time

import numpy as np
import pytest

from minio_tpu import fault
from minio_tpu.objectlayer import ErasureObjects
from minio_tpu.objectlayer import datatypes as dt
from minio_tpu.objectlayer.multipart import upload_path
from minio_tpu.obs import metrics as mx
from minio_tpu.obs import stages
from minio_tpu.scanner.janitor import DurabilityJanitor
from minio_tpu.storage import XLStorage
from minio_tpu.storage.health import DiskHealthCheck
from minio_tpu.storage.idcheck import DiskIDCheck
from minio_tpu.storage.xlstorage import META_MULTIPART, META_TMP
from minio_tpu.utils import errors

N, PARITY = 6, 2
PART = 5 << 20

#: a rule that matches no drive: arming it is what moves the process onto
#: the Python sequences
NO_DRIVE = "disk:no-such-drive:read_at:delay(1)"


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()


def _body(seed, n=PART):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _layer(root, wrap=lambda d: d):
    # zero-padded dirs: fault targets match by substring
    return ErasureObjects(
        [wrap(XLStorage(os.path.join(root, f"d{i:02d}"))) for i in range(N)],
        default_parity=PARITY)


def _base(d):
    while hasattr(d, "inner"):
        d = d.inner
    return d.base


def _upload_dirs(ol, uid, key="m"):
    return [os.path.join(_base(d), META_MULTIPART,
                         upload_path("b", key, uid)) for d in ol.disks]


def _part_files(ol, uid):
    return [sorted(n for n in os.listdir(u) if n.startswith("part."))
            if os.path.isdir(u) else None for u in _upload_dirs(ol, uid)]


def _staged(ol):
    return [os.listdir(os.path.join(_base(d), META_TMP)) for d in ol.disks]


def _break_upload_dir(ol, uid, i):
    """Drive ``i`` loses the upload's directory (moved aside) to a regular
    file at its path: a part's commit there fails with ENOTDIR on either
    route."""
    u = _upload_dirs(ol, uid)[i]
    os.rename(u, u + ".aside")
    with open(u, "wb"):
        pass


def _mend_upload_dir(ol, uid, i):
    u = _upload_dirs(ol, uid)[i]
    os.unlink(u)
    os.rename(u + ".aside", u)


@pytest.mark.parametrize("route", ["native", "python"])
def test_a_part_under_write_quorum_is_refused_and_left_nowhere(tmp_path,
                                                               route):
    ol = _layer(str(tmp_path))
    ol.make_bucket("b")
    uid = ol.new_multipart_upload("b", "m")
    first = ol.put_object_part("b", "m", uid, 1, io.BytesIO(_body(1)), PART)
    info = ol.get_multipart_info("b", "m", uid)
    # write quorum is 4 of 6: three drives that cannot commit are too many
    for i in (0, 2, 4):
        _break_upload_dir(ol, uid, i)
    if route == "python":
        fault.arm(NO_DRIVE)
    with pytest.raises(dt.InsufficientWriteQuorum):
        ol.put_object_part("b", "m", uid, 2, io.BytesIO(_body(2)), PART,
                           upload=info)
    fault.clear()
    # part 2 is on no drive, its sidecar neither, and nothing is staged
    assert _part_files(ol, uid) == [
        None if i in (0, 2, 4) else ["part.1", "part.1.meta"]
        for i in range(N)]
    assert _staged(ol) == [[]] * N
    # the drives come back: the upload lists the part it acknowledged and
    # no other, whichever drive is asked
    for i in (0, 2, 4):
        _mend_upload_dir(ol, uid, i)
    assert _part_files(ol, uid) == [["part.1", "part.1.meta"]] * N
    listed = ol.list_object_parts("b", "m", uid).parts
    assert [(p.part_number, p.etag) for p in listed] == [(1, first.etag)]


@pytest.mark.parametrize("route", ["native", "python"])
def test_a_part_with_one_drive_failing_is_acknowledged_and_reads_back(
        tmp_path, route):
    ol = _layer(str(tmp_path))
    ol.make_bucket("b")
    uid = ol.new_multipart_upload("b", "m")
    info = ol.get_multipart_info("b", "m", uid)
    _break_upload_dir(ol, uid, 3)
    if route == "python":
        fault.arm(NO_DRIVE)
    bodies = [_body(1), _body(2, PART // 2)]
    snap = mx.counters_snapshot()
    parts = [ol.put_object_part("b", "m", uid, n + 1, io.BytesIO(b), len(b),
                                upload=info) for n, b in enumerate(bodies)]
    after = mx.counters_snapshot()
    key = f'minio_tpu_storage_part_commits_total{{route="{route}"}}'
    assert after.get(key, 0) - snap.get(key, 0) == 2 * N
    # the drive that missed the parts keeps nothing staged either
    assert _staged(ol) == [[]] * N
    assert _part_files(ol, uid) == [
        None if i == 3 else ["part.1", "part.1.meta", "part.2", "part.2.meta"]
        for i in range(N)]
    listed = ol.list_object_parts("b", "m", uid).parts
    assert [(p.part_number, p.etag, p.size) for p in listed] == [
        (p.part_number, p.etag, len(b)) for p, b in zip(parts, bodies)]
    _mend_upload_dir(ol, uid, 3)
    ol.complete_multipart_upload("b", "m", uid, parts)
    fault.clear()
    assert ol.get_object_bytes("b", "m") == b"".join(bodies)
    assert ol.get_object_info("b", "m").size == PART + PART // 2


def test_an_injected_commit_error_counts_like_any_failed_drive(tmp_path):
    """The storage call is an injection point of its own name: one drive
    answering FaultyDisk is routed around, three refuse the part."""
    ol = _layer(str(tmp_path))
    ol.make_bucket("b")
    uid = ol.new_multipart_upload("b", "m")
    fault.arm(f"disk:{ol.disks[1].endpoint()}:commit_part:error(FaultyDisk)")
    part = ol.put_object_part("b", "m", uid, 1, io.BytesIO(_body(1)), PART)
    assert _part_files(ol, uid)[1] == []
    assert _staged(ol) == [[]] * N
    for i in (3, 5):
        fault.arm(
            f"disk:{ol.disks[i].endpoint()}:commit_part:error(FaultyDisk)")
    with pytest.raises(dt.InsufficientWriteQuorum):
        ol.put_object_part("b", "m", uid, 2, io.BytesIO(_body(2)), PART)
    fault.clear()
    assert _part_files(ol, uid) == [
        [] if i == 1 else ["part.1", "part.1.meta"] for i in range(N)]
    assert _staged(ol) == [[]] * N
    ol.complete_multipart_upload("b", "m", uid, [part])
    assert ol.get_object_bytes("b", "m") == _body(1)


def test_a_crash_at_pre_rename_file_on_one_drive_is_a_dead_process(tmp_path):
    """The crash point lives in the Python sequence, which every run with
    a fault armed takes: the request ends in SimulatedCrash (no handler
    catches it, on the pool thread or the request's), the restart reclaims
    the staging, and the part is sent again and completes."""
    root = str(tmp_path)
    ol = _layer(root)
    ol.make_bucket("b")
    uid = ol.new_multipart_upload("b", "m")
    before = mx.counters_snapshot()
    fault.arm(f"disk:{ol.disks[2].endpoint()}:pre_rename_file:crash")
    with pytest.raises(fault.SimulatedCrash):
        ol.put_object_part("b", "m", uid, 1, io.BytesIO(_body(1)), PART)
    time.sleep(0.3)  # the sibling pool tasks end
    fault.clear()
    after = mx.counters_snapshot()
    key = 'minio_tpu_storage_part_commits_total{route="%s"}'
    assert after.get(key % "python", 0) - before.get(key % "python", 0) == N
    assert after.get(key % "native", 0) == before.get(key % "native", 0)
    # the drive that died mid-commit holds the staged shard and no part
    assert _part_files(ol, uid)[2] == []
    assert len(_staged(ol)[2]) == 1
    ol2 = _layer(root)  # the restart: start-up recovery sweeps tmp
    DurabilityJanitor(ol2).sweep(tmp_age_s=0.0, reconcile=True,
                                 ddir_age_s=0.0)
    assert _staged(ol2) == [[]] * N
    part = ol2.put_object_part("b", "m", uid, 1, io.BytesIO(_body(3)), PART)
    assert _part_files(ol2, uid) == [["part.1", "part.1.meta"]] * N
    ol2.complete_multipart_upload("b", "m", uid, [part])
    assert ol2.get_object_bytes("b", "m") == _body(3)


def test_the_commit_is_one_pool_task_a_drive_in_the_requests_stages(tmp_path):
    ol = _layer(str(tmp_path))
    ol.make_bucket("b")
    uid = ol.new_multipart_upload("b", "m")
    with stages.collect() as st:
        ol.put_object_part("b", "m", uid, 1, io.BytesIO(_body(1)), PART)
    # the request's thread waits in ``commit`` once; beside it one wrapped
    # pool task a drive
    assert st.own["commit"][2] == 1 and "commit.pool" not in st.own
    assert st.aside["commit.pool"][2] == N


@pytest.mark.parametrize("wrap", [DiskHealthCheck, DiskIDCheck],
                         ids=["health", "idcheck"])
def test_the_wrappers_pass_the_call_on(tmp_path, wrap):
    """``commit_part`` is delegated where ``rename_data`` is: scored by the
    health tracker (a missing staged file is a benign answer), gated by
    the disk-id check."""
    ol = _layer(str(tmp_path), wrap=wrap)
    ol.make_bucket("b")
    uid = ol.new_multipart_upload("b", "m")
    part = ol.put_object_part("b", "m", uid, 1, io.BytesIO(_body(1)), PART)
    assert _part_files(ol, uid) == [["part.1", "part.1.meta"]] * N
    d = ol.disks[0]
    with pytest.raises(errors.FileNotFound):
        d.commit_part(META_TMP, "none/part.1", META_MULTIPART,
                      upload_path("b", "m", uid) + "/part.2", b"m")
    if wrap is DiskHealthCheck:
        assert d.total_errors == 0 and d.is_online()
    ol.complete_multipart_upload("b", "m", uid, [part])
    assert ol.get_object_bytes("b", "m") == _body(1)
