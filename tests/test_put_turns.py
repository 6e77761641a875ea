"""A PUT's drive work takes a turn at the interpreter lock a drive (ISSUE
36): shard files are staged (``native.stage_file``) and a version is
committed (``native.commit_version``) in one native call each, where the
Python sequences make a file-system call a step.

* the count: every ``os.*`` file-system function, ``builtins.open`` and the
  three native sequences are wrapped and counted by thread while one 10 MiB
  object is PUT under a new key over 12 and over 6 ``XLStorage`` drives
  (the tree before read 62 on the request's thread and 21 a drive's
  commit, 314 / 158 a PUT; a STAT read 50 / 26 by the same method, a GET
  86 / 44: the readers' own cut took those to 49 / 25 and 62 / 32);
* an ``xl.meta`` read is ONE native call (ISSUE 40: ``native.read_file``,
  where open, fstat, read and close were four), so a quorum metadata pass
  is ``n + 1`` calls on ``n`` drives: a STAT 13 / 7, a GET 26 / 14, an
  overwriting PUT the 38 / 20 of a new key; a DELETE, counted inside
  ``delete_version`` too, 242 / 122 (134 / 68 inline); both routes of the
  read give the same bytes or the same error;
* the native and the Python sequence leave the same tree, the same
  ``xl.meta`` bytes, the same fsyncs (``always``) and flusher markers
  (``batched``);
* they raise the same errors; a drive whose path is a regular file fails
  with the error the health tracker fences on and leaves nothing staged;
* a disk fault armed anywhere moves the process onto the Python sequence
  (where the crash points are), and the route counters say so;
* a multipart part (ISSUE 43) commits on all its drives at once, ONE native
  call a drive (``native.commit_part``: the shard renamed into the upload's
  directory, its sidecar written under a tmp name and renamed, the emptied
  staging directory removed), where ``rename_file`` + ``write_all`` made 8
  calls a drive, one drive after the other on the request's thread: a part
  PUT is 37 | 19 calls (121 | 61 before); its native and its Python
  sequence leave the same tree, fsyncs, markers and errors;
* an object at or under 128 KiB (ISSUE 39) stages nothing: its shards ride
  in the drives' xl.meta, a PUT is 2 calls a drive (the xl.meta read, one
  native commit) and a GET makes exactly the calls of a STAT; its native
  and its Python commit leave the same tree, bytes, fsyncs and markers;
* a counted turn is ANY call that lets go of the interpreter lock, the
  system's entropy included (ISSUE 45): ``os.urandom`` is wrapped beside the
  file-system functions, and a traced request, which minted an id a span, a
  staging id a drive and its data directory's name from ``uuid.uuid4()``
  (15 | 9 such calls a STAT, 40 | 22 a part PUT), makes none: ids are
  minted in the process (``minio_tpu/utils/ids.py``)."""
import builtins
import collections
import io
import os
import threading
import uuid
from dataclasses import replace

import numpy as np
import pytest

from minio_tpu import fault, native
from minio_tpu.objectlayer import ErasureObjects
from minio_tpu.objectlayer.datatypes import ObjectNotFound
from minio_tpu.objectlayer.multipart import upload_path
from minio_tpu.obs import metrics as mx
from minio_tpu.obs import spans
from minio_tpu.storage import (ErasureInfo, FileInfo, ObjectPartInfo,
                               XLStorage)
from minio_tpu.storage import durability
from minio_tpu.storage.health import DiskHealthCheck
from minio_tpu.storage.xlmeta import XL_META_FILE, XLMeta
from minio_tpu.storage.xlstorage import (META_MULTIPART, META_TMP,
                                         _FileWriter, _StagedFile)
from minio_tpu.utils import errors

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no native library")

#: every os function that names or holds a file: each lets go of the
#: interpreter lock and waits for it again
OS_CALLS = (
    "stat", "lstat", "fstat", "statvfs", "access", "open", "close", "read",
    "write", "pread", "pwrite", "fsync", "fdatasync", "mkdir", "rmdir",
    "unlink", "remove", "rename", "replace", "scandir", "listdir", "link",
    "symlink", "readlink", "truncate", "ftruncate", "utime", "chmod")
NATIVE_CALLS = ("stage_file", "close_fds", "commit_version", "commit_inline",
                "commit_part", "open_shard", "read_file")
#: the system's entropy: ``os.urandom`` is a system call made with the lock
#: released like any of the above (``uuid.uuid4`` reaches it through the
#: module attribute); it names no path, so it is counted on the request's
#: thread, inside the marked ``XLStorage`` methods and on every thread of
#: the program's pools (``POOL_THREADS``)
ENTROPY = "urandom"
POOL_THREADS = "minio-tpu-"

#: a rule that matches no drive: arming it is what moves the process onto
#: the Python sequences
NO_DRIVE = "disk:no-such-drive:read_at:delay(1)"


@pytest.fixture(autouse=True)
def _clean():
    fault.clear()
    yield
    fault.clear()


def _body(n, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _layer(root, n, parity):
    return ErasureObjects(
        [XLStorage(os.path.join(root, f"d{i:02d}")) for i in range(n)],
        default_parity=parity)


def _route_counters():
    snap = mx.counters_snapshot()
    return {(fam, route): snap.get(
        f'minio_tpu_storage_{fam}_total{{route="{route}"}}', 0)
        for fam in ("staged_files", "commits")
        for route in ("native", "python")}


def _route_delta(before):
    after = _route_counters()
    return {k: after[k] - before[k] for k in after}


def _file_reads(before=None):
    """The whole-file reads (an ``xl.meta``: ISSUE 40) by route, since
    ``before`` when given."""
    snap = mx.counters_snapshot()
    return {route: snap.get(
        f'minio_tpu_storage_file_reads_total{{route="{route}"}}', 0)
        - (before[route] if before else 0) for route in ("native", "python")}


def _part_commits(before=None):
    """The multipart part commits (ISSUE 43) by route, since ``before``
    when given."""
    snap = mx.counters_snapshot()
    return {route: snap.get(
        f'minio_tpu_storage_part_commits_total{{route="{route}"}}', 0)
        - (before[route] if before else 0) for route in ("native", "python")}


# --- (a) the count ----------------------------------------------------------

class _Turns:
    """Counts the wrapped calls by (thread, drive of the rename_data the
    thread is inside, name) while ``on``: those of the thread that made
    it (the request's), those inside a ``rename_data`` (or another of
    ``ops``), and any other thread's that name a path under ``root`` (a
    thread another test of the process left running is none of the
    PUT's). A pool thread's call that names no path (``os.scandir`` of a
    descriptor, ``os.unlink`` below one: ``shutil.rmtree``) is seen only
    inside one of ``ops``."""

    def __init__(self, monkeypatch, root, ops=("rename_data",)):
        self.calls = collections.Counter()
        self.on = False
        self.root = root
        self.request = threading.get_ident()
        self._drive = threading.local()
        for name in OS_CALLS:
            self._wrap(monkeypatch, os, name)
        self._wrap(monkeypatch, os, ENTROPY, pools=True)
        self._wrap(monkeypatch, builtins, "open")
        for name in NATIVE_CALLS:
            self._wrap(monkeypatch, native, name)
        for op in ops:
            self._mark(monkeypatch, op)

    def _mark(self, monkeypatch, op):
        turns, orig = self, getattr(XLStorage, op)

        def marked(disk, *a, **kw):
            turns._drive.base = disk.base
            try:
                return orig(disk, *a, **kw)
            finally:
                turns._drive.base = None

        monkeypatch.setattr(XLStorage, op, marked)

    def _wrap(self, monkeypatch, mod, name, pools=False):
        orig = getattr(mod, name)

        def counted(*a, **kw):
            if self.on:
                tid = threading.get_ident()
                drive = getattr(self._drive, "base", None)
                if tid == self.request or drive is not None or (
                        a and isinstance(a[0], str)
                        and a[0].startswith(self.root)) or (
                        pools and threading.current_thread().name
                        .startswith(POOL_THREADS)):
                    self.calls[(tid, drive, f"{mod.__name__}.{name}")] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(mod, name, counted)

    def __enter__(self):
        self.calls.clear()
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False

    def total(self):
        return sum(self.calls.values())

    def of_request(self):
        return sum(v for (t, _, _), v in self.calls.items()
                   if t == self.request)

    def minted(self):
        """The calls for the system's entropy, on any thread."""
        return sum(v for (_, _, name), v in self.calls.items()
                   if name == f"os.{ENTROPY}")

    def by_commit(self):
        out = collections.Counter()
        for (_, base, _), v in self.calls.items():
            if base is not None:
                out[base] += v
        return out


@pytest.mark.parametrize("n,parity,whole", [(12, 4, 60), (6, 2, 34)])
def test_put_takes_a_turn_a_drive(tmp_path, monkeypatch, n, parity, whole):
    """ISSUE 36's ceilings: the request's thread at most 16 calls, a
    drive's commit at most 3, the whole PUT at most 60 (12 drives) / 34
    (6): the tree before read 62, 21, 314 / 158."""
    ol = _layer(str(tmp_path), n, parity)
    ol.make_bucket("b")
    body = _body(10 << 20)
    ol.put_object("b", "warm", io.BytesIO(body), len(body))
    turns = _Turns(monkeypatch, str(tmp_path))
    before = _route_counters()
    with turns:
        ol.put_object("b", "k/new", io.BytesIO(body), len(body))
    assert ol.get_object_bytes("b", "k/new") == body
    assert turns.of_request() <= 16, turns.calls
    commits = turns.by_commit()
    assert len(commits) == n
    assert max(commits.values()) <= 3, turns.calls
    assert turns.total() <= whole, turns.calls
    assert _route_delta(before) == {
        ("staged_files", "native"): n, ("commits", "native"): n,
        ("staged_files", "python"): 0, ("commits", "python"): 0}
    # the readers, whose remaining turns the writers' cut made dearer: a
    # STAT is the bucket's stat (two before) and a quorum pass of ONE
    # native read a drive (ISSUE 40; four calls a drive before: 49 / 25,
    # and 50 / 26 before PR 36); a GET adds one open a shard file (with
    # its fstat: one native call) and one close of them all (62 / 32
    # before ISSUE 40, 86 / 44 before PR 36)
    reads = _file_reads()
    with turns:
        ol.get_object_info("b", "k/new")
    assert turns.total() == n + 1, turns.calls
    assert _file_reads(reads) == {"native": n, "python": 0}
    with turns:
        assert ol.get_object_bytes("b", "k/new") == body
    assert turns.total() <= 2 * n + 2, turns.calls
    # an overwrite: the xl.meta read finds a version and is still one call
    # (four before: 74 / 38 a PUT), so it takes the turns of a new key
    with turns:
        ol.put_object("b", "k/new", io.BytesIO(body[::-1]), len(body))
    assert turns.total() <= whole, turns.calls
    assert ol.get_object_bytes("b", "k/new") == body[::-1]


@pytest.mark.parametrize("n,parity,whole", [(12, 4, 26), (6, 2, 14)])
def test_inline_put_and_get_take_the_turns_of_a_stat(tmp_path, monkeypatch,
                                                     n, parity, whole):
    """ISSUE 39's ceilings: a 64 KiB PUT under a new key makes at most 26
    | 14 calls (12 | 6 drives) where a PUT of shard files makes 38 | 20:
    the bucket's stat, and a drive the xl.meta read (which finds none)
    and ONE native commit; nothing is staged, closed or cleaned. A GET of
    it makes exactly the calls of a STAT (13 | 7 since ISSUE 40, 49 | 25
    before, where a GET of shard files makes 26 | 14): no shard file is
    opened, and a journal that carries a shard is ONE native read."""
    ol = _layer(str(tmp_path), n, parity)
    ol.make_bucket("b")
    body = _body(64 << 10)
    ol.put_object("b", "warm", io.BytesIO(body), len(body))
    turns = _Turns(monkeypatch, str(tmp_path))
    before = _route_counters()
    with turns:
        ol.put_object("b", "k/new", io.BytesIO(body), len(body))
    assert turns.of_request() <= 2, turns.calls
    commits = turns.by_commit()
    assert len(commits) == n and max(commits.values()) <= 2, turns.calls
    assert turns.total() <= whole, turns.calls
    assert sum(v for (_, _, name), v in turns.calls.items()
               if name.endswith("commit_inline")) == n
    assert _route_delta(before) == {
        ("staged_files", "native"): 0, ("commits", "native"): n,
        ("staged_files", "python"): 0, ("commits", "python"): 0}
    for d in ol.disks:
        assert os.listdir(os.path.join(d.base, "b", "k", "new")) == [
            XL_META_FILE]
        assert os.listdir(os.path.join(d.base, META_TMP)) == []
    with turns:
        ol.get_object_info("b", "k/new")
    stat = turns.total()
    assert stat == n + 1, turns.calls
    reads = _file_reads()
    with turns:
        assert ol.get_object_bytes("b", "k/new") == body
    assert turns.total() == stat, turns.calls
    assert _file_reads(reads) == {"native": n, "python": 0}
    # an overwrite of it: the xl.meta read finds one (one call, four
    # before: 61 | 31), the commit is still one; the replaced version had
    # no directory to purge
    with turns:
        ol.put_object("b", "k/new", io.BytesIO(body[::-1]), len(body))
    assert turns.total() <= 2 * n + 1, turns.calls
    assert ol.get_object_bytes("b", "k/new") == body[::-1]


@pytest.mark.parametrize("n,parity,whole", [(12, 4, 37), (6, 2, 19)])
def test_part_put_commits_in_one_call_a_drive(tmp_path, monkeypatch, n,
                                              parity, whole):
    """ISSUE 43: a 10 MiB part PUT is 37 | 19 calls (12 | 6 drives): on the
    request's thread the upload's quorum pass (ONE native read a drive),
    ONE native call a drive to stage the shard file and one close of them
    all (25 | 13), and on the pool ONE native commit a drive. Before it
    read 121 | 61, all on the request's thread (this method on the parent
    commit dd56fce; PERF.md section 7, PR 40): ``rename_file`` (exists,
    makedirs' stat and mkdir, replace) + ``write_all`` (mkdir, open, write,
    close, replace) were 8 calls a drive, one drive after the other.
    Nothing is left staged: the commit removes the directory it emptied."""
    ol = _layer(str(tmp_path), n, parity)
    ol.make_bucket("b")
    body = _body(10 << 20)
    uid = ol.new_multipart_upload("b", "k/mp")
    first = ol.put_object_part("b", "k/mp", uid, 1, io.BytesIO(body),
                               len(body))
    turns = _Turns(monkeypatch, str(tmp_path),
                   ops=("rename_data", "commit_part"))
    before, reads = _part_commits(), _file_reads()
    with turns:
        part = ol.put_object_part("b", "k/mp", uid, 2, io.BytesIO(body[::-1]),
                                  len(body))
    assert turns.of_request() == n + n + 1, turns.calls
    commits = turns.by_commit()
    assert len(commits) == n and max(commits.values()) == 1, turns.calls
    assert turns.total() == whole, turns.calls
    assert sum(v for (t, _, name), v in turns.calls.items()
               if name.endswith("commit_part") and t != turns.request) == n
    assert _part_commits(before) == {"native": n, "python": 0}
    assert _file_reads(reads) == {"native": n, "python": 0}
    for d in ol.disks:
        assert os.listdir(os.path.join(d.base, META_TMP)) == []
    assert [p.part_number for p in
            ol.list_object_parts("b", "k/mp", uid).parts] == [1, 2]
    ol.complete_multipart_upload("b", "k/mp", uid, [first, part])
    assert ol.get_object_bytes("b", "k/mp") == body + body[::-1]


@pytest.mark.parametrize("n,parity", [(12, 4), (6, 2)])
@pytest.mark.parametrize("size", [10 << 20, 64 << 10],
                         ids=["shard_files", "inline"])
def test_delete_takes_two_reads_a_drive_and_the_python_removal(
        tmp_path, monkeypatch, n, parity, size):
    """A DELETE's calls, which ISSUE 40 has settled between PERF.md's two
    counts. The request's thread: the bucket's stat, twice, and the lock
    check's quorum pass, ONE native read a drive (four calls before).
    Inside a drive's ``delete_version``: its own ``xl.meta`` read (one
    call, four before), then the removal, still the Python sequence, a
    call a step: ``isdir`` + ``shutil.rmtree`` (lstat, open, fstat,
    scandir, unlink, close, rmdir) of the data directory (shard files
    only), the same of the object's directory, and the prefix pruned (two
    rmdir): 19 calls a drive, 10 for an inline version. 12 | 6 drives: of
    shard files 242 | 122 (314 | 158 before, PR 36's count), inline 134 |
    68 (206 | 104 before). Without the mark on ``delete_version`` a pool
    thread's ``fstat``, ``scandir``, ``unlink`` and ``close`` name no
    path and go unseen: so PR 39 read 170 | 86 and 110 | 56."""
    ol = _layer(str(tmp_path), n, parity)
    ol.make_bucket("b")
    body = _body(size)
    ol.put_object("b", "warm", io.BytesIO(body), len(body))
    ol.put_object("b", "k/gone", io.BytesIO(body), len(body))
    turns = _Turns(monkeypatch, str(tmp_path),
                   ops=("rename_data", "delete_version"))
    reads = _file_reads()
    with turns:
        ol.delete_object("b", "k/gone")
    assert _file_reads(reads) == {"native": 2 * n, "python": 0}
    assert turns.of_request() == n + 2, turns.calls
    drives = turns.by_commit()
    a_drive = 19 if size > (128 << 10) else 10
    assert len(drives) == n and max(drives.values()) <= a_drive, turns.calls
    assert turns.total() <= n + 2 + n * a_drive, turns.calls
    with pytest.raises(ObjectNotFound):
        ol.get_object_info("b", "k/gone")
    for d in ol.disks:
        assert os.listdir(os.path.join(d.base, "b")) == ["warm"]


#: what a served request asked of ``os.urandom`` before ISSUE 45 (this test
#: on the parent commit b324023, 12 | 6 drives): the request id and the
#: root's span id, a span id a traced storage call and a span of the object
#: layer, a staging id a PUT and one more a drive in its commit, the data
#: directory's name
MINTED_BEFORE = {
    "stat": (15, 9), "get_inline": (16, 10), "get_shard_files": (16, 10),
    "put_inline": (30, 18), "put_shard_files": (18, 12), "part_put": (40, 22),
    "delete_inline": (28, 16), "delete_shard_files": (28, 16)}


@pytest.mark.parametrize("n,parity", [(12, 4), (6, 2)])
@pytest.mark.parametrize("kind", sorted(MINTED_BEFORE))
def test_a_request_asks_the_system_for_no_random_number(
        tmp_path, monkeypatch, kind, n, parity):
    """ISSUE 45: an id is a name and is minted in the process
    (``minio_tpu/utils/ids.py``), so a traced request of an unversioned
    bucket makes no call of ``os.urandom`` on any of its threads, where
    ``uuid.uuid4()`` made one an id (``MINTED_BEFORE``), each a turn at the
    interpreter lock that the counts above did not see. The request is
    traced as a served one is: the request id minted, a sampled root
    opened, every storage call and object-layer span recorded under it
    with a span id of its own."""
    ol = _layer(str(tmp_path), n, parity)
    ol.make_bucket("b")
    small, big = _body(64 << 10), _body(10 << 20)
    ol.put_object("b", "small", io.BytesIO(small), len(small))
    ol.put_object("b", "big", io.BytesIO(big), len(big))
    uid = ol.new_multipart_upload("b", "mp")
    ol.put_object_part("b", "mp", uid, 1, io.BytesIO(big), len(big))
    act = {
        "stat": lambda: ol.get_object_info("b", "big"),
        "get_inline": lambda: ol.get_object_bytes("b", "small"),
        "get_shard_files": lambda: ol.get_object_bytes("b", "big"),
        "put_inline": lambda: ol.put_object(
            "b", "new", io.BytesIO(small), len(small)),
        "put_shard_files": lambda: ol.put_object(
            "b", "new", io.BytesIO(big), len(big)),
        "part_put": lambda: ol.put_object_part(
            "b", "mp", uid, 2, io.BytesIO(big), len(big)),
        "delete_inline": lambda: ol.delete_object("b", "small"),
        "delete_shard_files": lambda: ol.delete_object("b", "big"),
    }[kind]
    turns = _Turns(monkeypatch, str(tmp_path),
                   ops=("rename_data", "commit_part", "delete_version"))
    with turns:
        rid = spans.new_trace_id()
        root, tok = spans.begin_request(rid)
        try:
            act()
        finally:
            recorded = list(spans._active[rid]["spans"])
            spans.finish_request(root, tok, name=f"s3.{kind}",
                                 duration_s=0.0)
    assert root.sampled and len(rid) == 32
    # every span is still opened and recorded: a storage call a drive at
    # the least, each under an id of its own
    ids = [s["span_id"] for s in recorded] + [root.span_id]
    assert len(recorded) >= n, recorded
    assert len(set(ids)) == len(ids) and all(
        len(i) == 16 and int(i, 16) >= 0 for i in ids), ids
    assert turns.minted() == 0, (MINTED_BEFORE[kind], turns.calls)


# --- (b) the two sequences leave the same tree ------------------------------

def _fi(vid="", ddir=None, data=None, size=11):
    return FileInfo(
        volume="bucket", name="obj", version_id=vid,
        data_dir=ddir if ddir is not None else str(uuid.uuid4()),
        mod_time=1700000000.0 + size, size=size, data=data,
        metadata={"content-type": "text/plain", "etag": "e" * 32},
        parts=[ObjectPartInfo(number=1, size=size, actual_size=size)],
        erasure=ErasureInfo(data_blocks=4, parity_blocks=2,
                            block_size=1 << 20, index=1,
                            distribution=list(range(1, 7))))


def _tree(root):
    """Every directory and file below ``root`` with the files' bytes."""
    out = {}
    for d, dirs, files in os.walk(root):
        rel = os.path.relpath(d, root)
        out[rel + "/"] = None
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(rel, f)] = fh.read()
    return out


def _fsyncs():
    snap = mx.counters_snapshot()
    return {k: snap.get(f'minio_tpu_durability_fsync_total{{kind="{k}"}}', 0)
            for k in ("file", "dir")}


def _commit(disk, key, fi, shard=b"shard-bytes", parts=(1,)):
    """Stage ``parts`` and commit ``fi`` the way put_object does."""
    tmp_id = f"{os.getpid()}-{uuid.uuid4()}"
    if fi.data is None:
        for p in parts:
            w = disk.create_file_writer(
                META_TMP, f"{tmp_id}/{fi.data_dir}/part.{p}")
            w.write(shard)
            w.write(memoryview(np.frombuffer(shard, dtype=np.uint8)))
            w.close()
    disk.rename_data(META_TMP, tmp_id, fi, "bucket", key)


#: name -> (key, the versions committed one after another)
CASES = {
    "new_key": ("obj", lambda: [_fi()]),
    "overwrite": ("obj", lambda: [_fi(size=11), _fi(size=12)]),
    "versioned": ("obj", lambda: [_fi(vid=str(uuid.uuid4()), size=11),
                                  _fi(vid=str(uuid.uuid4()), size=12)]),
    "nested_prefix": ("a/b/c/obj", lambda: [_fi()]),
    "same_data_dir": ("obj", lambda: (lambda f: [f, replace(f, size=12)])(
        _fi())),
    "inline_data": ("obj", lambda: [_fi(ddir="", data=b"tiny")]),
    # an inline version (ISSUE 39): this drive's shard in xl.meta's Data
    "inline_shard": ("obj", lambda: [_fi(data=b"framed-shard" * 700)]),
    "inline_nested": ("a/b/c/obj", lambda: [_fi(data=b"s" * 33)]),
    "inline_over_inline": ("obj", lambda: [_fi(size=11, data=b"one" * 99),
                                           _fi(size=12, data=b"two" * 99)]),
    "inline_over_files": ("obj", lambda: [_fi(size=11),
                                          _fi(size=12, data=b"two" * 99)]),
    "files_over_inline": ("obj", lambda: [_fi(size=11, data=b"one" * 99),
                                          _fi(size=12)]),
    "inline_versioned": ("obj", lambda: [
        _fi(vid=str(uuid.uuid4()), size=11, data=b"one" * 99),
        _fi(vid=str(uuid.uuid4()), size=12, data=b"two" * 99)]),
}


UPLOAD = "5d41402abc4b2a76b9719d911017c592/upload-one"

#: a multipart part's commit (ISSUE 43). name -> (whether the upload's
#: directory is there, as the Create left it; the (part number, shard,
#: sidecar) committed one after another)
PART_CASES = {
    "part_in_its_upload": (True, [(3, b"shard-three", b"meta-three")]),
    # a drive that missed the Create: the upload's directories are made
    "part_upload_dir_missing": (False, [(3, b"shard-three", b"meta-three")]),
    "part_sent_again": (True, [(3, b"shard-three", b"meta-three"),
                               (3, b"shard-again", b"meta-again" * 40)]),
    "part_after_part": (True, [(1, b"one" * 999, b"meta-one"),
                               (2, b"", b"meta-two")]),
}


def _commit_part(disk, num, shard, sidecar, tmp_id=None,
                 volume=META_MULTIPART, upload=UPLOAD):
    """Stage a part's shard file and commit it the way put_object_part
    does."""
    tmp_id = tmp_id or f"{os.getpid()}-{uuid.uuid4()}"
    w = disk.create_file_writer(META_TMP, f"{tmp_id}/part.{num}")
    w.write(shard)
    w.close()
    disk.commit_part(META_TMP, f"{tmp_id}/part.{num}", volume,
                     f"{upload}/part.{num}", sidecar)


def _part_sequences_leave_the_same_tree(tmp_path, markers, case, mode):
    there, parts = PART_CASES[case]
    seen = {}
    for route in ("native", "python"):
        disk = XLStorage(str(tmp_path / route))
        if there:
            disk.write_all(META_MULTIPART, f"{UPLOAD}/{XL_META_FILE}",
                           b"the upload's journal")
        if route == "python":
            fault.arm(NO_DRIVE)
        del markers[:]
        syncs, routes, staged = _fsyncs(), _part_commits(), _route_counters()
        for num, shard, sidecar in parts:
            _commit_part(disk, num, shard, sidecar)
        after = _fsyncs()
        seen[route] = {
            "tree": _tree(disk.base),
            "fsyncs": {k: after[k] - syncs[k] for k in after},
            "markers": [(k, os.path.relpath(p, disk.base))
                        for k, p in markers],
            "routes": _part_commits(routes),
            "staged": _route_delta(staged)[("staged_files", route)]}
        fault.clear()
    nat, py = seen["native"], seen["python"]
    n = len(parts)
    assert nat["routes"] == {"native": n, "python": 0}
    assert py["routes"] == {"native": 0, "python": n}
    assert nat["staged"] == py["staged"] == n
    assert nat["tree"] == py["tree"]
    tree = nat["tree"]
    # the staging directory the shard left empty is gone on both routes
    assert [p for p in tree if p.startswith(".minio.sys/tmp")] == [
        ".minio.sys/tmp/"]
    up = f"{META_MULTIPART}/{UPLOAD}"
    last = {num: (shard, sidecar) for num, shard, sidecar in parts}
    assert sorted(p for p in tree if p.startswith(up + "/part.")) == sorted(
        f"{up}/part.{num}{ext}" for num in last for ext in ("", ".meta"))
    for num, (shard, sidecar) in last.items():
        assert tree[f"{up}/part.{num}"] == shard
        assert tree[f"{up}/part.{num}.meta"] == sidecar
    assert (f"{up}/{XL_META_FILE}" in tree) == there
    assert nat["fsyncs"] == py["fsyncs"]
    assert nat["markers"] == py["markers"]
    # under ``always``: the staged file at its close and again as
    # durable_replace's source, the sidecar's tmp; the upload's directory
    # after each of the two renames (docs/durability.md)
    assert nat["fsyncs"] == ({"file": 3 * n, "dir": 2 * n}
                             if mode == "always" else {"file": 0, "dir": 0})
    assert nat["markers"] == ([
        ("file", f"{up}/part.{num}{ext}") for num, _, _ in parts
        for ext in ("", ".meta")] if mode == "batched" else [])


@pytest.mark.parametrize("mode", ["off", "batched", "always"])
@pytest.mark.parametrize("case", sorted(CASES) + sorted(PART_CASES))
def test_native_and_python_sequences_leave_the_same_tree(
        tmp_path, monkeypatch, case, mode):
    monkeypatch.setenv("MINIO_TPU_FSYNC", mode)
    markers = []
    fl = durability.flusher()
    monkeypatch.setattr(fl, "enqueue_tree",
                        lambda p: markers.append(("tree", p)))
    monkeypatch.setattr(fl, "enqueue", lambda p: markers.append(("file", p)))
    if case in PART_CASES:
        _part_sequences_leave_the_same_tree(tmp_path, markers, case, mode)
        return
    key, versions = CASES[case]
    versions = versions()
    seen = {}
    for route in ("native", "python"):
        disk = XLStorage(str(tmp_path / route))
        disk.make_vol("bucket")
        if route == "python":
            fault.arm(NO_DRIVE)
        del markers[:]
        syncs, routes = _fsyncs(), _route_counters()
        for fi in versions:
            _commit(disk, key, fi, parts=(1, 2))
        after = _fsyncs()
        seen[route] = {
            "tree": _tree(disk.base),
            "fsyncs": {k: after[k] - syncs[k] for k in after},
            "markers": [(k, os.path.relpath(p, disk.base))
                        for k, p in markers],
            "routes": _route_delta(routes)}
        fault.clear()
    nat, py = seen["native"], seen["python"]
    inline = case == "inline_data"
    n = len(versions)
    files = sum(1 for v in versions if v.data is None)
    # the rule: a version with a data directory commits natively, its
    # shard in files or in xl.meta; FS mode's whole copy (no data
    # directory) never does
    assert nat["routes"] == {
        ("staged_files", "native"): 2 * files,
        ("commits", "native"): 0 if inline else n,
        ("staged_files", "python"): 0,
        ("commits", "python"): n if inline else 0}
    assert py["routes"][("commits", "python")] == n
    assert py["routes"][("commits", "native")] == 0
    assert nat["tree"] == py["tree"]
    tree = nat["tree"]
    assert ".minio.sys/tmp/" in tree
    assert not [p for p in tree if p.startswith(".minio.sys/tmp/")
                and p != ".minio.sys/tmp/"], "staging left behind"
    assert tree[f"bucket/{key}/{XL_META_FILE}"]
    kept = versions if case.endswith("versioned") else versions[-1:]
    live = {v.data_dir for v in kept if v.data_dir and v.data is None}
    ddirs = {p.split("/")[-2] for p in tree
             if p.startswith(f"bucket/{key}/") and p.endswith("/")
             and p != f"bucket/{key}/"}
    assert ddirs == live  # a replaced version's data directory is purged
    for d in live:
        assert tree[f"bucket/{key}/{d}/part.2"] == b"shard-bytes" * 2
    # an inline version lives in the journal's Data and nowhere else, and
    # a replaced one's entry is gone with it
    held = XLMeta.load(tree[f"bucket/{key}/{XL_META_FILE}"]).data
    assert held == {v.data_dir: v.data for v in kept
                    if v.data_dir and v.data is not None}
    assert nat["fsyncs"] == py["fsyncs"]
    assert nat["markers"] == py["markers"]
    if mode == "always" and not inline:
        # a version of shard files: 2 of them + xl.meta's tmp; the staged
        # directory, the object directory after each of the two renames.
        # An inline one: xl.meta's tmp; the object directory after its
        # rename (docs/durability.md)
        assert nat["fsyncs"] == {"file": 3 * files + (n - files),
                                 "dir": 3 * files + (n - files)}
    elif mode != "always":
        assert nat["fsyncs"] == {"file": 0, "dir": 0}
    if mode == "batched" and not inline:
        assert nat["markers"] == [
            m for v in versions
            for m in ([("tree", f"bucket/{key}/{v.data_dir}")]
                      if v.data is None else [])
            + [("file", f"bucket/{key}/{XL_META_FILE}")]]
    elif mode != "batched":
        assert nat["markers"] == []
    got = XLStorage(str(tmp_path / "native")).read_version("bucket", key)
    assert got.size == versions[-1].size


_SHIM = r"""
#define _GNU_SOURCE
#include <dlfcn.h>
#include <stdio.h>
#include <stdlib.h>
#include <unistd.h>
static void note(const char* op, const char* a, const char* b) {
  const char* log = getenv("FS_ORDER_LOG");
  if (!log) return;
  FILE* f = fopen(log, "a");
  if (!f) return;
  fprintf(f, "%s %s %s\n", op, a, b);
  fclose(f);
}
int fsync(int fd) {
  static int (*real)(int);
  if (!real) real = (int (*)(int))dlsym(RTLD_NEXT, "fsync");
  char link[64], path[4096];
  snprintf(link, sizeof link, "/proc/self/fd/%d", fd);
  ssize_t n = readlink(link, path, sizeof path - 1);
  path[n > 0 ? n : 0] = 0;
  note("fsync", path, "-");
  return real(fd);
}
int rename(const char* a, const char* b) {
  static int (*real)(const char*, const char*);
  if (!real) real = (int (*)(const char*, const char*))dlsym(RTLD_NEXT,
                                                             "rename");
  note("rename", a, b);
  return real(a, b);
}
"""

_ORDER_DRIVER = """
import os, sys
from minio_tpu import fault
from minio_tpu.storage import XLStorage
sys.path.insert(0, os.path.dirname(sys.argv[1]))
import test_put_turns as t
route, base = sys.argv[2], sys.argv[3]
disk = XLStorage(base)
disk.make_vol("bucket")
if route == "python":
    fault.arm(t.NO_DRIVE)
os.environ["FS_ORDER_LOG"] = base + ".log"
for fi in (t._fi(ddir="dd-one", size=11), t._fi(ddir="dd-two", size=12),
           t._fi(ddir="dd-three", size=13, data=b"framed-shard" * 9)):
    t._commit(disk, "a/obj", fi)
t._commit_part(disk, 7, b"shard-seven", b"meta-seven")
"""


def _staged_name(path, base):
    """``path`` below the drive ``base``; what is staged under
    ``.minio.sys/tmp`` by its last component (the ids are minted), and
    ``xl.meta``'s tmp (or a part's sidecar's), which has a name of its own
    on either route, as ``tmp/xl.meta``."""
    if path == "-":
        return path
    rel = os.path.relpath(path, base)
    if not rel.startswith(".minio.sys/tmp/"):
        return rel
    last = rel.split("/")[-1]
    return "tmp/" + (last if last in ("part.1", "part.7", "dd-one", "dd-two")
                     else "xl.meta")


def test_always_issues_the_python_sequences_fsyncs_in_their_order(tmp_path):
    """Under ``always`` the native sequence fsyncs what the Python one
    fsyncs, between the same renames: both are watched from below, by a
    shim around libc's ``fsync`` and ``rename``."""
    import shutil
    import subprocess
    import sys
    if shutil.which("gcc") is None:
        pytest.skip("no gcc for the shim")
    shim = tmp_path / "shim.so"
    (tmp_path / "shim.c").write_text(_SHIM)
    subprocess.run(["gcc", "-shared", "-fPIC", "-o", str(shim),
                    str(tmp_path / "shim.c"), "-ldl"], check=True)
    logs = {}
    for route in ("native", "python"):
        base = str(tmp_path / route)
        env = dict(os.environ, LD_PRELOAD=str(shim), MINIO_TPU_FSYNC="always",
                   JAX_PLATFORMS="cpu")
        env.pop("FS_ORDER_LOG", None)
        subprocess.run([sys.executable, "-c", _ORDER_DRIVER, __file__, route,
                        base], check=True, env=env, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
        with open(base + ".log") as f:
            logs[route] = [(op, _staged_name(a, base), _staged_name(b, base))
                           for op, a, b in map(str.split, f)]
    one = [("fsync", "tmp/part.1", "-"),
           ("fsync", "tmp/dd-one", "-"),
           ("rename", "tmp/dd-one", "bucket/a/obj/dd-one"),
           ("fsync", "bucket/a/obj", "-"),
           ("fsync", "tmp/xl.meta", "-"),
           ("rename", "tmp/xl.meta", "bucket/a/obj/xl.meta"),
           ("fsync", "bucket/a/obj", "-")]
    assert logs["native"] == logs["python"]
    assert logs["native"][:7] == one
    # the third version is inline: durable_replace of xl.meta and no more
    assert logs["native"][14:17] == one[4:]
    # a multipart part (ISSUE 43): the staged shard at its close, then
    # durable_replace of it and durable_replace of its sidecar
    up = f"{META_MULTIPART}/{UPLOAD}"
    assert logs["native"][17:] == [
        ("fsync", "tmp/part.7", "-"),
        ("fsync", "tmp/part.7", "-"),
        ("rename", "tmp/part.7", f"{up}/part.7"),
        ("fsync", up, "-"),
        ("fsync", "tmp/xl.meta", "-"),
        ("rename", "tmp/xl.meta", f"{up}/part.7.meta"),
        ("fsync", up, "-")]


def test_staged_file_means_what_file_writer_means(tmp_path, monkeypatch):
    """write, close, abort and a second close of the native twin."""
    disk = XLStorage(str(tmp_path / "d"))
    w = disk.create_file_writer(META_TMP, "t1/dd/part.1")
    assert isinstance(w, _StagedFile)
    os.pwrite(w.fileno(), b"abc", 0)
    w.close()
    w.close()  # harmless, and closes nobody else's fd
    assert disk.read_all(META_TMP, "t1/dd/part.1") == b"abc"
    w = disk.create_file_writer(META_TMP, "t1/dd/part.2")  # directory there
    w.write(b"x")
    w.abort()
    assert disk.list_dir(META_TMP, "t1/dd") == ["part.1"]
    # the files of a PUT's drives close in one call; one fsync that fails
    # under ``always`` is that file's error alone
    monkeypatch.setenv("MINIO_TPU_FSYNC", "always")
    ws = [disk.create_file_writer(META_TMP, f"t2/dd/part.{i}")
          for i in range(3)]
    monkeypatch.setattr(native, "close_fds", lambda fds, sync: (
        [os.close(fd) for fd in fds], [0, 5, 0])[1])
    errs = _StagedFile.close_many(ws)
    assert [type(e) for e in errs] == [type(None), OSError, type(None)]
    assert errs[1].errno == 5 and errs[1].filename.endswith("t2/dd/part.1")
    fault.arm(NO_DRIVE)
    assert isinstance(disk.create_file_writer(META_TMP, "t3/dd/part.1"),
                      _FileWriter)


# --- (c) the errors ---------------------------------------------------------

def _outcome(fn):
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — the outcome IS the error
        return type(e), getattr(e, "errno", None)
    return None


@pytest.mark.parametrize("route", ["native", "python"])
def test_commit_errors_are_the_same_on_both_routes(tmp_path, route):
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_vol("bucket")
    if route == "python":
        fault.arm(NO_DRIVE)

    def staged(tmp_id, fi):
        w = disk.create_file_writer(META_TMP,
                                    f"{tmp_id}/{fi.data_dir}/part.1")
        w.write(b"s")
        w.close()

    # the volume is not there
    fi = _fi()
    staged("t-vol", fi)
    with pytest.raises(errors.VolumeNotFound):
        disk.rename_data(META_TMP, "t-vol", fi, "nobucket", "obj")
    assert not os.path.exists(os.path.join(disk.base, "nobucket"))
    # nothing was staged
    with pytest.raises(errors.FileNotFound):
        disk.rename_data(META_TMP, "t-none", _fi(), "bucket", "obj")
    with pytest.raises(errors.VolumeNotFound):
        disk.write_all("nobucket", "a/b", b"x")
    with pytest.raises(errors.VolumeNotFound):
        disk.write_all("nobucket", "top", b"x")
    assert not os.path.exists(os.path.join(disk.base, "nobucket"))
    disk.write_all("bucket", "deep/er/file", b"x")  # parents made
    disk.write_all("bucket", "deep/er/file", b"y")
    assert disk.read_all("bucket", "deep/er/file") == b"y"
    # a whole-file read, and what it says of what is not there
    big = bytes(range(256)) * 300
    disk.write_all("bucket", "deep/big", big)
    assert disk.read_all("bucket", "deep/big") == big
    assert disk.read_all("bucket", "deep/er/file") == b"y"
    with pytest.raises(errors.FileNotFound):
        disk.read_all("bucket", "deep/none")
    with pytest.raises(errors.VolumeNotFound):
        disk.read_all("nobucket", "deep/none")
    with pytest.raises(errors.IsNotRegular):
        disk.read_all("bucket", "deep/er")
    # a reader's open: open + fstat, one native call or two from Python
    disk.write_all("bucket", "deep/er/file", b"0123456789")
    r = disk.read_file_at("bucket", "deep/er/file")
    assert r.read_at(2, 3) == b"234" and os.pread(r.fileno(), 2, 8) == b"89"
    r.close()
    r.close()
    with pytest.raises(errors.IsNotRegular):
        disk.read_file_at("bucket", "deep/er")
    with pytest.raises(errors.FileNotFound):
        disk.read_file_at("bucket", "deep/er/none")
    # the object's directory is in the way as a regular file
    disk.write_all("bucket", "file", b"x")
    fi = _fi()
    staged("t-file", fi)
    out = _outcome(lambda: disk.rename_data(META_TMP, "t-file", fi,
                                            "bucket", "file/obj"))
    assert out[0] is NotADirectoryError, out
    # a multipart part's commit (ISSUE 43). No shard file was staged:
    # nothing is made for it
    with pytest.raises(errors.FileNotFound):
        disk.commit_part(META_TMP, "t-none/part.1", META_MULTIPART,
                         f"{UPLOAD}/part.1", b"m")
    assert disk.list_dir(META_MULTIPART, "") == []
    # the volume is not there: it is not made, the shard stays staged
    with pytest.raises(errors.VolumeNotFound):
        _commit_part(disk, 1, b"s", b"m", tmp_id="t-pvol", volume="nobucket")
    assert not os.path.exists(os.path.join(disk.base, "nobucket"))
    assert disk.list_dir(META_TMP, "t-pvol") == ["part.1"]
    # the upload's directory is there (the Create made it) or not (this
    # drive missed it): the part and its sidecar land, the staging goes
    for tmp_id, num in (("t-made", 1), ("t-there", 2)):
        _commit_part(disk, num, b"shard", b"sidecar", tmp_id=tmp_id)
        assert disk.read_all(META_MULTIPART, f"{UPLOAD}/part.{num}") \
            == b"shard"
        assert disk.read_all(META_MULTIPART, f"{UPLOAD}/part.{num}.meta") \
            == b"sidecar"
        assert not os.path.exists(os.path.join(disk.base, META_TMP, tmp_id))
    # the upload's directory, or the part's name, is in the way
    disk.write_all(META_MULTIPART, "hash/upload", b"x")
    out = _outcome(lambda: _commit_part(disk, 1, b"s", b"m", tmp_id="t-nd",
                                        upload="hash/upload"))
    assert out == (NotADirectoryError, 20), out
    disk.write_all(META_MULTIPART, f"{UPLOAD}/part.9/below", b"x")
    out = _outcome(lambda: _commit_part(disk, 9, b"s", b"m", tmp_id="t-isd"))
    assert out == (IsADirectoryError, 21), out
    assert disk.list_dir(META_MULTIPART, UPLOAD) == [
        "part.1", "part.1.meta", "part.2", "part.2.meta", "part.9/"]


def _kill(disk):
    """``xl-8p4-12d-1down``'s dead drive: the directory renamed aside and
    a regular file at its path."""
    os.rename(disk.base, disk.base + ".aside")
    with open(disk.base, "wb"):
        pass
    return disk


def _journal_with_a_shard(n):
    fi = _fi(data=_body(n))
    meta = XLMeta()
    meta.add_version(fi)
    return meta.dump()


_ENOTDIR = (NotADirectoryError, 20)

#: name -> (what lies at ``bucket/a/file``, or None for a dead drive; what
#: is read; probe_volume; the error, None for the bytes) of a whole-file read
READS = {
    "small": (b"0123456789", "bucket", "a/file", True, None),
    "empty": (b"", "bucket", "a/file", True, None),
    "at_the_one_call_buffer": (
        lambda: _body(native.READ_FILE_ONE_CALL), "bucket", "a/file", True,
        None),
    "over_the_one_call_buffer": (
        lambda: _body(native.READ_FILE_ONE_CALL + 1), "bucket", "a/file",
        True, None),
    "far_over_the_one_call_buffer": (
        lambda: _body(3 * native.READ_FILE_ONE_CALL + 17), "bucket",
        "a/file", True, None),
    # a 64 KiB object's shard at 8+4 (8.5 KiB) and one at the 128 KiB
    # threshold on 4+2 (~33 KiB): inside ONE call
    "journal_with_a_shard": (
        lambda: _journal_with_a_shard(8 << 10), "bucket", "a/file", True,
        None),
    "journal_at_the_threshold": (
        lambda: _journal_with_a_shard(33 << 10), "bucket", "a/file", True,
        None),
    "missing_file": (b"x", "bucket", "a/none", True,
                     (errors.FileNotFound, None)),
    "missing_file_unprobed": (b"x", "bucket", "a/none", False,
                              (errors.FileNotFound, None)),
    "missing_prefix": (b"x", "bucket", "none/file", True,
                       (errors.FileNotFound, None)),
    "missing_volume": (b"x", "nobucket", "a/file", True,
                       (errors.VolumeNotFound, None)),
    "missing_volume_unprobed": (b"x", "nobucket", "a/file", False,
                                (errors.FileNotFound, None)),
    "directory": (b"x", "bucket", "a", True, (errors.IsNotRegular, None)),
    "below_a_file": (b"x", "bucket", "a/file/below", True, _ENOTDIR),
    "dead_drive": (None, "bucket", "a/file", True, _ENOTDIR),
}


@pytest.mark.parametrize("route", ["native", "python"])
@pytest.mark.parametrize("case", sorted(READS))
def test_file_reads_mean_the_same_on_both_routes(tmp_path, monkeypatch, case,
                                                 route):
    """ISSUE 40: ``_read_all_inner`` is one native call, or open, fstat,
    read and close from Python: the same bytes or the same error, errno
    for errno (``ENOENT`` -> FileNotFound, or VolumeNotFound by the probe
    AFTER the failure and only when asked; a directory -> IsNotRegular;
    the dead drive's ``ENOTDIR`` leaves as the OSError the health tracker
    fences on), the calls counted, and the route counter says which."""
    body, volume, path, probe, want = READS[case]
    disk = XLStorage(str(tmp_path / "d"))
    if body is None:
        _kill(disk)
    else:
        disk.make_vol("bucket")
        body = body() if callable(body) else body
        disk.write_all("bucket", "a/file", body)
    if route == "python":
        fault.arm(NO_DRIVE)
    turns = _Turns(monkeypatch, str(tmp_path))
    lib, entered = native.load_native(), []
    monkeypatch.setattr(lib, "mt_read_file", lambda *a, _c=lib.mt_read_file: (
        entered.append(1), _c(*a))[1])
    reads = _file_reads()
    got = []
    with turns:
        outcome = _outcome(lambda: got.append(
            disk._read_all_inner(volume, path, probe_volume=probe)))
    assert outcome == want
    if want is None:
        assert got == [body] and type(got[0]) is bytes
    assert _file_reads(reads) == {
        "native": int(route == "native"), "python": int(route == "python")}
    names = collections.Counter()
    for (_, _, name), v in turns.calls.items():
        names[name.split(".")[-1]] += v
    probed = {"stat": 1} if case in (
        "missing_file", "missing_prefix", "missing_volume") else {}
    if route == "native":
        assert names == {"read_file": 1, **probed}
        # a file over the buffer: the first call sizes it and reads
        # nothing, the second reads it into a buffer of its size
        assert len(entered) == (2 if "over_the_one_call" in case else 1)
    elif want is None:
        assert names == {"open": 1, "fstat": 1, "close": 1,
                         **({"read": 1} if body else {})}
    elif case == "directory":
        assert names == {"open": 1, "fstat": 1, "read": 1, "close": 1}
    else:
        assert names == {"open": 1, **probed}
    assert route == "native" or not entered
    if want is None:
        # the traced call, and the journal as its readers get it
        assert disk.read_all(volume, path) == body
    if case.startswith("journal"):
        disk.write_all("bucket", "obj/" + XL_META_FILE, body)
        fi = disk.read_version("bucket", "obj", read_data=True)
        assert fi.data == XLMeta.load(body).data[fi.data_dir]
        assert disk.read_version("bucket", "obj").data is None


def test_a_torn_journal_is_found_and_quarantined_on_both_routes(tmp_path):
    """The read checks nothing: ``XLMeta.load`` finds a torn journal and
    the Python side moves it aside, whichever route read it."""
    for route in ("native", "python"):
        disk = XLStorage(str(tmp_path / route))
        disk.make_vol("bucket")
        disk.write_all("bucket", "obj/" + XL_META_FILE,
                       _journal_with_a_shard(8 << 10)[:-7])
        if route == "python":
            fault.arm(NO_DRIVE)
        with pytest.raises(errors.FileCorrupt):
            disk.read_version("bucket", "obj")
        assert disk.list_dir("bucket", "obj") == ["xl.meta.corrupt"]
        with pytest.raises(errors.FileNotFound):
            disk.read_version("bucket", "obj")


@pytest.mark.parametrize("route", ["native", "python"])
def test_dead_drive_fails_with_what_the_tracker_fences_on(tmp_path, route,
                                                          monkeypatch):
    """``xl-8p4-12d-1down``'s dead drive: a regular file at the drive's
    path. Staging and committing there fail with ENOTDIR, which the
    health tracker counts (it is no benign answer), the PUT is
    acknowledged at quorum and nothing is left staged anywhere."""
    ol = ErasureObjects(
        [DiskHealthCheck(XLStorage(str(tmp_path / f"d{i:02d}")),
                         trip_threshold=1000) for i in range(6)],
        default_parity=2)
    ol.make_bucket("b")
    dead = ol.disks[2]
    _kill(dead.inner)
    orig_delete_path = XLStorage.delete_path
    if route == "python":
        fault.arm(NO_DRIVE)
    for fn in (lambda: dead.inner.create_file_writer(META_TMP, "t/d/part.1"),
               lambda: dead.inner.read_file_at("b", "o/d/part.1"),
               lambda: dead.inner.read_all("b", "o/xl.meta"),
               lambda: dead.inner.rename_data(META_TMP, "t", _fi(), "b",
                                              "o")):
        assert _outcome(fn) == (NotADirectoryError, 20)
    # a whole-file write there still answers "no such volume", as it did
    for path in ("top", "deep/er/file"):
        assert _outcome(lambda: dead.inner.write_all("b", path, b"x"))[0] \
            is errors.VolumeNotFound
    errs = dead.total_errors
    body = _body(3 << 20)
    swept = []
    monkeypatch.setattr(XLStorage, "delete_path", lambda disk, *a, **kw: (
        swept.append(disk.base), orig_delete_path(disk, *a, **kw))[1])
    ol.put_object("b", "k", io.BytesIO(body), len(body))
    assert dead.total_errors > errs
    assert ol.get_object_bytes("b", "k") == body
    # the drives that committed removed their own staging: only the one
    # that missed the PUT is visited again (all six before ISSUE 40, two
    # turns a drive: what a PUT paid for a dead drive in the set)
    assert swept == [dead.inner.base]
    for d in ol.disks:
        if d is not dead:
            assert d.list_dir(META_TMP, "") == []


# --- (d) a fault armed: the Python sequence, and the counters say so --------

def test_armed_fault_takes_the_python_route(tmp_path):
    ol = _layer(str(tmp_path), 6, 2)
    ol.make_bucket("b")
    body = _body(3 << 20)
    before, reads = _route_counters(), _file_reads()
    ol.put_object("b", "native", io.BytesIO(body), len(body))
    assert _route_delta(before) == {
        ("staged_files", "native"): 6, ("commits", "native"): 6,
        ("staged_files", "python"): 0, ("commits", "python"): 0}
    # the commit's xl.meta read a drive (it finds none), then a STAT's pass
    assert ol.get_object_info("b", "native").size == len(body)
    assert _file_reads(reads) == {"native": 12, "python": 0}
    fault.arm(NO_DRIVE)
    before, reads = _route_counters(), _file_reads()
    ol.put_object("b", "python", io.BytesIO(body), len(body))
    assert _route_delta(before) == {
        ("staged_files", "native"): 0, ("commits", "native"): 0,
        ("staged_files", "python"): 6, ("commits", "python"): 6}
    assert ol.get_object_info("b", "python").size == len(body)
    assert _file_reads(reads) == {"native": 0, "python": 12}
    # a multipart part goes by the same rule (ISSUE 43)
    uid = ol.new_multipart_upload("b", "mp")
    parts, before = [], _part_commits()
    for num, arm in ((1, False), (2, True)):
        fault.clear()
        if arm:
            fault.arm(NO_DRIVE)
        parts.append(ol.put_object_part("b", "mp", uid, num,
                                        io.BytesIO(body * 2), 2 * len(body)))
        assert _part_commits(before) == {"native": 6, "python": 6 * arm}
    for d in ol.disks:
        assert sorted(os.listdir(os.path.join(
            d.base, META_MULTIPART, upload_path("b", "mp", uid)))) == [
            "part.1", "part.1.meta", "part.2", "part.2.meta", XL_META_FILE]
        assert os.listdir(os.path.join(d.base, META_TMP)) == []
    ol.complete_multipart_upload("b", "mp", uid, parts)
    assert ol.get_object_bytes("b", "mp") == body * 4
    fault.clear()
    trees = [_tree(os.path.join(d.base, "b")) for d in ol.disks]
    for t in trees:
        # the same shape under both keys: xl.meta and one data directory
        # holding part.1
        shape = {k: sorted(p.split("/")[-1] for p in t
                           if p.startswith(k + "/") and not p.endswith("/"))
                 for k in ("native", "python")}
        assert shape["native"] == shape["python"] == ["part.1", "xl.meta"]
    assert ol.get_object_bytes("b", "python") == body
    assert ol.get_object_bytes("b", "native") == body


def test_armed_fault_takes_the_python_route_for_an_inline_version(tmp_path):
    """A 64 KiB PUT commits natively; with a disk fault armed (any: the
    rule matches no drive) the Python sequence with its crash points runs,
    the route counter says so, and both leave xl.meta alone in the
    object's directory, holding that drive's shard."""
    ol = _layer(str(tmp_path), 6, 2)
    ol.make_bucket("b")
    body = _body(64 << 10)
    before = _route_counters()
    ol.put_object("b", "native", io.BytesIO(body), len(body))
    assert _route_delta(before) == {
        ("staged_files", "native"): 0, ("commits", "native"): 6,
        ("staged_files", "python"): 0, ("commits", "python"): 0}
    fault.arm(NO_DRIVE)
    before = _route_counters()
    ol.put_object("b", "python", io.BytesIO(body), len(body))
    assert _route_delta(before) == {
        ("staged_files", "native"): 0, ("commits", "native"): 0,
        ("staged_files", "python"): 0, ("commits", "python"): 6}
    assert ol.get_object_bytes("b", "python") == body
    fault.clear()
    for d in ol.disks:
        t = _tree(os.path.join(d.base, "b"))
        assert sorted(p for p in t if not p.endswith("/")) == [
            "native/xl.meta", "python/xl.meta"]
        shards = [next(iter(XLMeta.load(t[f"{k}/xl.meta"]).data.values()))
                  for k in ("native", "python")]
        # one body, one place in the distribution? no: the keys differ,
        # so the shard a drive holds differs; the framed LENGTH is one
        assert len(shards[0]) == len(shards[1]) > (64 << 10) // 4
        assert os.listdir(os.path.join(d.base, META_TMP)) == []
    assert ol.get_object_bytes("b", "python") == body
    assert ol.get_object_bytes("b", "native") == body
