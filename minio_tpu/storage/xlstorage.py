"""XLStorage — the local posix disk backend (reference xlStorage,
cmd/xl-storage.go:91): one directory per disk, one sub-directory per volume
(bucket), per object a directory holding ``xl.meta`` plus
``<dataDir-uuid>/part.N`` shard files (layout doc
cmd/xl-storage-format-v2.go:72-80, SURVEY.md A.2).

Write discipline mirrors the reference: shard data streams into
``.minio.sys/tmp/<uuid>/...`` and is committed by an atomic rename
(rename_data); xl.meta updates write-to-tmp + ``durable_replace`` (the
fsync-policy commit primitive, storage/durability.py — docs/durability.md
has the crash-consistency story, WRITE_STEPS below the crash-point
catalogue). A small object's
shard rides in xl.meta itself (``Data``, A.4: no data directory, nothing
staged; storage/xlmeta.py). O_DIRECT is intentionally not used —
Python buffered I/O + the OS page cache stand in for the reference's
hand-rolled aligned reads; the TPU hot path cares about device dispatch, not
host file I/O syscalls.
"""
from __future__ import annotations

import errno
import os
import shutil
import stat
import threading
import time
from typing import Iterator

from .. import fault as _fault
from .. import native as _native
from ..obs import latency as _lat
from ..obs import metrics as _mx
from ..obs import spans as _spans
from ..obs import trace as _trc
from ..utils import errors
from ..utils import ids as _ids
from .datatypes import DiskInfo, FileInfo, VolInfo
from .durability import (FSYNC_ALWAYS, FSYNC_BATCHED, durable_replace,
                         durable_replace_dir, flusher, fsync_after_write,
                         fsync_mode, fsync_path)
from .interface import StorageAPI
from .xlmeta import XL_META_CORRUPT_FILE, XL_META_FILE, XLMeta

#: Reserved system volume (reference minioMetaBucket ".minio.sys").
META_BUCKET = ".minio.sys"
META_TMP = f"{META_BUCKET}/tmp"
META_MULTIPART = f"{META_BUCKET}/multipart"
META_BUCKETS = f"{META_BUCKET}/buckets"
FORMAT_FILE = "format.json"

#: Registered crash points (docs/durability.md): each is a named step in
#: the commit choreography where a ``crash`` or ``torn`` fault rule
#: (``disk:<target>:<step>:crash``) can fire, and the crash matrix
#: (tests/test_crash.py) proves all-or-nothing recovery for every one.
WRITE_STEPS = (
    "pre_replace",        # tmp written, about to become visible
    "post_replace",       # rename landed, fsync policy applied
    "pre_data_rename",    # rename_data: before the dataDir moves
    "post_data_rename",   # dataDir visible, xl.meta not yet updated
    "pre_meta_write",     # version journal about to be rewritten
    "post_meta_write",    # journal committed, tmp/purge cleanup pending
    "pre_rename_file",    # rename_file / commit_part's Python sequence
                          # (multipart part promote)
    "pre_append",         # append_file about to mutate in place
)


def _check_path(p: str):
    if p.startswith("/") or ".." in p.split("/"):
        raise errors.FileAccessDenied(p)
    if any(len(seg) > 255 for seg in p.split("/")):
        raise errors.FileNameTooLong(p)


def new_tmp_id() -> str:
    """pid-prefixed staging id for everything under ``.minio.sys/tmp``:
    sweep_tmp skips entries minted by a DIFFERENT still-alive process
    (shared-disk peer layers must not eat each other's in-flight
    staging), while a restart — a new pid — reclaims everything the
    dead process left behind."""
    return f"{os.getpid()}-{_ids.uuid4_str()}"


def _minted_by_live_peer(name: str) -> bool:
    """True when a tmp entry carries another LIVE process's pid prefix.
    Legacy/unprefixed names (plain uuids) parse as absent or absurd pids
    and sweep exactly as before."""
    pid_s = name.split("-", 1)[0]
    if not pid_s.isdigit():
        return False
    pid = int(pid_s)
    if pid == os.getpid():
        return False
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, OverflowError):
        return False
    except OSError:
        return True  # EPERM etc.: exists under another uid — alive


def _native_fs() -> bool:
    """The rule that picks the route of a request's file-system sequences
    (stage a shard file, commit a version or a multipart part, open a
    shard file, read an ``xl.meta`` whole): one native call each when the
    native library is loaded and no disk fault is armed, else the Python
    sequence, a system call a turn at the interpreter lock. Nothing
    steers it: the Python sequence is the only one without a compiler,
    the one the crash points (``_write_step``) live in, and the
    reference the native one is tested against."""
    return not _fault.armed("disk") and _native.available()


class _FileWriter:
    """Streaming file writer with abort support: the Python sequence
    (``_StagedFile`` is its native twin)."""

    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        self._path = path
        self._f = open(path, "wb")

    def write(self, b: bytes):
        self._f.write(b)

    def fileno(self) -> int:
        """Expose the fd for the fused native write path (pwrite from
        C++); callers must not mix fd writes with buffered write()s."""
        return self._f.fileno()

    def close(self):
        self._f.close()
        # shard bytes land under the fsync policy too: a commit
        # (rename_data) of dirents whose file CONTENT never hit media is
        # exactly the torn-shard case the durability plane exists for.
        # ``always`` fsyncs here, pre-rename (strongest ordering);
        # ``batched`` must NOT enqueue this soon-to-be-renamed tmp path
        # — rename_data enqueues the files at their committed location
        # instead (durable_replace_dir's tree marker)
        if fsync_mode() == FSYNC_ALWAYS:
            # strict: a failed shard writeback fails THIS disk's write;
            # quorum routes around it instead of committing air
            fsync_path(self._path, kind="file", strict=True)

    def abort(self):
        self._f.close()
        try:
            os.unlink(self._path)
        except OSError:
            pass


class _StagedFile:
    """A shard's staging file whose directories were made and which was
    opened in ONE native call (``native.stage_file``), where
    ``_FileWriter`` takes five turns at the interpreter lock: holds the
    raw fd ``mt_put_block_fds`` writes to. ``write``, ``close`` (fsynced
    first under ``always``) and ``abort`` mean what ``_FileWriter``'s do;
    ``close_many`` closes the files of all a PUT's drives in one call
    (erasure/streaming.py ``close_writers``)."""

    __slots__ = ("_path", "_fd")

    def __init__(self, path: str, fd: int):
        self._path = path
        self._fd = fd

    def write(self, b: bytes):
        mv = memoryview(b).cast("B")
        while mv.nbytes:
            mv = mv[os.write(self._fd, mv):]

    def fileno(self) -> int:
        return self._fd

    def close(self):
        err = self.close_many([self])[0]
        if err is not None:
            raise err

    @staticmethod
    def close_many(files: list["_StagedFile"]) -> list[OSError | None]:
        """Close every file (one native call); per file None, or the
        error of its fsync under ``always``: strict as in
        ``_FileWriter.close``, and that file's drive's alone."""
        always = fsync_mode() == FSYNC_ALWAYS
        fds = [f._fd for f in files]  # below 0: closed before
        out: list[OSError | None] = []
        for f, fd, e in zip(files, fds, _native.close_fds(fds, always)):
            f._fd = -1
            if e:
                _mx.inc("minio_tpu_durability_fsync_failed_total",
                        kind="file")
                out.append(OSError(e, os.strerror(e), f._path))
                continue
            if always and fd >= 0:
                _mx.inc("minio_tpu_durability_fsync_total", kind="file")
            out.append(None)
        return out

    def abort(self):
        self._drop()
        try:
            os.unlink(self._path)
        except OSError:
            pass

    def _drop(self):
        if self._fd >= 0:
            os.close(self._fd)
            self._fd = -1

    __del__ = _drop  # a raw fd has no GC finalizer


class _FileReadAt:
    """Positional reads over one shard file (reference odirectReader /
    ReadFileStream, cmd/xl-storage.go:1381). Raw os.open, not io.open:
    only pread ever touches the file, and a 16+4 GET constructs 16-20 of
    these per request — the BufferedReader setup was measurable GIL time
    under concurrent reads."""

    def __init__(self, path: str, endpoint: str = ""):
        self._fd = -1  # __del__ runs even when the open below raises
        self._endpoint = endpoint
        try:
            if _native_fs():
                # open + fstat in one turn at the interpreter lock
                fd = _native.open_shard(path)
                if fd < 0:
                    raise OSError(-fd, os.strerror(-fd), path)
                self._fd = fd
                return
            self._fd = os.open(path, os.O_RDONLY)
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except IsADirectoryError:
            raise errors.IsNotRegular(path) from None
        # os.open(dir) succeeds on Linux where io.open raised — keep the
        # IsNotRegular contract
        if stat.S_ISDIR(os.fstat(self._fd).st_mode):
            os.close(self._fd)
            self._fd = -1
            raise errors.IsNotRegular(path)

    def read_at(self, offset: int, length: int) -> bytes:
        out = os.pread(self._fd, length, offset)
        if _fault.armed("disk"):
            # per-shard-read injection (chaos harness): delay/hang make
            # this source a straggler (hedged reads route around it),
            # error raises a typed vote, bitrot corrupts the returned
            # span (the bitrot reader upstairs detects the mismatch)
            if _fault.inject("disk", self._endpoint,
                             "read_at") is _fault.BITROT:
                out = _fault.corrupt(out)
        return out

    def fileno(self) -> int:
        """Expose the fd for the fused native read path (pread from
        C++, native/pipeline.cpp mt_get_block_pread)."""
        return self._fd

    def close(self):
        if self._fd >= 0:
            os.close(self._fd)
            self._fd = -1

    def detach_fd(self) -> int:
        """Hand the fd over (this reader is closed from here on): whoever
        closes the shard files of one read closes them together
        (erasure/streaming.py ``close_readers``). Below 0: closed before."""
        fd, self._fd = self._fd, -1
        return fd

    def __del__(self):  # belt-and-braces: raw fds have no GC finalizer
        self.close()


class _OpSpan:
    """One traced storage call (reference storageTrace wrapping every
    xlStorage op with trace type madmin.TraceStorage): measures the op,
    feeds the per-disk last-minute latency window, and — only while a
    trace subscriber is listening — publishes a storage-type TraceInfo
    with path, bytes and duration."""

    __slots__ = ("disk", "op", "path", "in_bytes", "out_bytes", "t0")

    def __init__(self, disk: str, op: str, path: str, in_bytes: int = 0):
        self.disk = disk
        self.op = op
        self.path = path
        self.in_bytes = in_bytes
        self.out_bytes = 0

    def __enter__(self) -> "_OpSpan":
        self.t0 = time.perf_counter()
        if _fault.armed("disk"):
            # per-op injection point (chaos harness): a raised typed
            # error propagates to the caller exactly like a real disk
            # failure; a delay lands inside the measured span so the
            # latency windows and health EWMA see it
            _fault.inject("disk", self.disk, self.op)
        return self

    def __exit__(self, etype, exc, tb) -> bool:
        dur = time.perf_counter() - self.t0
        try:
            ctx = _spans.current()
            tid = ctx.trace_id if ctx is not None and ctx.sampled else ""
            _lat.observe("disk", dur, self.in_bytes + self.out_bytes,
                         disk=self.disk, op=self.op, trace_id=tid)
            _trc.publish_storage(
                node=self.disk, op=self.op, path=self.path,
                duration_s=dur, input_bytes=self.in_bytes,
                output_bytes=self.out_bytes,
                error=f"{etype.__name__}: {exc}" if etype else "")
            if tid:
                # leaf span into the request's tree (the inner _inner
                # helpers stay untraced: one logical storage call = one
                # span, same rule the window observation follows)
                _spans.record({
                    "name": f"storage.{self.op}", "trace_id": tid,
                    "span_id": _spans.new_span_id(),
                    "parent_span_id": ctx.span_id,
                    "time": time.time() - dur,
                    "duration_s": round(dur, 6),
                    "error": f"{etype.__name__}: {exc}" if etype else "",
                    "attrs": {"disk": self.disk, "path": self.path,
                              "bytes": self.in_bytes + self.out_bytes}})
        except Exception:  # noqa: BLE001 — obs must never break storage
            pass
        return False


class XLStorage(StorageAPI):
    def __init__(self, base_dir: str, endpoint: str = ""):
        self.base = os.path.abspath(base_dir)
        self._endpoint = endpoint or self.base
        self._disk_id = ""
        # RLock: _quarantine_meta re-verifies under the lock and is
        # reached from _load_meta calls that may already hold it.
        # The GL021 pragmas on this lock are deliberate: the per-disk
        # metadata read-modify-write (load xl.meta -> mutate -> durable
        # store, plus the dataDir commit rename) IS the critical
        # section — the bounded single-file IO must stay inside it for
        # commit atomicity w.r.t. this disk. Only O(subtree) walks are
        # hoisted out (see reconcile_object's phase structure).
        self._meta_lock = threading.RLock()
        os.makedirs(self.base, exist_ok=True)
        os.makedirs(self._abs(META_TMP), exist_ok=True)
        os.makedirs(self._abs(META_MULTIPART), exist_ok=True)
        os.makedirs(self._abs(META_BUCKETS), exist_ok=True)

    # --- helpers ------------------------------------------------------------

    def _abs(self, *parts: str) -> str:
        for p in parts:
            _check_path(p)
        return os.path.join(self.base, *parts)

    def endpoint(self) -> str:
        return self._endpoint

    def _op(self, op: str, volume: str, path: str = "",
            in_bytes: int = 0) -> _OpSpan:
        return _OpSpan(self._endpoint, op,
                       f"{volume}/{path}" if path else volume, in_bytes)

    def _write_step(self, step: str, tmp: str | None = None) -> None:
        """Named crash point in the commit choreography (WRITE_STEPS):
        a ``crash`` rule raises SimulatedCrash here (no cleanup runs —
        in-process kill -9), a ``torn`` rule truncates the pending tmp
        file at a random offset before it becomes visible. One armed-
        flag check when no chaos is running."""
        if not _fault.armed("disk"):
            return
        res = _fault.inject("disk", self._endpoint, step)
        if isinstance(res, _fault._Torn):
            if tmp:
                _fault.torn_truncate(tmp, res.rng)
            else:
                # the rule fired (and spent its hit budget) but this
                # step owns no pending tmp — a silently green chaos
                # test is worse than a loud misconfiguration
                from ..obs.logger import log_sys
                log_sys().log_once(
                    f"torn-no-tmp:{step}", "warning", "fault",
                    f"torn rule fired at step {step!r} which owns no "
                    f"pending tmp file — nothing was torn")

    def get_disk_id(self) -> str:
        return self._disk_id

    def set_disk_id(self, disk_id: str) -> None:
        self._disk_id = disk_id

    def disk_info(self) -> DiskInfo:
        with self._op("disk_info", ""):
            return self._disk_info_inner()

    def _disk_info_inner(self) -> DiskInfo:
        st = os.statvfs(self.base)
        total = st.f_blocks * st.f_frsize
        free = st.f_bavail * st.f_frsize
        return DiskInfo(total=total, free=free, used=total - free,
                        fs_type="posix", endpoint=self._endpoint,
                        mount_path=self.base, id=self._disk_id)

    # --- volumes ------------------------------------------------------------

    def make_vol(self, volume: str) -> None:
        with self._op("make_vol", volume):
            p = self._abs(volume)
            if os.path.isdir(p):
                raise errors.VolumeExists(volume)
            os.makedirs(p, exist_ok=True)

    def list_vols(self) -> list[VolInfo]:
        with self._op("list_vols", ""):
            out = []
            for name in sorted(os.listdir(self.base)):
                if name == META_BUCKET:
                    continue
                p = os.path.join(self.base, name)
                if os.path.isdir(p):
                    out.append(VolInfo(name=name,
                                       created=os.stat(p).st_ctime))
            return out

    def stat_vol(self, volume: str) -> VolInfo:
        with self._op("stat_vol", volume):
            # one stat, not isdir + stat: every request pays this turn
            try:
                st = os.stat(self._abs(volume))
            except OSError:
                raise errors.VolumeNotFound(volume) from None
            if not stat.S_ISDIR(st.st_mode):
                raise errors.VolumeNotFound(volume)
            return VolInfo(name=volume, created=st.st_ctime)

    def delete_vol(self, volume: str, force: bool = False) -> None:
        with self._op("delete_vol", volume):
            p = self._abs(volume)
            if not os.path.isdir(p):
                raise errors.VolumeNotFound(volume)
            if force:
                shutil.rmtree(p)
                return
            try:
                os.rmdir(p)
            except OSError:
                raise errors.VolumeNotEmpty(volume) from None

    # --- raw files ----------------------------------------------------------

    def list_dir(self, volume: str, dir_path: str, count: int = -1
                 ) -> list[str]:
        with self._op("list", volume, dir_path):
            return self._list_dir_inner(volume, dir_path, count)

    def _list_dir_inner(self, volume: str, dir_path: str, count: int = -1
                        ) -> list[str]:
        base = self._abs(volume, dir_path) if dir_path else self._abs(volume)
        if not os.path.isdir(self._abs(volume)):
            raise errors.VolumeNotFound(volume)
        try:
            names = sorted(os.listdir(base))
        except FileNotFoundError:
            raise errors.FileNotFound(dir_path) from None
        except NotADirectoryError:
            raise errors.IsNotRegular(dir_path) from None
        out = []
        for n in names:
            if os.path.isdir(os.path.join(base, n)):
                n += "/"
            out.append(n)
            if 0 < count <= len(out):
                break
        return out

    def read_all(self, volume: str, path: str) -> bytes:
        with self._op("read_all", volume, path) as sp:
            out = self._read_all_inner(volume, path)
            sp.out_bytes = len(out)
            return out

    def _read_all_inner(self, volume: str, path: str,
                        probe_volume: bool = True) -> bytes:
        """Untraced read_all for composite ops (xl.meta loads) — keeps
        one logical storage call = one span/window observation. One
        native call (``native.read_file``: open, fstat, read to the end,
        close, the interpreter lock let go once) when ``_native_fs()``
        says so, else the same four from Python, raw os.open/os.read and
        not io.open: the reference sequence, and the one a run with a
        disk fault armed takes. From Python one xl.meta read is four
        turns at the interpreter lock, a quorum pass 49 of them on a
        12-drive set and 25 on a 6-drive one, and on the chip's host a
        turn costs ~3 ms (12 drives) or ~2.5 ms (6) beside 20 clients
        (PERF.md section 5): a STAT that moved no byte took 134-156 ms at
        12 drives, 62 ms at 6 (ledger, PR 39); natively the pass is 13
        and 7 (PERF.md section 6, PR 40). A file over 64 KiB costs a
        second call (``native.READ_FILE_ONE_CALL``). Both routes raise
        the same errors. A missing file is told from a missing volume by
        a probe AFTER the failure; ``probe_volume=False`` leaves even
        that out for a caller whose next step fails on a missing volume
        anyway (rename_data)."""
        full = self._abs(volume, path)
        native_route = _native_fs()
        _mx.inc("minio_tpu_storage_file_reads_total",
                route="native" if native_route else "python")
        try:
            if native_route:
                out = _native.read_file(full)
                if isinstance(out, int):
                    raise OSError(-out, os.strerror(-out), full)
                return out
            fd = os.open(full, os.O_RDONLY)
            try:
                size = os.fstat(fd).st_size
                chunks = []
                got = 0
                while got < size:
                    b = os.read(fd, size - got)
                    if not b:
                        break
                    chunks.append(b)
                    got += len(b)
                return chunks[0] if len(chunks) == 1 else b"".join(chunks)
            finally:
                os.close(fd)
        except FileNotFoundError:
            if probe_volume and not os.path.isdir(self._abs(volume)):
                raise errors.VolumeNotFound(volume) from None
            raise errors.FileNotFound(path) from None
        except IsADirectoryError:
            raise errors.IsNotRegular(path) from None

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        """Atomic whole-file write (tmp + rename)."""
        with self._op("write_all", volume, path, in_bytes=len(data)):
            self._write_all_inner(volume, path, data)

    def _make_parent(self, volume: str, dst: str) -> None:
        """mkdir -p of ``dst``'s directory, below the volume and never
        the volume itself. One mkdir when the directory is there or only
        it is missing; the VolumeNotFound probe comes after a failure
        (before PR 36 every write paid an ``isdir`` of the volume and
        ``makedirs``' own ``stat`` first: three turns, now one)."""
        parent = os.path.dirname(dst)
        vol_root = self._abs(volume)
        if parent != vol_root:
            try:
                os.mkdir(parent)
                return
            except FileExistsError:
                return
            except (FileNotFoundError, NotADirectoryError):
                pass  # a volume that is no directory is "not found" too
        if not os.path.isdir(vol_root):
            raise errors.VolumeNotFound(volume)
        os.makedirs(parent, exist_ok=True)

    def _write_all_inner(self, volume: str, path: str, data: bytes) -> None:
        dst = self._abs(volume, path)
        self._make_parent(volume, dst)
        tmp = self._abs(META_TMP, new_tmp_id())
        with open(tmp, "wb") as f:
            f.write(data)
        self._write_step("pre_replace", tmp=tmp)
        durable_replace(tmp, dst)
        self._write_step("post_replace")

    def append_file(self, volume: str, path: str, data: bytes) -> None:
        with self._op("append_file", volume, path, in_bytes=len(data)):
            dst = self._abs(volume, path)
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            self._write_step("pre_append")
            with open(dst, "ab") as f:
                f.write(data)
            fsync_after_write(dst)

    def create_file_writer(self, volume: str, path: str):
        if _fault.armed("disk"):
            _fault.inject("disk", self._endpoint, "create_file_writer")
        full = self._abs(volume, path)
        if _native_fs():
            fd = _native.stage_file(self._abs(volume), path)
            if fd >= 0:
                _mx.inc("minio_tpu_storage_staged_files_total",
                        route="native")
                return _StagedFile(full, fd)
            if fd != -errno.ENOENT:
                raise OSError(-fd, os.strerror(-fd), full)
            # the volume is not there: makedirs below makes it, as ever
        w = _FileWriter(full)
        _mx.inc("minio_tpu_storage_staged_files_total", route="python")
        return w

    def read_file_at(self, volume: str, path: str):
        if _fault.armed("disk"):
            _fault.inject("disk", self._endpoint, "read_file_at")
        return _FileReadAt(self._abs(volume, path), self._endpoint)

    def rename_file(self, src_volume: str, src_path: str, dst_volume: str,
                    dst_path: str) -> None:
        with self._op("rename_file", src_volume, src_path):
            src = self._abs(src_volume, src_path)
            dst = self._abs(dst_volume, dst_path)
            if not os.path.exists(src):
                raise errors.FileNotFound(src_path)
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            self._write_step("pre_rename_file", tmp=src)
            durable_replace(src, dst)

    def commit_part(self, src_volume: str, src_path: str, dst_volume: str,
                    dst_path: str, meta: bytes) -> None:
        """Commit a multipart part on this drive: the staged shard file
        ``<src>`` becomes ``<dst>`` and ``meta`` its sidecar
        ``<dst>.meta`` (a tmp name under ``.minio.sys/tmp``, then
        renamed: never seen torn, and never before the shard it names),
        and the staging directory the shard leaves empty is removed.

        ONE native call (``native.commit_part``) when ``_native_fs()``
        says so; every run with a disk fault armed takes the Python
        sequence below (``rename_file`` + ``write_all``'s, crash points
        ``pre_rename_file``, ``pre_replace`` / ``post_replace``). Both
        leave the same tree and issue the same fsyncs in the same order
        (docs/durability.md, tests/test_put_turns.py). Why: from Python
        the sequence is 8 file-system calls a drive, each a turn at the
        interpreter lock of ~4-6 ms beside 8 clients on the chip's host,
        and ``put_object_part`` made them one drive after the other on
        the request's thread: 59 % of a 16 MiB part PUT's wall (PERF.md
        section 6, PR 43). Now a drive's commit is one call, all drives
        at once."""
        native_route = _native_fs()
        _mx.inc("minio_tpu_storage_part_commits_total",
                route="native" if native_route else "python")
        with self._op("commit_part", dst_volume, dst_path,
                      in_bytes=len(meta)):
            src = self._abs(src_volume, src_path)
            dst = self._abs(dst_volume, dst_path)
            staging = self._abs(src_volume, src_path.split("/")[0])
            if native_route:
                # native/pipeline.cpp mt_commit_part; the policy, the
                # counters, ``batched``'s markers (durable_replace's two,
                # for as far as the sequence came) and the typed errors
                # stay here, as in ``_commit_native``
                mode = fsync_mode()
                result = _native.commit_part(
                    self._abs(dst_volume), dst_path, src,
                    self._abs(META_TMP, new_tmp_id()), staging, meta,
                    mode == FSYNC_ALWAYS)
                step = self._count_syncs(result)
                if mode == FSYNC_BATCHED:
                    if step == 0 or step > _native.COMMIT_DATA_RENAME:
                        flusher().enqueue(dst)
                    if step == 0:
                        flusher().enqueue(dst + ".meta")
                self._commit_outcome(result, dst_volume, src_path, dst)
                return
            if not os.path.exists(src):
                raise errors.FileNotFound(src_path)
            self._make_parent(dst_volume, dst)
            self._write_step("pre_rename_file", tmp=src)
            durable_replace(src, dst)
            self._write_all_inner(dst_volume, dst_path + ".meta", meta)
            try:
                os.rmdir(staging)
            except FileNotFoundError:
                pass
            except OSError:
                _mx.inc("minio_tpu_durability_purge_failed_total",
                        kind="tmp")

    def delete_path(self, volume: str, path: str, recursive: bool = False
                    ) -> None:
        with self._op("delete", volume, path):
            self._delete_path_inner(volume, path, recursive)

    def _delete_path_inner(self, volume: str, path: str,
                           recursive: bool = False) -> None:
        p = self._abs(volume, path)
        try:
            if os.path.isdir(p):
                if recursive:
                    shutil.rmtree(p)
                else:
                    os.rmdir(p)
            else:
                os.unlink(p)
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        # prune now-empty parents up to the volume root (reference
        # deleteFile parent cleanup)
        parent = os.path.dirname(p)
        vol_root = self._abs(volume)
        while parent != vol_root and parent.startswith(self.base):
            try:
                os.rmdir(parent)
            except OSError:
                break
            parent = os.path.dirname(parent)

    def stat_file_size(self, volume: str, path: str) -> int:
        with self._op("stat", volume, path):
            return self._stat_file_size_inner(volume, path)

    def _stat_file_size_inner(self, volume: str, path: str) -> int:
        try:
            st = os.stat(self._abs(volume, path))
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        if not os.path.isfile(self._abs(volume, path)):
            raise errors.IsNotRegular(path)
        return st.st_size

    # --- xl.meta version ops ------------------------------------------------

    def _meta_path(self, volume: str, path: str) -> str:
        return self._abs(volume, path, XL_META_FILE)

    def _load_meta(self, volume: str, path: str,
                   probe_volume: bool = True) -> XLMeta:
        # untraced inner read: the calling meta op owns the span
        try:
            blob = self._read_all_inner(volume, f"{path}/{XL_META_FILE}",
                                        probe_volume)
        except errors.FileNotFound:
            raise errors.FileNotFound(path) from None
        try:
            return XLMeta.load(blob)
        except errors.FileCorrupt:
            self._quarantine_meta(volume, path)
            raise

    def _quarantine_meta(self, volume: str, path: str) -> bool:
        """Move an unparseable/torn xl.meta aside to xl.meta.corrupt:
        forensics survive, and the slot reads FileNotFound from now on —
        which heal classifies as MISSING and rebuilds from quorum
        (leaving the torn journal in place would wedge every write path
        that loads-then-stores it).

        Re-verifies under ``_meta_lock`` before renaming: the lockless
        read paths (read_version/read_versions) reach here too, and
        between their torn read and this rename a writer or heal may
        have committed a VALID journal at the same path — quarantining
        that would re-degrade a just-healed disk."""
        src = self._meta_path(volume, path)
        dst = self._abs(volume, path, XL_META_CORRUPT_FILE)
        with self._meta_lock:
            try:
                XLMeta.load(self._read_all_inner(  # graftlint: disable=GL021
                    volume, f"{path}/{XL_META_FILE}"))
                return False  # valid now — a concurrent commit won
            except errors.FileCorrupt:
                pass
            except (errors.StorageError, OSError):
                return False  # gone/unreadable: nothing to move aside
            try:
                durable_replace(src, dst)  # graftlint: disable=GL021
            except OSError:
                return False
        _mx.inc("minio_tpu_durability_quarantined_meta_total")
        return True

    def _store_meta(self, volume: str, path: str, meta: XLMeta) -> None:
        if not meta.versions:
            # last version removed: delete the whole object dir
            self._delete_path_inner(volume, path, recursive=True)
            return
        self._write_all_inner(volume, f"{path}/{XL_META_FILE}",
                              meta.dump())

    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str) -> None:
        """Commit a freshly written object version: move
        ``<src>/<dataDir>`` under the object dir and add the version to
        xl.meta atomically w.r.t. this disk (reference RenameData).

        Shard files (``fi.data`` is None) and an inline version
        (``fi.data`` is THIS drive's framed shard, kept in xl.meta's
        ``Data``: no data directory, nothing staged under ``src``) commit
        through ONE native call each when ``_native_fs()`` says so
        (``_commit_native``, ``_commit_inline_native``); every run with a
        disk fault armed takes the Python sequence below, where the crash
        points are. Both leave the same tree and
        issue the same fsyncs in the same order (docs/durability.md,
        tests/test_put_turns.py). Why: counted from Python the sequence
        is 21 file-system calls a drive, each a turn at the interpreter
        lock of ~3 ms beside 20 clients on the chip's host (12 drives;
        ~2 ms at 6); with the staging before it a 10 MiB PUT made 314
        such calls at 12 drives and 158 at 6 (PERF.md section 6, PR 36).
        Now a drive's commit is 2 (the ``xl.meta`` read, the native
        call), a PUT 38 and 20, and an inline PUT, which stages nothing,
        25 and 13."""
        inline = fi.data is not None
        native_route = bool(fi.data_dir) and _native_fs()
        _mx.inc("minio_tpu_storage_commits_total",
                route="native" if native_route else "python")
        with self._op("rename_data", dst_volume, dst_path), \
                self._meta_lock:
            if native_route and inline:
                self._commit_inline_native(fi, dst_volume, dst_path)  # graftlint: disable=GL021
                return
            if native_route:
                self._commit_native(src_volume, src_path, fi,  # graftlint: disable=GL021
                                    dst_volume, dst_path)
                return
            try:
                meta = self._load_meta(dst_volume, dst_path)  # graftlint: disable=GL021
            except errors.FileNotFound:
                meta = XLMeta()
            if fi.data_dir and not inline:
                src = self._abs(src_volume, src_path, fi.data_dir)
                if not os.path.isdir(src):
                    raise errors.FileNotFound(src_path)
                dst = self._abs(dst_volume, dst_path, fi.data_dir)
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                if os.path.isdir(dst):
                    shutil.rmtree(dst)
                # tmp=src: a torn rule here tears a shard inside the
                # staged dataDir before it becomes visible
                self._write_step("pre_data_rename", tmp=src)
                # dir commit: batched mode enqueues ONE tree marker
                # covering the shard files' CONTENT at the committed
                # location (their tmp paths are gone after the rename),
                # dst itself, and the parent dirent
                durable_replace_dir(src, dst)  # graftlint: disable=GL021
                self._write_step("post_data_rename")
            self._write_step("pre_meta_write")
            old_ddirs = meta.add_version(fi)
            self._store_meta(dst_volume, dst_path, meta)  # graftlint: disable=GL021
            self._write_step("post_meta_write")
            self._purge_ddirs(dst_volume, dst_path, old_ddirs)
        if inline:
            return  # nothing was staged
        # clean the tmp parent dir; a failure here leaks tmp space until
        # the janitor reclaims it — make that visible, not silent
        # (already-gone is success: a prior call or the janitor won)
        try:
            shutil.rmtree(self._abs(src_volume, src_path.split("/")[0]))
        except FileNotFoundError:
            pass
        except OSError:
            _mx.inc("minio_tpu_durability_purge_failed_total", kind="tmp")

    def _commit_native(self, src_volume: str, src_path: str, fi: FileInfo,
                       dst_volume: str, dst_path: str) -> None:
        """rename_data's file-system sequence as one native call
        (native/pipeline.cpp mt_commit_version: object directory, data
        rename, xl.meta under its tmp name and renamed over, replaced
        data directories and the tmp parent removed; under ``always``
        the Python sequence's fsyncs at the Python sequence's places).
        The journal logic, the policy, the errors and the counters stay
        here. The volume is probed only after a failure: a missing one
        fails the native call's first mkdir."""
        try:
            meta = self._load_meta(dst_volume, dst_path, probe_volume=False)
        except errors.FileNotFound:
            meta = XLMeta()
        old_ddirs = meta.add_version(fi)
        dst = self._abs(dst_volume, dst_path, fi.data_dir)
        mode = fsync_mode()
        self._commit_result(
            _native.commit_version(
                self._abs(dst_volume), dst_path, fi.data_dir,
                self._abs(src_volume, src_path, fi.data_dir),
                self._abs(src_volume, src_path.split("/")[0]),
                meta.dump(), old_ddirs, mode == FSYNC_ALWAYS),
            mode, dst_volume, dst_path, dst, src_path)

    def _commit_inline_native(self, fi: FileInfo, dst_volume: str,
                              dst_path: str) -> None:
        """rename_data's sequence for an inline version as one native
        call (native/pipeline.cpp mt_commit_inline): object directory,
        the new xl.meta (the shard in its ``Data``) under a tmp name and
        renamed over, replaced data directories removed; the fsyncs and
        markers are durable_replace's. Journal logic, policy, errors and
        counters stay here, as in ``_commit_native``."""
        try:
            meta = self._load_meta(dst_volume, dst_path, probe_volume=False)
        except errors.FileNotFound:
            meta = XLMeta()
        old_ddirs = meta.add_version(fi)
        mode = fsync_mode()
        self._commit_result(
            _native.commit_inline(
                self._abs(dst_volume), dst_path,
                self._abs(META_TMP, new_tmp_id()), meta.dump(), old_ddirs,
                mode == FSYNC_ALWAYS),
            mode, dst_volume, dst_path, None, dst_path)

    def _commit_result(self, result: list, mode: str, dst_volume: str,
                       dst_path: str, ddir_dst: str | None,
                       src_path: str) -> None:
        """What a native commit reports, turned into the counters, the
        flusher's markers (for as far as the sequence came) and the typed
        errors of the Python sequence. ``ddir_dst``: the committed data
        directory, None for an inline version."""
        step = self._count_syncs(result)
        if mode == FSYNC_BATCHED:
            # the markers durable_replace_dir and durable_replace leave,
            # for as far as the sequence came
            if ddir_dst is not None and (
                    step == 0 or step > _native.COMMIT_DATA_RENAME):
                flusher().enqueue_tree(ddir_dst)
            if step == 0:
                flusher().enqueue(self._meta_path(dst_volume, dst_path))
        self._commit_outcome(
            result, dst_volume, src_path,
            ddir_dst or self._meta_path(dst_volume, dst_path))

    @staticmethod
    def _count_syncs(result: list) -> int:
        """The fsyncs a native commit made, counted; returns its step."""
        step, _, _, file_syncs, dir_syncs, _, _ = result
        if file_syncs:
            _mx.inc("minio_tpu_durability_fsync_total", file_syncs,
                    kind="file")
        if dir_syncs:
            _mx.inc("minio_tpu_durability_fsync_total", dir_syncs,
                    kind="dir")
        return step

    def _commit_outcome(self, result: list, dst_volume: str, src_path: str,
                        where: str) -> None:
        """A native commit that stands counts what it could not remove;
        one that failed raises the Python sequence's typed error for the
        step it names (the volume is probed only now)."""
        step, err, sync_kind, _, _, ddirs_left, tmp_left = result
        if step == 0:
            if ddirs_left:
                _mx.inc("minio_tpu_durability_purge_failed_total",
                        ddirs_left, kind="ddir")
            if tmp_left:
                _mx.inc("minio_tpu_durability_purge_failed_total",
                        kind="tmp")
            return
        if step == _native.COMMIT_STAGED:
            raise errors.FileNotFound(src_path)
        if step == _native.COMMIT_OBJECT_DIR \
                and err in (errno.ENOENT, errno.ENOTDIR) \
                and not os.path.isdir(self._abs(dst_volume)):
            raise errors.VolumeNotFound(dst_volume)
        if step == _native.COMMIT_FSYNC:
            _mx.inc("minio_tpu_durability_fsync_failed_total",
                    kind="dir" if sync_kind else "file")
        raise OSError(err, os.strerror(err), where)

    def _purge_ddirs(self, volume: str, path: str, ddirs: list[str]):
        """Remove data dirs of replaced versions (overwrite cleanup).
        Failures count in ``minio_tpu_durability_purge_failed_total`` so
        leaked space is visible before the janitor reclaims it."""
        for ddir in ddirs:
            try:
                shutil.rmtree(self._abs(volume, path, ddir))
            except FileNotFoundError:
                pass
            except OSError:
                _mx.inc("minio_tpu_durability_purge_failed_total",
                        kind="ddir")

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        with self._op("write_metadata", volume, path), self._meta_lock:
            try:
                meta = self._load_meta(volume, path)  # graftlint: disable=GL021
            except errors.FileNotFound:
                meta = XLMeta()
            old_ddirs = meta.add_version(fi)
            self._store_meta(volume, path, meta)  # graftlint: disable=GL021
            self._purge_ddirs(volume, path, old_ddirs)

    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        with self._op("update_metadata", volume, path), self._meta_lock:
            meta = self._load_meta(volume, path)  # graftlint: disable=GL021
            meta.find_version(fi.version_id)  # must exist
            meta.add_version(fi)
            self._store_meta(volume, path, meta)  # graftlint: disable=GL021

    def read_version(self, volume: str, path: str, version_id: str = "",
                     read_data: bool = False) -> FileInfo:
        # Inline data (fi.data) comes ONLY from xl.meta's Data section
        # written at commit time, as in the reference
        # (cmd/xl-storage.go:1138): this drive's bitrot-framed shard of a
        # version at or under SMALL_FILE_THRESHOLD, what part.1 would hold.
        # Without ``read_data`` (a STAT) it stays behind: a remote drive
        # would ship it for nothing.
        with self._op("read_version", volume, path):
            meta = self._load_meta(volume, path)
            return meta.to_fileinfo(volume, path, version_id, read_data)

    def list_versions(self, volume: str, path: str) -> list[FileInfo]:
        with self._op("list_versions", volume, path):
            return self._load_meta(volume, path).list_versions(volume,
                                                               path)

    def delete_version(self, volume: str, path: str, fi: FileInfo) -> None:
        with self._op("delete_version", volume, path), self._meta_lock:
            meta = self._load_meta(volume, path)  # graftlint: disable=GL021
            ddir = meta.delete_version(fi)
            if ddir:
                try:
                    self._delete_path_inner(volume, f"{path}/{ddir}",
                                            recursive=True)
                except errors.FileNotFound:
                    pass
            self._store_meta(volume, path, meta)  # graftlint: disable=GL021

    def check_parts(self, volume: str, path: str, fi: FileInfo) -> None:
        """Verify all parts exist with the expected shard file size
        (reference CheckParts). An inline version (``fi.data``: this
        drive's shard as its xl.meta handed it back) is held to the
        framed length of its one part."""
        from ..erasure.bitrot import (BITROT_CHUNK_KEY, BitrotAlgorithm,
                                      bitrot_shard_file_size)
        with self._op("check_parts", volume, path):
            algo = BitrotAlgorithm(fi.metadata.get(
                "x-minio-internal-bitrot", "blake2b256S"))
            chunk = int(fi.metadata.get(BITROT_CHUNK_KEY,
                                        str(fi.erasure.shard_size())))
            for part in fi.parts:
                p = f"{path}/{fi.data_dir}/part.{part.number}"
                want = bitrot_shard_file_size(
                    fi.erasure.shard_file_size(part.size), chunk, algo)
                have, _ = self._shard_size(volume, path, fi, p)
                if have != want:
                    raise errors.FileCorrupt(p)

    def _shard_size(self, volume: str, path: str, fi: FileInfo,
                    part_path: str) -> tuple[int, bytes | None]:
        """(stored bytes of this drive's shard of one part, the shard
        itself when it is inline). Inline is ``fi.data``, or, for a
        ``fi`` that was read without its data, what this drive's own
        journal holds under the version's data directory: looked for only
        after the part file was not found, so shard files pay nothing."""
        if fi.data is not None:
            return len(fi.data), fi.data
        try:
            return self._stat_file_size_inner(volume, part_path), None
        except errors.FileNotFound:
            try:
                shard = self._load_meta(volume, path).data.get(fi.data_dir)
            except errors.StorageError:
                shard = None
            if shard is None:
                raise
            return len(shard), shard

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        """Deep bitrot scan of every part on this disk (reference
        VerifyFile / bitrotVerify); of an inline version, of the shard in
        ``fi.data``: bitrot inside xl.meta is looked for like any other."""
        with self._op("verify_file", volume, path):
            self._verify_file_inner(volume, path, fi)

    def _verify_file_inner(self, volume: str, path: str,
                           fi: FileInfo) -> None:
        from ..erasure.bitrot import (BITROT_CHUNK_KEY, BitrotAlgorithm,
                                      bitrot_logical_size, new_bitrot_reader)
        algo = BitrotAlgorithm(fi.metadata.get(
            "x-minio-internal-bitrot", "blake2b256S"))
        chunk = int(fi.metadata.get(BITROT_CHUNK_KEY,
                                    str(fi.erasure.shard_size())))
        from ..erasure.streaming import BufferSource
        for part in fi.parts:
            p = f"{path}/{fi.data_dir}/part.{part.number}"
            fsize, shard = self._shard_size(volume, path, fi, p)
            logical = bitrot_logical_size(fsize, chunk, algo)
            want = fi.erasure.shard_file_size(part.size)
            if logical != want:
                raise errors.FileCorrupt(p)
            src = BufferSource(shard) if shard is not None \
                else self.read_file_at(volume, p)
            try:
                r = new_bitrot_reader(src, algo, logical, chunk)
                # verify in multi-chunk spans: read_at does one backing
                # read per call, so bigger spans keep syscall count low
                span = chunk * max(1, (4 << 20) // chunk)
                off = 0
                while off < logical:
                    n = min(span, logical - off)
                    r.read_at(off, n)
                    off += n
            finally:
                if shard is None:
                    src.close()

    # --- crash recovery -----------------------------------------------------

    def sweep_tmp(self, age_s: float = 0.0) -> int:
        """Reclaim ``.minio.sys/tmp`` entries older than ``age_s``
        (reference: formatting tmp wholesale at startup, the scanner
        reaping strays later). Crash-stranded upload staging is the only
        thing that lives here; age 0 sweeps everything minted by this or
        any DEAD process. Entries pid-prefixed by a different still-LIVE
        process are always skipped: a second ObjectLayer booting over
        shared disk dirs (the peer-layer pattern) must not eat a live
        peer's in-flight PUT staging."""
        with self._op("sweep_tmp", META_TMP):
            base = self._abs(META_TMP)
            try:
                names = os.listdir(base)
            except OSError:
                return 0
            now = time.time()
            swept = 0
            for name in names:
                p = os.path.join(base, name)
                if _minted_by_live_peer(name):
                    continue
                try:
                    if age_s > 0 and now - os.stat(p).st_mtime < age_s:
                        continue
                    if os.path.isdir(p):
                        shutil.rmtree(p)
                    else:
                        os.unlink(p)
                    swept += 1
                except OSError:
                    continue  # raced with a concurrent commit/clean
            if swept:
                from ..obs import metrics as mx
                mx.inc("minio_tpu_durability_recovered_tmp_total", swept)
            return swept

    @staticmethod
    def _subtree_has_meta(p: str) -> bool:
        """True when any descendant carries a version journal (xl.meta,
        or a quarantined one awaiting heal) — the dir is object
        namespace, never dataDir residue."""
        for _root, _dirs, files in os.walk(p):
            if XL_META_FILE in files or XL_META_CORRUPT_FILE in files:
                return True
        return False

    def reconcile_object(self, volume: str, path: str,
                         age_s: float = 0.0) -> dict:
        """Reconcile one object dir against its version journal
        (recovery janitor): quarantine a torn xl.meta (via _load_meta),
        then remove data dirs no version references — the residue of a
        crash between ``post_data_rename`` and the journal commit, or of
        a failed purge. ``age_s`` guards in-flight overwrites (their
        dataDir lands moments before the journal does)."""
        out = {"orphan_ddirs": 0, "quarantined": 0, "has_meta": False}
        with self._op("reconcile", volume, path):
            obj_dir = self._abs(volume, path)
            now = time.time()
            # phase 1 (locked, fast): load/quarantine the journal,
            # snapshot referenced ddirs, list the dir
            with self._meta_lock:
                referenced = self._reconcile_refs(volume, path, out,  # graftlint: disable=GL021
                                                  age_s, now)
            try:
                names = os.listdir(obj_dir)
            except OSError:
                return out
            # phase 2 (lock-FREE): the expensive subtree walks. Nested
            # namespaces ('a' and 'a/b' both exist: 'b' is a NAMESPACE
            # dir under 'a''s object dir, holding live objects) are only
            # SKIPPED here, so walking them without the lock is safe —
            # holding _meta_lock across O(subtree) IO would stall every
            # foreground commit on the disk for the walk's duration
            candidates = []
            for name in names:
                p = os.path.join(obj_dir, name)
                if not os.path.isdir(p) or name in referenced:
                    continue
                if self._subtree_has_meta(p):
                    continue
                try:
                    if age_s > 0 and now - os.stat(p).st_mtime < age_s:
                        continue
                except OSError:
                    continue
                candidates.append(name)
            # phase 3 (locked, per-candidate, rare): re-verify against a
            # FRESH journal + subtree (a commit may have raced phase 2 —
            # rename_data holds the same lock, so this is race-free),
            # then atomically move the orphan into META_TMP; the actual
            # rmtree runs outside the lock (a crash mid-way leaves it in
            # tmp, which the startup sweep reclaims)
            trash: list[str] = []
            for name in candidates:
                p = os.path.join(obj_dir, name)
                with self._meta_lock:
                    fresh: dict = {"orphan_ddirs": 0, "quarantined": 0,
                                   "has_meta": False}
                    refs = self._reconcile_refs(volume, path, fresh,  # graftlint: disable=GL021
                                                0.0, now)
                    if name in refs or self._subtree_has_meta(p):
                        continue
                    t = self._abs(META_TMP, new_tmp_id())
                    try:
                        os.replace(p, t)  # graftlint: disable=GL009
                    except OSError:
                        continue
                    trash.append(t)
                    out["orphan_ddirs"] += 1
            for t in trash:
                shutil.rmtree(t, ignore_errors=True)
            if out["orphan_ddirs"]:
                from ..obs import metrics as mx
                mx.inc("minio_tpu_durability_orphan_ddirs_total",
                       out["orphan_ddirs"])
            if not out["has_meta"]:
                # journal-less slot: fold the dir away so walks stop
                # yielding a phantom object — immediately when empty,
                # and after age_s when only the quarantined journal
                # remains (keeps forensics through the heal window; an
                # all-disks-corrupt object would otherwise re-walk
                # forever with no quorum to rebuild it from)
                with self._meta_lock:
                    try:
                        entries = os.listdir(obj_dir)
                        if not entries:
                            self._delete_path_inner(volume, path)
                        elif entries == [XL_META_CORRUPT_FILE] \
                                and age_s > 0:
                            cp = os.path.join(obj_dir,
                                              XL_META_CORRUPT_FILE)
                            if now - os.stat(cp).st_mtime >= age_s:
                                self._delete_path_inner(
                                    volume, path, recursive=True)
                    except (OSError, errors.StorageError):
                        pass
        return out

    def _reconcile_refs(self, volume: str, path: str, out: dict,
                        age_s: float, now: float) -> set:
        """Locked journal snapshot for reconcile_object: referenced
        ddirs, quarantine side effects, and reclamation of a stale
        ``xl.meta.corrupt`` left beside a journal heal has since
        rebuilt (forensics are kept for age_s, then they are just a
        leaked file per torn event)."""
        referenced: set = set()
        try:
            meta = self._load_meta(volume, path)
            out["has_meta"] = True
            for d in meta.versions:
                ddir = d.get("V", {}).get("ddir", "")
                if ddir:
                    referenced.add(ddir)
            cp = self._abs(volume, path, XL_META_CORRUPT_FILE)
            try:
                if age_s > 0 and now - os.stat(cp).st_mtime >= age_s:
                    os.unlink(cp)
            except OSError:
                pass
        except errors.FileCorrupt:
            out["quarantined"] = 1  # _load_meta moved it aside
        except errors.FileNotFound:
            pass
        return referenced

    def walk_unjournaled(self, volume: str) -> Iterator[str]:
        """Object dirs holding shard residue but NO xl.meta — the
        residue of a crash between the dataDir rename and the FIRST
        journal write of a brand-new object. walk_dir keys on
        XL_META_FILE and so never yields these; the recovery janitor
        unions this walk in so reconcile_object can reclaim them. A dir
        qualifies when it carries a quarantined journal or any child dir
        with ``part.N`` files; non-qualifying dirs recurse as prefixes."""
        # eager entry point (not a generator): validation + chaos hook
        # fire at CALL time, before first next()
        _fault.inject("disk", self._endpoint, "walk_unjournaled")
        base = self._abs(volume)
        if not os.path.isdir(base):
            raise errors.VolumeNotFound(volume)
        return self._walk_unjournaled_inner(base)

    @staticmethod
    def _walk_unjournaled_inner(base: str) -> Iterator[str]:

        def qualifies(d: str, names: list[str]) -> bool:
            if XL_META_CORRUPT_FILE in names:
                return True
            for n in names:
                sub = os.path.join(d, n)
                if not os.path.isdir(sub):
                    continue
                try:
                    if any(s.startswith("part.")
                           for s in os.listdir(sub)):
                        return True
                except OSError:
                    continue
            return False

        def walk(d: str, rel: str) -> Iterator[str]:
            try:
                names = sorted(os.listdir(d))
            except OSError:
                return
            if XL_META_FILE in names:
                return  # journaled: walk_dir territory
            if rel and qualifies(d, names):
                yield rel
                return
            for n in names:
                sub = os.path.join(d, n)
                if os.path.isdir(sub):
                    yield from walk(sub, f"{rel}/{n}" if rel else n)

        yield from walk(base, "")

    # --- walk ---------------------------------------------------------------

    def walk_dir(self, volume: str, dir_path: str = "",
                 recursive: bool = True) -> Iterator[str]:
        # eager entry point (not a generator): volume validation and the
        # chaos-harness hook fire at CALL time, before first next()
        _fault.inject("disk", self._endpoint, "walk_dir")
        base = self._abs(volume)
        if not os.path.isdir(base):
            raise errors.VolumeNotFound(volume)
        root = os.path.join(base, dir_path) if dir_path else base
        return self._walk_dir_inner(root, dir_path, recursive)

    def _walk_dir_inner(self, root: str, dir_path: str,
                        recursive: bool) -> Iterator[str]:

        def walk(d: str, rel: str) -> Iterator[str]:
            try:
                names = sorted(os.listdir(d))
            except (FileNotFoundError, NotADirectoryError):
                return
            if XL_META_FILE in names:
                yield rel
                return
            for n in names:
                sub = os.path.join(d, n)
                if os.path.isdir(sub):
                    child = f"{rel}/{n}" if rel else n
                    if recursive:
                        yield from walk(sub, child)
                    elif os.path.isfile(os.path.join(sub, XL_META_FILE)):
                        yield child  # an object, not a prefix
                    else:
                        yield child + "/"

        yield from walk(root, dir_path)

    def walk_versions(self, volume: str, prefix: str = "", marker: str = "",
                      limit: int = -1) -> Iterator[tuple[str, bytes]]:
        """Stream (object_name, raw xl.meta bytes) in S3 lexicographic key
        order, names strictly after ``marker`` and matching ``prefix`` —
        the per-disk sorted metadata stream the metacache merge consumes
        (reference WalkDir, cmd/metacache-walk.go).

        Marker and prefix push down into the directory descent, so a page
        read touches O(page) of the namespace, not all of it. Sort order
        treats non-leaf directories as ``name + "/"`` (the reference's
        trailing-slash convention) because a subtree's keys all carry the
        separator, which sorts differently from the bare dir name."""
        # eager entry point (not a generator): validation + chaos hook
        # fire at CALL time, before first next()
        _fault.inject("disk", self._endpoint, "walk_versions")
        base = self._abs(volume)
        if not os.path.isdir(base):
            raise errors.VolumeNotFound(volume)
        return self._walk_versions_inner(base, prefix, marker, limit)

    def _walk_versions_inner(self, base: str, prefix: str, marker: str,
                             limit: int) -> Iterator[tuple[str, bytes]]:
        high = "\U0010ffff"
        emitted = 0

        def walk(d: str, rel: str) -> Iterator[tuple[str, bytes]]:
            nonlocal emitted
            try:
                names = os.listdir(d)
            except (FileNotFoundError, NotADirectoryError):
                return
            ents = []
            for n in names:
                sub = os.path.join(d, n)
                if not os.path.isdir(sub):
                    continue
                leaf = os.path.isfile(os.path.join(sub, XL_META_FILE))
                ents.append((n if leaf else n + "/", n, leaf, sub))
            for sort_key, n, leaf, sub in sorted(ents):
                if limit >= 0 and emitted >= limit:
                    return
                child = f"{rel}/{n}" if rel else n
                cmp_key = child if leaf else child + "/"
                # sorted order: once past the prefix range, nothing later
                # can match
                if prefix and cmp_key > prefix and \
                        not cmp_key.startswith(prefix) and \
                        not prefix.startswith(cmp_key):
                    return
                if leaf:
                    if child > marker and child.startswith(prefix):
                        try:
                            with open(os.path.join(sub, XL_META_FILE),
                                      "rb") as f:
                                blob = f.read()
                        except OSError:
                            continue  # raced with delete
                        emitted += 1
                        yield child, blob
                else:
                    cslash = child + "/"
                    if prefix and not (cslash.startswith(prefix)
                                       or prefix.startswith(cslash)):
                        continue
                    # skip subtrees entirely <= marker
                    if marker and marker >= cslash + high:
                        continue
                    yield from walk(sub, child)

        yield from walk(base, "")
