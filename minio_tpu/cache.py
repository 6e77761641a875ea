"""Disk cache ObjectLayer wrapper (reference cacheObjects,
cmd/disk-cache.go:88 + cmd/disk-cache-backend.go): a read-through SSD
cache in front of any ObjectLayer, with the reference's on-disk format:

* one directory per object — ``<dir>/<sha256(bucket/object)>/`` holding
  ``cache.json`` (metadata: etag, size, user metadata, hits, ranges) and
  ``part.1`` (full object data), plus ``range-<start>-<end>`` files for
  cached partial reads (disk-cache-backend.go:47-74)
* multiple cache drives, objects distributed by key hash
* watermark GC: when usage crosses quota*high%, evict by atime/hits
  score down to quota*low% (disk-cache-backend.go:204-224)
* ``exclude`` glob patterns and ``after`` (cache only after N reads —
  cache.json carries the hit counter before any data is cached)
* backend-offline serving: when the inner layer errors (not a
  NotFound), a cached entry still serves reads — the reference's
  BackendDown path (cmd/disk-cache.go GetObjectNInfo)

GET hits validate the cached etag against the backend's metadata so
stale entries self-invalidate; writes drop the entry (read-through, not
write-back)."""
from __future__ import annotations

import fnmatch
import hashlib
import io
import json
import os
import shutil
import threading
import time

from .objectlayer import datatypes as dt
from .objectlayer.interface import ObjectLayer

CACHE_META = "cache.json"
CACHE_DATA = "part.1"
#: one cached range must not exceed this (whole objects have no cap
#: beyond the half-quota rule)
MAX_RANGE_BYTES = 64 << 20


class CacheObjects:
    """Duck-typed ObjectLayer wrapper (NOT an ObjectLayer subclass: the
    ABC's concrete no-op stubs would shadow the __getattr__ delegation)."""

    def __init__(self, inner, cache_dir, quota_bytes: int = 1 << 30,
                 watermark_low: int = 70, watermark_high: int = 80,
                 exclude: list[str] | None = None, after: int = 0):
        self.inner = inner
        self.dirs = [cache_dir] if isinstance(cache_dir, str) \
            else list(cache_dir)
        self.quota = quota_bytes                    # per cache dir
        self.low = watermark_low / 100.0
        self.high = watermark_high / 100.0
        self.exclude = list(exclude or [])
        self.after = after
        for d in self.dirs:
            os.makedirs(d, exist_ok=True)
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        #: per-entry hit counts not yet flushed into cache.json (the
        #: flush throttle must not lose increments between flushes)
        self._pending_hits: dict[str, int] = {}
        #: per-dir single-flight gate: at most one GC sweep walks a
        #: cache dir at a time, and readers never wait behind the walk
        self._gc_busy = [False] * len(self.dirs)
        # per-dir used-bytes tracked incrementally so the hot path never
        # walks the cache; one walk per dir seeds the counters
        self._used = [self._walk_usage(d) for d in self.dirs]

    # -- layout ---------------------------------------------------------------

    def _entry_dir(self, bucket: str, object: str) -> tuple[int, str]:
        h = hashlib.sha256(f"{bucket}/{object}".encode()).hexdigest()
        di = int(h[:8], 16) % len(self.dirs)
        return di, os.path.join(self.dirs[di], h)

    def _load_meta(self, edir: str) -> dict | None:
        try:
            with open(os.path.join(edir, CACHE_META),
                      encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _save_meta(self, edir: str, meta: dict) -> None:
        from .storage.durability import durable_write
        try:
            os.makedirs(edir, exist_ok=True)
            durable_write(os.path.join(edir, CACHE_META),
                          json.dumps(meta).encode("utf-8"))
        except OSError:
            pass

    def _excluded(self, bucket: str, object: str) -> bool:
        key = f"{bucket}/{object}"
        return any(fnmatch.fnmatch(key, pat) or
                   fnmatch.fnmatch(bucket, pat)
                   for pat in self.exclude)

    def _new_meta(self, bucket: str, object: str, oi) -> dict:
        return {"version": "1.0.0", "bucket": bucket, "object": object,
                "etag": oi.etag, "size": oi.size,
                "content_type": oi.content_type,
                "user_defined": dict(getattr(oi, "user_defined", {}) or {}),
                "atime": time.time(), "hits": 0, "ranges": {}}

    # -- accounting / gc ------------------------------------------------------

    def _walk_usage(self, d: str) -> int:
        total = 0
        for dirpath, _, files in os.walk(d):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(dirpath, f))
                except OSError:
                    pass
        return total

    def usage(self) -> int:
        with self._lock:
            return sum(self._used)

    def _account(self, di: int, delta: int) -> None:
        with self._lock:
            self._used[di] = max(0, self._used[di] + delta)
            trigger = self._used[di] > self.quota * self.high
        if trigger:
            self._gc(di)

    def _gc(self, di: int) -> None:
        """Evict whole entries by (atime, hits) score until the dir is
        under quota*low (disk-cache-backend.go gc + scorer).

        Single-flight: the lock only guards the busy flag, the counters,
        and a snapshot of pending hits — the disk walk, meta loads, and
        rmtrees all run outside it so hot-path `_account` callers never
        block behind seconds of IO. Concurrent triggers for the same dir
        collapse into the in-flight sweep."""
        with self._lock:
            if self._gc_busy[di]:
                return
            self._gc_busy[di] = True
            pending = dict(self._pending_hits)
        try:
            d = self.dirs[di]
            used = self._walk_usage(d)   # re-seed while we're here
            target = self.quota * self.low
            if used > target:
                entries = []
                for name in os.listdir(d):
                    edir = os.path.join(d, name)
                    if not os.path.isdir(edir):
                        continue
                    meta = self._load_meta(edir) or {}
                    size = self._walk_usage(edir)
                    # older + colder first; each hit is worth five
                    # minutes of recency, so hot objects survive a sweep
                    hits = meta.get("hits", 0) + pending.get(edir, 0)
                    score = meta.get("atime", 0.0) + 300.0 * hits
                    entries.append((score, size, edir))
                entries.sort()
                for _, size, edir in entries:
                    if used <= target:
                        break
                    shutil.rmtree(edir, ignore_errors=True)
                    used -= size
            with self._lock:
                self._used[di] = used
        finally:
            with self._lock:
                self._gc_busy[di] = False

    def _drop(self, bucket: str, object: str) -> None:
        di, edir = self._entry_dir(bucket, object)
        with self._lock:
            self._pending_hits.pop(edir, None)
        if os.path.isdir(edir):
            size = self._walk_usage(edir)
            shutil.rmtree(edir, ignore_errors=True)
            self._account(di, -size)

    # -- store/serve ----------------------------------------------------------

    def _store_full(self, bucket: str, object: str, data: bytes, oi):
        if len(data) > self.quota // 2 or self._excluded(bucket, object):
            return
        di, edir = self._entry_dir(bucket, object)
        old = self._load_meta(edir)
        meta = self._new_meta(bucket, object, oi)
        meta["hits"] = (old or {}).get("hits", 0) + 1
        try:
            os.makedirs(edir, exist_ok=True)
            # a full copy supersedes any cached ranges
            for name in os.listdir(edir):
                if name.startswith("range-"):
                    try:
                        os.unlink(os.path.join(edir, name))
                    except OSError:
                        pass
            from .storage.durability import durable_write
            durable_write(os.path.join(edir, CACHE_DATA), data)
        except OSError:
            return
        self._save_meta(edir, meta)
        self._account(di, len(data) + 256)

    def _clear_stale_data(self, edir: str) -> None:
        """Remove part.1 and range files left by a previous object
        version: meta about to be written with a NEW etag must never
        coexist with old data files (a later full-read hit would serve
        the old bytes under the new etag)."""
        removed = 0
        try:
            for name in os.listdir(edir):
                if name == CACHE_DATA or name.startswith("range-"):
                    p = os.path.join(edir, name)
                    try:
                        removed += os.path.getsize(p)
                        os.unlink(p)
                    except OSError:
                        pass
        except OSError:
            return
        if removed:
            di = int(os.path.basename(edir)[:8], 16) % len(self.dirs)
            self._account(di, -removed)

    def _store_range(self, bucket: str, object: str, start: int,
                     data: bytes, oi):
        if not data or len(data) > MAX_RANGE_BYTES or \
                self._excluded(bucket, object):
            return
        di, edir = self._entry_dir(bucket, object)
        meta = self._load_meta(edir)
        if meta is None or meta.get("etag") != oi.etag:
            if meta is not None:
                self._clear_stale_data(edir)
            meta = self._new_meta(bucket, object, oi)
        end = start + len(data) - 1
        fname = f"range-{start}-{end}"
        try:
            from .storage.durability import durable_write
            os.makedirs(edir, exist_ok=True)
            durable_write(os.path.join(edir, fname), data)
        except OSError:
            return
        meta.setdefault("ranges", {})[f"{start}-{end}"] = fname
        meta["atime"] = time.time()
        self._save_meta(edir, meta)
        self._account(di, len(data) + 256)

    def _serve(self, edir: str, meta: dict, writer, offset: int,
               length: int) -> bool:
        """Serve [offset, offset+length) from part.1 or a covering cached
        range. Returns False when nothing covers the request."""
        size = meta.get("size", 0)
        if length < 0:
            length = size - offset
        end = offset + length - 1
        data_path = os.path.join(edir, CACHE_DATA)
        try:
            if os.path.exists(data_path):
                with open(data_path, "rb") as f:
                    f.seek(offset)
                    writer.write(f.read(max(0, length)))
                return True
            for rng, fname in (meta.get("ranges") or {}).items():
                s, _, e = rng.partition("-")
                rs, re_ = int(s), int(e)
                if rs <= offset and end <= re_:
                    with open(os.path.join(edir, fname), "rb") as f:
                        f.seek(offset - rs)
                        writer.write(f.read(max(0, length)))
                    return True
        except (OSError, ValueError):
            return False
        return False

    def _bump(self, edir: str, meta: dict) -> None:
        # throttle: rewriting cache.json on EVERY hit doubles hit-path
        # IO; increments accumulate in memory and flush every few hits
        # (or when recency is stale), so none are lost to the throttle
        with self._lock:
            pending = self._pending_hits.get(edir, 0) + 1
            stale = time.time() - meta.get("atime", 0) >= 60
            if pending < 8 and not stale:
                self._pending_hits[edir] = pending
                return
            self._pending_hits.pop(edir, None)
        meta["hits"] = meta.get("hits", 0) + pending
        meta["atime"] = time.time()
        self._save_meta(edir, meta)

    # -- hot paths ------------------------------------------------------------

    def get_object(self, bucket, object, writer, offset=0, length=-1,
                   opts=None):
        opts = opts or dt.ObjectOptions()
        if opts.version_id:
            # versioned reads bypass the cache (it stores latest only)
            return self.inner.get_object(bucket, object, writer, offset,
                                         length, opts)
        di, edir = self._entry_dir(bucket, object)
        meta = self._load_meta(edir)
        try:
            oi = self.inner.get_object_info(bucket, object, opts)
        except (dt.ObjectNotFound, dt.BucketNotFound, dt.VersionNotFound):
            self._drop(bucket, object)
            raise
        except Exception:  # noqa: BLE001 — backend down: serve cached
            if meta is not None and self._serve(edir, meta, writer,
                                                offset, length):
                self.hits += 1
                return self._oi_from_meta(bucket, object, meta)
            raise
        if meta is not None and meta.get("etag") == oi.etag and \
                self._serve(edir, meta, writer, offset, length):
            self.hits += 1
            self._bump(edir, meta)
            return oi
        self.misses += 1
        # "after" gate: count reads in a meta-only entry until the
        # object earns a cached copy (config cache.after). A new object
        # version (etag change) starts counting over.
        if self.after > 0:
            same = meta is not None and meta.get("etag") == oi.etag
            seen = (meta.get("hits", 0) + 1) if same else 1
            if seen < self.after:
                m = meta if same else self._new_meta(bucket, object, oi)
                if not same and meta is not None:
                    self._clear_stale_data(edir)
                m["hits"] = seen
                if not self._excluded(bucket, object):
                    self._save_meta(edir, m)
                return self.inner.get_object(bucket, object, writer,
                                             offset, length, opts)
        if offset == 0 and (length < 0 or length >= oi.size):
            buf = io.BytesIO()
            out = self.inner.get_object(bucket, object, buf, 0, -1, opts)
            data = buf.getvalue()
            writer.write(data)
            self._store_full(bucket, object, data, oi)
            return out
        # ranged miss: buffer + cache only when the range is cacheable;
        # oversized or excluded ranges stream straight through (one huge
        # Range request must not balloon into a full in-RAM copy)
        want = length if length >= 0 else max(0, oi.size - offset)
        if want > MAX_RANGE_BYTES or self._excluded(bucket, object):
            return self.inner.get_object(bucket, object, writer, offset,
                                         length, opts)
        buf = io.BytesIO()
        out = self.inner.get_object(bucket, object, buf, offset, length,
                                    opts)
        data = buf.getvalue()
        writer.write(data)
        self._store_range(bucket, object, offset, data, oi)
        return out

    def _oi_from_meta(self, bucket: str, object: str, meta: dict):
        return dt.ObjectInfo(
            bucket=bucket, name=object, size=meta.get("size", 0),
            etag=meta.get("etag", ""),
            content_type=meta.get("content_type", ""),
            user_defined=dict(meta.get("user_defined", {})))

    def get_object_info(self, bucket, object, opts=None):
        opts = opts or dt.ObjectOptions()
        try:
            return self.inner.get_object_info(bucket, object, opts)
        except (dt.ObjectNotFound, dt.BucketNotFound, dt.VersionNotFound):
            raise
        except Exception:  # noqa: BLE001 — backend down: cached HEAD
            if not opts.version_id:
                _, edir = self._entry_dir(bucket, object)
                meta = self._load_meta(edir)
                if meta is not None:
                    return self._oi_from_meta(bucket, object, meta)
            raise

    # the body has to come through the cache: the layers' default (this
    # get_object_info, then this get_object), not the inner layer's
    # handle, which __getattr__ would hand out
    get_object_n_info = ObjectLayer.get_object_n_info

    def put_object(self, bucket, object, stream, size, opts=None):
        oi = self.inner.put_object(bucket, object, stream, size, opts)
        self._drop(bucket, object)  # stale entry out; repopulate on read
        return oi

    def delete_object(self, bucket, object, opts=None):
        self._drop(bucket, object)
        return self.inner.delete_object(bucket, object, opts)

    def delete_objects(self, bucket, objects, opts=None):
        for obj in objects:
            name = obj if isinstance(obj, str) else obj.get("object", "")
            self._drop(bucket, name)
        return self.inner.delete_objects(bucket, objects, opts)

    def copy_object(self, src_bucket, src_object, dst_bucket, dst_object,
                    src_info, src_opts, dst_opts):
        self._drop(dst_bucket, dst_object)
        return self.inner.copy_object(src_bucket, src_object, dst_bucket,
                                      dst_object, src_info, src_opts,
                                      dst_opts)

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "usage": self.usage(), "quota": self.quota * len(self.dirs),
                "dirs": len(self.dirs)}

    # -- delegation -----------------------------------------------------------

    def __getattr__(self, name):
        return getattr(self.inner, name)
