"""S3-compatible HTTP API server (reference cmd/api-router.go:82 +
cmd/object-handlers.go / cmd/bucket-handlers.go): path-style routing over an
ObjectLayer, SigV4 auth, XML responses.

Threaded stdlib HTTP server: request concurrency maps to the dispatch
queue's batching (many in-flight PUT/GET blocks coalesce into single device
launches); the reference's per-node request throttle (cmd/handler-api.go:29)
is the QoS admission controller (minio_tpu.qos.admission): per-class token
buckets + a bounded-wait concurrency gate answering 503 SlowDown +
Retry-After under overload."""
from __future__ import annotations

import hashlib
import os
import socket
import threading
import urllib.parse
import xml.etree.ElementTree as ET
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..bucket import BucketMetadataSys
from ..obs import attribution as _attr
from ..obs import stages as _stages
from ..objectlayer import ObjectLayer, ObjectOptions
from ..objectlayer import datatypes as dt
from ..utils.hashreader import (BadDigestError, HashReader,
                                SHA256MismatchError)
from . import xmlutil as xu
from .auth import (STREAMING_PAYLOAD, UNSIGNED_PAYLOAD, AuthError,
                   ChunkedSigV4Reader, SigV4Verifier, parse_auth_header,
                   signing_key)

MAX_OBJECT_SIZE = 5 << 40       # 5 TiB (docs/minio-limits.md:25)
MAX_PUT_SIZE = 5 << 30          # single PUT cap 5 GiB

_HOST_ID = ""


def host_id() -> str:
    """Stable per-host opaque id stamped as ``x-amz-id-2`` / error-XML
    ``HostId`` (the reference derives its extended request id the same
    way: an opaque token identifying the serving host)."""
    global _HOST_ID
    if not _HOST_ID:
        import base64
        _HOST_ID = base64.b64encode(hashlib.sha256(
            socket.gethostname().encode()).digest()).decode()[:44]
    return _HOST_ID


class S3Server:
    """Owns the ObjectLayer, auth, bucket metadata; builds the HTTP server."""

    def __init__(self, objlayer: ObjectLayer, address: str = "0.0.0.0",
                 port: int = 9000, region: str = "us-east-1",
                 access_key: str = "", secret_key: str = "",
                 max_requests: int = 256,
                 extra_addresses: list[tuple[str, int]] | None = None):
        #: additional (host, port) bindings served alongside the main
        #: one (reference multi-addr xhttp.Listener)
        self.extra_addresses = list(extra_addresses or [])
        self._extra_httpds: list[ThreadingHTTPServer] = []
        self.obj = objlayer
        self.region = region
        self.access_key = access_key or os.environ.get(
            "MINIO_ROOT_USER", "minioadmin")
        self.secret_key = secret_key or os.environ.get(
            "MINIO_ROOT_PASSWORD", "minioadmin")
        self.bucket_meta = BucketMetadataSys(objlayer)
        #: pluggable credential lookup — IAM replaces this (minio_tpu.iam)
        self.lookup_secret = lambda ak: (
            self.secret_key if ak == self.access_key else None)
        #: optional IAM policy gate: fn(access_key, action, bucket, object)
        self.authorize = None
        self.iam = None
        #: optional event notifier: fn(event_name, bucket, object_info)
        self.notify = None
        self._notifier = None
        #: federation bucket DNS (dist.federation.BucketDNS) — None when
        #: the deployment is not federated
        self.federation = None
        self._notifier_lock = threading.Lock()
        self.verifier = SigV4Verifier(lambda ak: self.lookup_secret(ak),
                                      region)
        self.address = address
        self.port = port
        from ..crypto import kms as _kms_mod
        _kms_mod.configure(self.secret_key)
        cfg = None
        if objlayer is not None:
            # attach the config KVS to its persistence backend so stored
            # settings survive restarts (env > stored > default)
            from ..config import get_config_sys
            cfg = get_config_sys(objlayer)
        # QoS admission control (minio_tpu.qos.admission) replaces the
        # old bare 256-permit semaphore: a request that cannot get a slot
        # within the bounded wait (or whose class token bucket is empty)
        # is answered 503 SlowDown + Retry-After instead of parking a
        # handler thread
        from ..qos import AdmissionController
        if cfg is not None and cfg.source("api", "requests_max") != \
                "default":
            # operator-set env/stored value wins over the constructor
            # default; an explicit constructor argument wins otherwise
            max_requests = cfg.get_int("api", "requests_max", max_requests)
        self.qos_admission = AdmissionController(max_requests=max_requests)
        if cfg is not None:
            import weakref
            ref = weakref.ref(self)

            def _apply_api(c, _ref=ref):
                s = _ref()
                if s is not None and \
                        c.source("api", "requests_max") != "default":
                    s.qos_admission.reconfigure(
                        c.get_int("api", "requests_max",
                                  s.qos_admission.max_requests))

            cfg.on_apply("api", _apply_api)
            # declarative KVS fault rules (chaos harness): applied once
            # at start and on every dynamic `fault` subsystem change
            from .. import fault as _fault
            cfg.on_apply("fault", _fault.apply_config)
            _fault.apply_config(cfg)
        # always-on continuous profiler (obs/profiler.py): one
        # process-global daemon whatever the server count — repeated
        # server cycles must not accumulate threads (test_leaks)
        from ..obs import profiler as _profiler
        _profiler.ensure_started()
        self._httpd: ThreadingHTTPServer | None = None
        #: internal RPC services mounted under /minio/<name>/v1/<method>
        #: (storage/lock/peer — populated by dist.node.Node)
        self.internal: dict[str, object] = {}
        #: live accepted connections — node-kill chaos severs these the
        #: way a dead process would (keep-alive peers must not keep
        #: talking to a "killed" node through zombie sockets)
        self._conns: set = set()
        self._conns_lock = threading.Lock()

    def _track_conn(self, sock) -> None:
        with self._conns_lock:
            self._conns.add(sock)

    def _untrack_conn(self, sock) -> None:
        with self._conns_lock:
            self._conns.discard(sock)

    def hard_close_connections(self) -> None:
        """Sever every accepted connection (fault.node.node_kill): a
        SIGKILL'd process takes its established sockets with it, so
        the in-process kill must too — otherwise peers keep completing
        RPCs against the 'dead' node over keep-alive connections."""
        with self._conns_lock:
            conns, self._conns = list(self._conns), set()
        for s in conns:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def enable_iam(self):
        """Attach the IAM subsystem: per-user credentials, policy
        enforcement, STS, anonymous bucket-policy access."""
        from ..iam import IAMSys
        self.iam = IAMSys(self.obj, self.access_key, self.secret_key)
        self.lookup_secret = self.iam.lookup_secret
        self.authorize = self._iam_authorize
        return self.iam

    def create_bucket(self, bucket: str, object_lock: bool = False):
        """Bucket creation shared by the S3 and console paths: federation
        namespace check + metadata record + DNS registration, with a
        symmetric rollback when registration fails."""
        dns = self.federation
        if dns is not None:
            owners = dns.lookup(bucket)
            if owners and not dns.is_mine(owners):
                raise dt.BucketExists(bucket)
        self.obj.make_bucket(bucket)
        from ..bucket.metadata import BucketMetadata
        meta = BucketMetadata(name=bucket)
        if object_lock:
            meta.object_lock_enabled = True
            meta.versioning_enabled = True
        self.bucket_meta.set(bucket, meta)
        if dns is not None:
            from ..dist.federation import FederationConflict
            try:
                dns.put(bucket)
            except Exception as e:  # noqa: BLE001 — unregistered bucket
                # would be invisible to the federation: undo everything
                self.obj.delete_bucket(bucket, force=True)
                self.bucket_meta.remove(bucket)
                if self._notifier is not None:
                    self._notifier.invalidate(bucket)
                if isinstance(e, FederationConflict):
                    # lost the atomic claim race to another cluster
                    raise dt.BucketExists(bucket) from None
                raise dt.InvalidRequest(
                    bucket, "", f"federation DNS: {e}") from None

    def remove_bucket(self, bucket: str, force: bool = False):
        """Bucket deletion shared by the S3 and console paths."""
        if force and self.bucket_meta.get(bucket).object_lock_enabled:
            # force delete would bypass WORM retention (the reference
            # refuses force-delete on lock buckets the same way)
            raise dt.InvalidRequest(
                bucket, "",
                "force delete not allowed on object-lock buckets")
        # the bucket must exist locally before DNS is touched: deleting
        # a bucket we don't hold must not strip (or, via the restore
        # below, resurrect) another cluster's registration
        self.obj.get_bucket_info(bucket)
        if self.federation is not None:
            # unregister FIRST and fail the request when etcd is down:
            # entries take no lease, so a silently-skipped delete would
            # poison the name federation-wide forever (the reference
            # DeleteBucketHandler errors out the same way)
            try:
                self.federation.delete(bucket)
            except Exception as e:  # noqa: BLE001
                raise dt.InvalidRequest(
                    bucket, "", f"federation DNS: {e}") from None
        try:
            self.obj.delete_bucket(bucket, force=force)
        except dt.BucketNotFound:
            raise  # lost a delete race: nothing to restore
        except BaseException:
            if self.federation is not None:
                try:  # local delete failed: restore the DNS record
                    self.federation.put(bucket)
                except Exception:  # noqa: BLE001 — best effort
                    pass
            raise
        self.bucket_meta.remove(bucket)
        if self._notifier is not None:
            # a recreated bucket must not inherit the old routing rules
            self._notifier.invalidate(bucket)

    def enable_federation(self, dns):
        """Attach a federation BucketDNS (dist.federation): bucket
        create/delete register in etcd, foreign-bucket requests proxy to
        the owning cluster, ListBuckets shows the federated namespace."""
        self.federation = dns
        return dns

    def ensure_notifier(self):
        """The event notifier, created lazily when a live listener needs
        it before any target configuration. Chains with (never replaces)
        an existing notify hook — a replication chain attached earlier
        must keep firing — and the lock closes the concurrent-first-
        listener race that would orphan one notifier."""
        with self._notifier_lock:
            if self._notifier is None:
                from ..event import EventNotifier
                n = EventNotifier(self.bucket_meta, [], "", self.region)
                prev = self.notify
                if prev is None:
                    self.notify = n
                else:
                    def chained(event, bucket, oi, *a):
                        n(event, bucket, oi, *a)
                        prev(event, bucket, oi, *a)
                    self.notify = chained
                self._notifier = n
            return self._notifier

    def enable_replication(self, pool):
        """Attach a ReplicationPool: object events feed it (chained with
        any existing notifier) and GETs of locally-missing objects proxy
        to the bucket's target (reference proxy-to-target on GET miss)."""
        self.replication = pool
        # read-chain-store of self.notify must be atomic: an unguarded
        # enable racing another notifier attach drops one of the links
        with self._notifier_lock:
            prev = self.notify

            def chained(event, bucket, oi, *a):
                pool.on_event(event, bucket, oi)
                if prev is not None:
                    prev(event, bucket, oi, *a)

            self.notify = chained
        return pool

    def enable_cross_replication(self, rs):
        """Attach the cross-node ReplicationSys (bucket/replicate.py):
        completed writes/deletes charge replication debt through the
        notify chain, and the scanner re-charges PENDING/FAILED
        leftovers each cycle. Distinct from ``enable_replication``
        (the S3-target pool): this plane ships over the dist peer RPC
        with MRF-style journalled retry."""
        self.replication_sys = rs
        # same atomic read-chain-store discipline as enable_replication
        with self._notifier_lock:
            prev = self.notify

            def chained(event, bucket, oi, *a):
                rs.charge(event, bucket, oi)
                if prev is not None:
                    prev(event, bucket, oi, *a)

            self.notify = chained
        sc = getattr(self, "scanner", None)
        if sc is not None:
            sc.replication = rs
        # replication lag rides the SLO plane as a real objective
        from ..obs import slo as _slo
        _slo.register_async_probe("replication", rs.lag_report)
        return rs

    def enable_events(self, targets: list | None = None,
                      queue_root: str = ""):
        """Attach the event-notification subsystem: persistent per-target
        delivery queues + ARN routing from bucket notification configs.
        Targets default to the env-configured webhooks
        (MINIO_TPU_NOTIFY_WEBHOOK_ENDPOINT_<ID>); the queue root defaults
        to MINIO_TPU_NOTIFY_QUEUE_DIR or .events under the cwd."""
        from ..event import EventNotifier, targets_from_env
        from ..event.notifier import targets_from_config
        if targets is None:
            targets = targets_from_env(self.region)
            try:
                from ..config import get_config_sys
                targets += targets_from_config(get_config_sys(self.obj),
                                               self.region)
            except Exception:  # noqa: BLE001 — no config plane wired
                pass
        if not queue_root:
            queue_root = os.environ.get(
                "MINIO_TPU_NOTIFY_QUEUE_DIR",
                os.path.join(os.getcwd(), ".minio-tpu-events"))
        with self._notifier_lock:
            if self._notifier is not None:
                # a lazily created (listener-only) notifier already
                # exists and live streams hold subscriptions on it —
                # attach the targets to THAT instance instead of
                # replacing it (which would orphan every open listen
                # stream and drop any chained notify hook)
                self._notifier.add_targets(targets, queue_root)
                return self._notifier
            self._notifier = EventNotifier(self.bucket_meta, targets,
                                           queue_root, self.region)
            prev = self.notify
            if prev is None:
                self.notify = self._notifier
            else:
                n = self._notifier

                def chained(event, bucket, oi, *a):
                    n(event, bucket, oi, *a)
                    prev(event, bucket, oi, *a)

                self.notify = chained
            return self._notifier

    def _iam_authorize(self, access_key: str, action: str, bucket: str,
                       object: str) -> bool:
        if self.iam.is_allowed(access_key, action, bucket, object):
            return True
        # bucket policy may grant the (possibly anonymous) principal
        if bucket:
            from ..iam.policy import Policy, policy_allows
            meta = self.bucket_meta.get(bucket)
            if meta.policy_json:
                try:
                    bp = Policy.parse(meta.policy_json)
                except ValueError:
                    return False
                resource = f"{bucket}/{object}" if object else bucket
                return policy_allows([bp], action, resource,
                                     principal=access_key or "*")
        return False

    # --- server lifecycle ---------------------------------------------------

    def build(self) -> ThreadingHTTPServer:
        server = self

        class Handler(_S3Handler):
            s3 = server

        class TunedServer(ThreadingHTTPServer):
            """Listener tuning (reference cmd/http/server.go +
            listener.go): deep accept backlog for bursty S3 clients,
            TCP_NODELAY + keepalive on every accepted connection so small
            metadata responses don't sit in Nagle buffers and dead peers
            get reaped, and an idle read timeout so keep-alive
            connections that go quiet release their handler thread
            (thread-per-connection's slowloris exposure; reference
            ReadTimeout, cmd/http/server.go)."""
            request_queue_size = 1024
            daemon_threads = True
            idle_timeout_s = float(os.environ.get(
                "MINIO_TPU_HTTP_IDLE_TIMEOUT_S", "120"))

            def process_request(self, request, client_address):
                try:
                    request.setsockopt(socket.IPPROTO_TCP,
                                       socket.TCP_NODELAY, 1)
                    request.setsockopt(socket.SOL_SOCKET,
                                       socket.SO_KEEPALIVE, 1)
                    if self.idle_timeout_s > 0:
                        request.settimeout(self.idle_timeout_s)
                except OSError:
                    pass
                server._track_conn(request)
                super().process_request(request, client_address)

            def shutdown_request(self, request):
                server._untrack_conn(request)
                super().shutdown_request(request)

            def handle_error(self, request, client_address):
                # a client (or node-kill chaos) severing the socket
                # mid-response is normal churn, not a server error —
                # everything else keeps the stderr traceback
                import sys as _sys
                et = _sys.exc_info()[0]
                if et is not None and issubclass(
                        et, (BrokenPipeError, ConnectionResetError,
                             TimeoutError, socket.timeout)):
                    return
                super().handle_error(request, client_address)

        httpd = TunedServer((self.address, self.port), Handler)
        self._httpd = httpd
        self.port = httpd.server_address[1]
        # multi-address listening (reference xhttp.Listener,
        # cmd/http/listener.go: one logical server accepting on several
        # host:port bindings): each extra address gets its own accept
        # loop feeding the same handler/server state
        try:
            for host, port in self.extra_addresses:
                extra = TunedServer((host, port), Handler)
                self._extra_httpds.append(extra)
        except OSError:
            # a failed extra bind must not leak the sockets already
            # bound (or leave a shutdown() that would wait forever on
            # servers whose serve_forever never ran)
            for s in self._extra_httpds:
                s.server_close()
            self._extra_httpds = []
            httpd.server_close()
            self._httpd = None
            raise
        self.extra_ports = [s.server_address[1]
                            for s in self._extra_httpds]
        return httpd

    def serve_forever(self):
        httpd = self.build()
        for extra in self._extra_httpds:
            threading.Thread(target=extra.serve_forever,
                             name="minio-tpu-http-extra",
                             daemon=True).start()
        httpd.serve_forever()

    def start_background(self) -> threading.Thread:
        httpd = self.build()
        t = threading.Thread(target=httpd.serve_forever,
                             name="minio-tpu-http", daemon=True)
        t.start()
        for extra in self._extra_httpds:
            threading.Thread(target=extra.serve_forever,
                             name="minio-tpu-http-extra",
                             daemon=True).start()
        return t

    def start_background_services(self, scan_interval_s: float = 300.0):
        """Attach and start the background plane (reference
        cmd/server-main.go:508-514 initAutoHeal / initDataScanner + MRF):
        MRF healer, data scanner with lifecycle+transition hooks, fresh-
        disk auto-heal monitor. Idempotent; services land on self.mrf /
        self.scanner / self.autoheal, where the admin bg-heal-status op,
        peer RPC and the heal metrics group already look for them."""
        if getattr(self, "mrf", None) is not None:
            return
        from ..bucket.lifecycle import LifecycleSys
        from ..obs.metrics import _all_disks
        from ..scanner.autoheal import AutoHealMonitor
        from ..scanner.mrf import MRFHealer
        from ..scanner.scanner import DataScanner
        self.mrf = MRFHealer(self.obj)
        # persist the heal queue beside the tracker state on the first
        # local disk: heal debt recorded before a crash is re-enqueued
        # at the next start instead of waiting for a deep scanner cycle
        try:
            from ..storage.xlstorage import META_BUCKET
            disk = next(d for d in _all_disks(self.obj)
                        if getattr(d, "base", ""))
            self.mrf.attach_persistence(
                os.path.join(disk.base, META_BUCKET, "mrf.json"))
        except StopIteration:
            pass
        self.mrf.start()
        lc = LifecycleSys(self.obj, self.bucket_meta, self.transition)
        self.scanner = DataScanner(
            self.obj, interval_s=float(os.environ.get(
                "MINIO_TPU_SCANNER_INTERVAL_S", str(scan_interval_s))),
            mrf=self.mrf, lifecycle=lc).start()
        self.autoheal = AutoHealMonitor(
            self.obj, _all_disks(self.obj)).start()

        # wire the degraded-path signals into the background plane:
        # partial/bitrot detections enqueue MRF heals, and a health-
        # tracked disk that re-onlines kicks the auto-heal monitor so
        # the objects it missed get rebuilt promptly, and releases the
        # heal debt the MRF parked against it while it was away
        def _disk_state(disk, state, _srv=self):
            if state != "ok":
                return
            if getattr(_srv, "autoheal", None) is not None:
                from ..scanner.autoheal import set_healing_tracker
                try:
                    set_healing_tracker(disk)
                except Exception:  # noqa: BLE001 — disk may still be sick
                    pass
                _srv.autoheal.kick()
            if getattr(_srv, "mrf", None) is not None:
                _srv.mrf.release(disk.endpoint())
        for layer in self._erasure_layers():
            layer.on_partial = self.mrf.add_partial
            layer.on_disk_state = _disk_state

    def _erasure_layers(self) -> list:
        """Every ErasureObjects under any ObjectLayer shape (one set, a
        sets layer, or server pools)."""
        obj = self.obj
        if hasattr(obj, "pools"):
            out = []
            for p in obj.pools:
                out.extend(p.sets if hasattr(p, "sets") else [p])
            return out
        if hasattr(obj, "sets"):
            return list(obj.sets)
        return [obj] if hasattr(obj, "on_partial") else []

    def shutdown(self):
        for svc_name in ("scanner", "autoheal", "mrf", "replication_sys"):
            svc = getattr(self, svc_name, None)
            if svc is not None:
                try:
                    svc.stop()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
        if self._httpd is not None:
            self._httpd.shutdown()
        for extra in self._extra_httpds:
            extra.shutdown()

    def endpoint(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    @property
    def tiers(self):
        """Lazy tier registry (reference globalTierConfigMgr)."""
        if getattr(self, "_tiers", None) is None:
            from ..bucket.tiers import TierRegistry
            self._tiers = TierRegistry(self.obj)
        return self._tiers

    @property
    def transition(self):
        if getattr(self, "_transition", None) is None:
            from ..bucket.transition import TransitionSys
            self._transition = TransitionSys(self.obj, self.tiers,
                                             self.bucket_meta)
        return self._transition


class _ChunkedWriter:
    """HTTP/1.1 chunked transfer encoding over a raw socket file — lets
    event-stream responses (S3 Select) stream frames without knowing the
    total length up front."""

    def __init__(self, wfile):
        self.wfile = wfile

    def write(self, b: bytes) -> int:
        if b:
            self.wfile.write(f"{len(b):x}\r\n".encode() + b + b"\r\n")
        return len(b)

    def flush(self):  # writer-protocol consumers (zipfile) call this
        pass

    def close(self):
        self.wfile.write(b"0\r\n\r\n")


class _CountingWriter:
    """Transparent wfile proxy counting bytes written — the per-bucket
    traffic counters (obs/bucketstats) read ``sent`` deltas per request
    on a keep-alive connection, so streamed GET bodies are charged
    without any hook inside the streaming loops."""

    __slots__ = ("_w", "sent")

    def __init__(self, w):
        self._w = w
        self.sent = 0

    def write(self, b) -> int:
        n = self._w.write(b)
        self.sent += len(b)
        return n

    def __getattr__(self, name):
        return getattr(self._w, name)


class _S3Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    s3: S3Server = None  # set by subclass factory

    def setup(self):
        super().setup()
        self.wfile = _CountingWriter(self.wfile)

    # silence default request logging (trace subsystem handles this)
    def log_message(self, fmt, *args):  # noqa: A003
        pass

    def parse_request(self):
        """The request line is in: the first end of the request's record
        (obs/attribution.py), read before the head is parsed so that
        ``head`` is a stage of it. The wait for the line is the
        connection's idle time, not the request's."""
        self._head = _attr.mark() if _attr.enabled() else None
        return super().parse_request()

    # --- plumbing -----------------------------------------------------------

    def _parse(self):
        split = urllib.parse.urlsplit(self.path)
        self.raw_query = split.query
        self.url_path = urllib.parse.unquote(split.path)
        self.query = urllib.parse.parse_qs(split.query,
                                           keep_blank_values=True)
        parts = self.url_path.lstrip("/").split("/", 1)
        self.bucket = parts[0]
        self.key = parts[1] if len(parts) > 1 else ""
        self.hdr = {k.lower(): v for k, v in self.headers.items()}
        self._consumed = 0  # request-body bytes read (keep-alive hygiene)

    def q(self, key: str, default: str = "") -> str:
        v = self.query.get(key)
        return v[0] if v else default

    def has_q(self, key: str) -> bool:
        return key in self.query

    def _api_name(self) -> str:
        """S3 API name for the per-API metric labels (the reference tags
        minio_s3_requests_total / minio_s3_ttfb_seconds_distribution with
        api="getobject"-style names, cmd/metrics-v2.go:147-154)."""
        m, b, k = self.command, self.bucket, self.key
        if not b:
            return "listbuckets" if m == "GET" else "sts"
        if k:
            if m == "GET":
                if self.has_q("uploadId"):
                    return "listobjectparts"
                for sub in ("tagging", "retention", "legal-hold", "acl"):
                    if self.has_q(sub):
                        return f"getobject{sub.replace('-', '')}"
                return "getobject"
            if m == "HEAD":
                return "headobject"
            if m == "PUT":
                if self.has_q("partNumber"):
                    return "putobjectpart"
                if "x-amz-copy-source" in self.hdr:
                    return "copyobject"
                for sub in ("tagging", "retention", "legal-hold", "acl"):
                    if self.has_q(sub):
                        return f"putobject{sub.replace('-', '')}"
                return "putobject"
            if m == "POST":
                if self.has_q("uploads"):
                    return "newmultipartupload"
                if self.has_q("uploadId"):
                    return "completemultipartupload"
                if self.has_q("select") or self.q("select-type"):
                    return "selectobjectcontent"
                if self.has_q("restore"):
                    return "restoreobject"
                return "postobject"
            if m == "DELETE":
                if self.has_q("uploadId"):
                    return "abortmultipartupload"
                if self.has_q("tagging"):
                    return "deleteobjecttagging"
                return "deleteobject"
            return m.lower()
        # bucket-level
        subs = ("policy", "lifecycle", "versioning", "notification",
                "tagging", "object-lock", "replication", "encryption",
                "quota", "versions", "uploads", "location")
        sub = next((s for s in subs if self.has_q(s)), "")
        if m == "GET":
            if sub == "versions":
                return "listobjectversions"
            if sub == "uploads":
                return "listmultipartuploads"
            if sub:
                return f"getbucket{sub.replace('-', '')}"
            return "listobjectsv2" if self.q("list-type") == "2" \
                else "listobjectsv1"
        if m == "HEAD":
            return "headbucket"
        if m == "PUT":
            return f"putbucket{sub.replace('-', '')}" if sub \
                else "putbucket"
        if m == "DELETE":
            return f"deletebucket{sub.replace('-', '')}" if sub \
                else "deletebucket"
        if m == "POST":
            if self.has_q("delete"):
                return "deletemultipleobjects"
            return "postpolicybucket"
        return m.lower()

    def _send(self, status: int, body: bytes = b"",
              content_type: str = "application/xml",
              headers: dict | None = None):
        if getattr(self, "_last_status", 0):
            # a response already started for this request — this is an
            # error surfacing MID-BODY (e.g. the object was deleted under
            # a streaming GET). Appending an error document would corrupt
            # the keep-alive framing: the client would block inside the
            # truncated body instead of seeing EOF. Cut the connection.
            self.close_connection = True
            return
        with _stages.stage("respond"):
            self.send_response(status)
            for k, v in (headers or {}).items():
                if v is not None and v != "":
                    self.send_header(k, v)
            if body or status not in (204, 304):
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
            else:
                self.send_header("Content-Length", "0")
            self.end_headers()
            if body and self.command != "HEAD":
                self.wfile.write(body)

    def _error(self, code: str, message: str, status: int):
        if status in (204, 304):  # bodiless statuses per RFC 9110
            return self._send(status)
        self._send(status, xu.error_xml(
            code, message, getattr(self, "url_path", self.path),
            request_id=getattr(self, "_request_id", ""),
            host_id=host_id()))

    def _api_error(self, e: dt.ObjectAPIError):
        self._error(e.code, str(e), e.http_status)

    def _read_body(self) -> bytes:
        n = int(self.hdr.get("content-length", "0") or "0")
        data = self.rfile.read(n) if n else b""
        self._consumed += len(data)
        return data

    def _drain_body(self):
        """Discard any unread request body so the next request on this
        keep-alive connection parses cleanly; large remainders close the
        connection instead of burning bandwidth."""
        try:
            n = int(self.hdr.get("content-length", "0") or "0")
        except (AttributeError, ValueError):
            return
        remaining = n - getattr(self, "_consumed", 0)
        if remaining <= 0:
            return
        if remaining > (1 << 20):
            self.close_connection = True
            return
        while remaining > 0:
            chunk = self.rfile.read(min(65536, remaining))
            if not chunk:
                break
            remaining -= len(chunk)

    # --- auth ---------------------------------------------------------------

    def _authenticate(self) -> str:
        headers = dict(self.hdr)
        headers.setdefault("host", self.headers.get("Host", ""))
        return self.s3.verifier.verify(
            self.command, self.url_path, self.query, headers)

    def _authorize(self, access_key: str, action: str,
                   bucket: str | None = None, key: str | None = None):
        gate = self.s3.authorize
        if gate is None:
            if access_key == "":
                raise AuthError("AccessDenied", "anonymous access denied")
            return
        bucket = self.bucket if bucket is None else bucket
        key = self.key if key is None else key
        with _stages.stage("auth"):
            allowed = gate(access_key, action, bucket, key)
        if not allowed:
            raise AuthError("AccessDenied", f"not allowed to {action}")

    def _sts(self, body: bytes):
        """STS: AssumeRole (signed caller), AssumeRoleWithWebIdentity /
        AssumeRoleWithClientGrants (OIDC JWT against the configured
        provider) and AssumeRoleWithLDAPIdentity (simple bind) —
        reference cmd/sts-handlers.go:43-93."""
        form = dict(urllib.parse.parse_qsl(body.decode("utf-8", "replace")))
        action = form.get("Action", "AssumeRole")
        try:
            duration = int(form.get("DurationSeconds", "3600") or "3600")
        except ValueError:
            return self._error("InvalidParameterValue",
                               "DurationSeconds must be an integer", 400)
        session_policy = form.get("Policy", "").encode()
        try:
            if action == "AssumeRoleWithWebIdentity":
                cred = self.s3.iam.assume_role_with_web_identity(
                    form.get("WebIdentityToken", ""), duration,
                    session_policy)
            elif action == "AssumeRoleWithClientGrants":
                cred = self.s3.iam.assume_role_with_client_grants(
                    form.get("Token", ""), duration, session_policy)
            elif action == "AssumeRoleWithLDAPIdentity":
                cred = self.s3.iam.assume_role_with_ldap_identity(
                    form.get("LDAPUsername", ""),
                    form.get("LDAPPassword", ""), duration,
                    session_policy)
            elif action == "AssumeRole":
                try:
                    ak = self._authenticate()
                except AuthError as e:
                    return self._error(e.code, e.message, e.status)
                cred = self.s3.iam.assume_role(ak, duration,
                                               session_policy)
            else:
                return self._error("InvalidAction",
                                   f"unsupported STS action {action}",
                                   400)
        except ValueError as e:
            return self._error("InvalidParameterValue", str(e), 400)
        import datetime
        exp = datetime.datetime.fromtimestamp(
            cred.expiration, tz=datetime.timezone.utc
        ).strftime("%Y-%m-%dT%H:%M:%SZ")
        result = f"{action}Result"
        xml = (
            '<?xml version="1.0" encoding="UTF-8"?>'
            f'<{action}Response xmlns='
            '"https://sts.amazonaws.com/doc/2011-06-15/">'
            f"<{result}><Credentials>"
            f"<AccessKeyId>{cred.access_key}</AccessKeyId>"
            f"<SecretAccessKey>{cred.secret_key}</SecretAccessKey>"
            f"<SessionToken>minio-tpu-session</SessionToken>"
            f"<Expiration>{exp}</Expiration>"
            f"</Credentials></{result}></{action}Response>"
        ).encode()
        self._send(200, xml)

    def _body_stream(self, size: int):
        """Request-body reader honoring aws-chunked streaming signatures."""
        sha = self.hdr.get("x-amz-content-sha256", "")
        if sha == STREAMING_PAYLOAD:
            auth = parse_auth_header(self.hdr.get("authorization", ""))
            secret = self.s3.lookup_secret(auth.access_key)
            key = signing_key(secret, auth.scope_date, auth.region,
                              auth.service)
            scope = (f"{auth.scope_date}/{auth.region}/{auth.service}/"
                     "aws4_request")
            # chunked framing makes residual length unknowable: if the
            # handler errors mid-stream, close rather than drain
            self._consumed = 1 << 62
            self.close_connection = True
            return ChunkedSigV4Reader(
                self.rfile, auth.signature, key,
                self.hdr.get("x-amz-date", ""), scope)
        return _CappedReader(self.rfile, size, self)

    # --- routing ------------------------------------------------------------

    def _plane_name(self) -> str:
        """'admin' / 'internal' for the server's own planes (every path
        under /minio/), '' for an S3 call."""
        path = getattr(self, "url_path", self.path)
        if path.startswith("/minio/admin/"):
            return "admin"
        return "internal" if path.startswith("/minio/") else ""

    def _plane(self):
        """The routing ladder ahead of S3: what serves this request when
        it is not an S3 call, as a callable (run by ``_route`` outside the
        ``route`` stage), or None."""
        # unauthenticated health endpoints (cmd/healthcheck-handler.go):
        # liveness = this process serves HTTP (the RPC reconnect pings
        # probe it DURING cluster bootstrap, when no node has an object
        # layer yet — gating it on readiness deadlocks a fresh cluster);
        # readiness/cluster = storage is actually online
        if self.url_path.startswith("/minio/health/"):
            if self.url_path.rstrip("/").endswith("/live"):
                return lambda: self._send(200, b"",
                                          "text/plain; charset=utf-8")
            ok = self.s3.obj is not None and self.s3.obj.is_ready()
            return lambda: self._send(200 if ok else 503, b"",
                                      "text/plain; charset=utf-8")
        # internal RPC services (storage/lock/peer — reference
        # registerDistErasureRouters, cmd/routers.go:26-39)
        if self.url_path.startswith("/minio/") and self.s3.internal:
            parts = self.url_path.split("/", 4)
            if len(parts) >= 5 and parts[2] in self.s3.internal:
                return lambda: self._internal_rpc(parts[2], parts[4])
        if self.s3.obj is None:
            return lambda: self._error("ServerNotInitialized",
                                       "server still starting", 503)
        if self.url_path.startswith("/minio/metrics") or \
                self.url_path.startswith("/minio/v2/metrics"):
            return self._metrics
        if self.url_path.startswith("/minio/admin/"):
            from .admin import handle_admin
            return lambda: handle_admin(self)
        # web console plane (reference cmd/web-router.go: /minio/webrpc
        # JSON-RPC + JWT-authenticated upload/download routes + the static
        # single-file SPA at /minio/)
        if self.url_path in ("/minio", "/minio/", "/minio/index.html"):
            from .webrpc import handle_console
            return lambda: handle_console(self)
        if self.url_path == "/minio/webrpc":
            from .webrpc import handle_webrpc
            return lambda: handle_webrpc(self)
        if self.url_path.startswith("/minio/upload/"):
            from .webrpc import handle_upload
            rest = self.url_path[len("/minio/upload/"):]
            bucket, _, obj = rest.partition("/")
            return lambda: handle_upload(self, bucket, obj)
        if self.url_path.startswith("/minio/download/"):
            from .webrpc import handle_download
            rest = self.url_path[len("/minio/download/"):]
            bucket, _, obj = rest.partition("/")
            return lambda: handle_download(self, bucket, obj)
        if self.url_path == "/minio/zip":
            from .webrpc import handle_download_zip
            return lambda: handle_download_zip(self)
        # STS endpoint: POST / with form-encoded Action (cmd/sts-handlers.go)
        # — AssumeRoleWithWebIdentity carries no Authorization header (the
        # JWT is the credential), so the gate is the Action itself
        if self.command == "POST" and self.url_path == "/" and \
                self.s3.iam is not None:
            body = self._read_body()
            if b"Action=Assume" in body or b"Action=assume" in body:
                return lambda: self._sts(body)
        # browser POST uploads authenticate via the signed policy inside
        # the form, not an Authorization header
        if self.command == "POST" and self.key == "" and \
                self.bucket and self.hdr.get("content-type", "").startswith(
                    "multipart/form-data"):
            return self._post_policy
        return None

    def _metrics(self):
        from ..obs.metrics import render_prometheus
        scope = "node" if self.url_path.rstrip("/").endswith("/node") \
            else "cluster"
        # ?attribution=1 appends the standing per-op stage
        # breakdown families (minio_tpu_stage_*, ISSUE 9)
        attribution = self.query.get("attribution", [""])[0] == "1"
        # exemplars are OpenMetrics-only syntax: emit them (and the
        # matching content type + # EOF) only on EXPLICIT
        # ?openmetrics=1 request. Not Accept-negotiated on purpose:
        # modern Prometheus lists openmetrics-text in its default
        # Accept, and this exposition keeps classic counter naming
        # ('X_total' declared as-is), which a STRICT OM parser
        # rejects wholesale — sniffing Accept would break scrapers
        # that parse the classic form fine today. A classic parser
        # conversely reads a trailing exemplar '#' as an invalid
        # timestamp, so the default form strips them.
        om = self.query.get("openmetrics", [""])[0] == "1"
        ctype = ("application/openmetrics-text; version=1.0.0; "
                 "charset=utf-8") if om else \
            "text/plain; version=0.0.4"
        return self._send(200, render_prometheus(
            self.s3, scope, attribution=attribution,
            openmetrics=om), ctype)

    def _post_policy(self):
        try:
            return self.post_policy_upload()
        except dt.ObjectAPIError as e:
            return self._api_error(e)
        except AuthError as e:
            return self._error(e.code, e.message, e.status)

    def _route(self):
        with _stages.stage("route"):
            self._parse()
            unit = getattr(self, "_unit", None)
            if unit is not None:
                # the stages of a request go by its API's name from here
                unit.st.api = self._api = self._plane_name() or \
                    self._api_name()
            plane = self._plane()
        if plane is not None:
            return plane()
        try:
            with _stages.stage("auth"):
                access_key = self._authenticate()
        except AuthError as e:
            # anonymous access rides bucket policies when IAM is on
            if self.s3.iam is not None and e.code == "AccessDenied" and \
                    "no authentication" in e.message:
                access_key = ""
            else:
                return self._error(e.code, e.message, e.status)
        try:
            if self._maybe_forward_federated(access_key):
                return
            self._dispatch(access_key)
        except dt.ObjectAPIError as e:
            self._api_error(e)
        except AuthError as e:
            self._error(e.code, e.message, e.status)
        except (BadDigestError, SHA256MismatchError) as e:
            self._error("BadDigest", str(e), 400)
        except BrokenPipeError:
            # client went away mid-response; the half-written reply makes
            # this connection unusable for keep-alive
            self.close_connection = True
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            self._error("InternalError", str(e), 500)

    #: federation forwarding: S3 action to enforce locally before the
    #: request is re-signed with cluster credentials — without this gate
    #: a scoped IAM user could escalate to root on the remote cluster
    _FWD_ACTIONS = {"GET": ("s3:GetObject", "s3:ListBucket"),
                    "HEAD": ("s3:GetObject", "s3:ListBucket"),
                    "PUT": ("s3:PutObject", "s3:CreateBucket"),
                    "POST": ("s3:PutObject", "s3:PutObject"),
                    "DELETE": ("s3:DeleteObject", "s3:DeleteBucket")}

    def _maybe_forward_federated(self, access_key: str) -> bool:
        """Federation forwarding (reference setBucketForwardingHandler,
        cmd/routers.go:73 + cmd/bucket-handlers.go DNS lookups): when the
        requested bucket is not local but the federation DNS says another
        cluster owns it, proxy the request there re-signed with this
        cluster's credentials (federated clusters share root creds).
        The caller's OWN policy gate runs first. Returns True when the
        response was served by the remote."""
        dns = self.s3.federation
        if dns is None or not self.bucket:
            return False
        if self.command == "PUT" and not self.key and \
                not self.query:
            return False  # bucket create: handled by put_bucket
        from ..utils import errors as st_errors
        try:
            self.s3.obj.get_bucket_info(self.bucket)
            return False  # local bucket: serve it here
        except (dt.BucketNotFound, st_errors.StorageError):
            pass
        if self.hdr.get("x-minio-tpu-forwarded"):
            # loop guard: a forwarded request that still isn't local here
            # (stale DNS pointing back at us) must fail, not re-forward
            return False
        owners = dns.lookup(self.bucket)
        if not owners or dns.is_mine(owners):
            return False  # unknown everywhere -> local NoSuchBucket
        obj_action, bkt_action = self._FWD_ACTIONS.get(
            self.command, ("s3:PutObject", "s3:PutObject"))
        if self.command == "POST" and "delete" in self.query:
            # multi-object delete rides POST: enforce the delete action,
            # not PutObject
            obj_action = bkt_action = "s3:DeleteObject"
        self._authorize(access_key,
                        obj_action if self.key else bkt_action)
        host, port = owners[0]
        import requests as rq
        # aws-chunked bodies: the wire length includes chunk framing; the
        # proxied body is the DECODED payload (the local handlers use the
        # same header, s3api _hash_reader)
        if self.hdr.get("x-amz-content-sha256", "") == STREAMING_PAYLOAD:
            size = int(self.hdr.get("x-amz-decoded-content-length",
                                    "0") or "0")
        else:
            size = int(self.hdr.get("content-length", "0") or "0")
        body = _LenReader(self._body_stream(size), size) if size else b""
        headers = {"host": f"{host}:{port}"}
        passthrough = ("content-type", "range", "if-match",
                       "if-none-match", "if-modified-since",
                       "if-unmodified-since", "content-md5")
        for k, v in self.hdr.items():
            if k in passthrough or k.startswith("x-amz-meta-"):
                headers[k] = v
        headers["x-minio-tpu-forwarded"] = "1"
        auth = self.s3.verifier.sign_request(
            self.s3.access_key, self.s3.secret_key, self.command,
            self.url_path, self.query, headers, UNSIGNED_PAYLOAD)
        headers["authorization"] = auth
        qs = urllib.parse.urlencode(
            [(k, v) for k, vs in self.query.items() for v in vs])
        url = f"http://{host}:{port}" \
              f"{urllib.parse.quote(self.url_path)}" + \
              (f"?{qs}" if qs else "")
        try:
            resp = rq.request(self.command, url, data=body,
                              headers=headers, timeout=30, stream=True)
        except Exception as e:  # noqa: BLE001 — owning cluster down
            self._error("ServiceUnavailable",
                        f"federated cluster unreachable: {e}", 503)
            return True
        self.send_response(resp.status_code)
        hop = {"connection", "transfer-encoding", "keep-alive"}
        length = resp.headers.get("Content-Length")
        for k, v in resp.headers.items():
            if k.lower() not in hop:
                self.send_header(k, v)
        if length is None:
            body_bytes = resp.content
            self.send_header("Content-Length", str(len(body_bytes)))
            self.end_headers()
            self.wfile.write(body_bytes)
        else:
            self.end_headers()
            for chunk in resp.iter_content(1 << 20):
                self.wfile.write(chunk)
        resp.close()
        return True

    def _internal_rpc(self, service: str, method: str):
        """Dispatch an internal RPC call (bearer-token auth, typed errors
        over headers — SURVEY.md A.7 wire shape)."""
        from ..dist.rpc import check_token, rpc_error_response
        auth = self.hdr.get("authorization", "")
        token = auth[len("Bearer "):] if auth.startswith("Bearer ") else ""
        if not check_token(self.s3.secret_key, token):
            return self._send(401, b"invalid rpc token", "text/plain")
        params = {k: v[0] for k, v in self.query.items()}
        body = self._read_body()
        # span propagation: an RPC that carried the caller's traceparent
        # joins that trace — storage/lock/peer spans recorded under this
        # fragment share the caller's trace_id and are stored locally
        # for the caller's ?trace_id=...&peers=1 merge
        from ..obs import spans as sp
        ctx_in = sp.parse_traceparent(self.hdr.get(sp.RPC_HEADER, ""))
        try:
            with sp.fragment(ctx_in, f"rpc.{service}.{method}",
                             node=f"{self.s3.address}:{self.s3.port}"):
                out = self.s3.internal[service].handle(method, params,
                                                       body)
        except Exception as e:  # noqa: BLE001
            return rpc_error_response(self, e)
        if out is not None and not isinstance(out, (bytes, bytearray)):
            # streaming method (live trace/console): chunked NDJSON with
            # keepalive newlines (A.7 framing)
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            w = _ChunkedWriter(self.wfile)
            try:
                for chunk in out:
                    if chunk:
                        w.write(chunk)
            except Exception:  # noqa: BLE001 — client went away mid-stream
                self.close_connection = True
                return
            w.close()
            return
        self._send(200, out, "application/octet-stream")

    def _dispatch(self, access_key: str):
        m = self.command
        if not self.bucket:
            if m == "GET":
                return self.list_buckets(access_key)
            return self._error("MethodNotAllowed", "bad service op", 405)
        if not self.key:
            return self._bucket_op(m, access_key)
        return self._object_op(m, access_key)

    def _bucket_op(self, m: str, ak: str):
        s = self
        if m == "PUT":
            if s.has_q("versioning"):
                return s.put_versioning(ak)
            if s.has_q("tagging"):
                return s.put_bucket_tagging(ak)
            if s.has_q("policy"):
                return s.put_bucket_policy(ak)
            if s.has_q("notification"):
                return s.put_bucket_notification(ak)
            if s.has_q("lifecycle"):
                return s.put_bucket_lifecycle(ak)
            if s.has_q("replication"):
                return s.put_bucket_replication(ak)
            if s.has_q("object-lock"):
                return s.put_object_lock_config(ak)
            return s.put_bucket(ak)
        if m in ("GET", "HEAD"):
            if s.has_q("location"):
                return s._send(200, xu.location_xml(s.s3.region))
            if s.has_q("versioning"):
                return s.get_versioning(ak)
            if s.has_q("tagging"):
                return s.get_bucket_tagging(ak)
            if s.has_q("policy"):
                return s.get_bucket_policy(ak)
            if s.has_q("notification"):
                return s.get_bucket_notification(ak)
            if s.has_q("lifecycle"):
                return s.get_bucket_lifecycle(ak)
            if s.has_q("replication"):
                return s.get_bucket_replication(ak)
            if s.has_q("object-lock"):
                return s.get_object_lock_config(ak)
            if s.has_q("uploads"):
                return s.list_uploads(ak)
            if s.has_q("versions"):
                return s.list_versions(ak)
            if s.has_q("events") and m == "GET":
                return s.listen_bucket_notification(ak)
            if m == "HEAD":
                return s.head_bucket(ak)
            return s.list_objects(ak)
        if m == "DELETE":
            if s.has_q("tagging"):
                return s.delete_bucket_tagging(ak)
            if s.has_q("policy"):
                return s.delete_bucket_policy(ak)
            if s.has_q("lifecycle"):
                return s.delete_bucket_lifecycle(ak)
            if s.has_q("replication"):
                return s.delete_bucket_replication(ak)
            return s.delete_bucket(ak)
        if m == "POST":
            if s.has_q("delete"):
                return s.delete_multiple(ak)
        return s._error("MethodNotAllowed", f"bad bucket op {m}", 405)

    def post_policy_upload(self):
        """Browser POST upload with a signed policy document (reference
        PostPolicyBucketHandler, cmd/bucket-handlers.go +
        cmd/postpolicyform.go): the form's base64 policy is signed with
        the SigV4 signing key, conditions are enforced, then the file
        field becomes the object."""
        import base64
        import email.parser
        import email.policy as email_policy
        import json as jsonmod
        import re as remod

        from .auth import signing_key
        # the multipart parser is in-memory and the signature can only be
        # checked AFTER parsing, so an unauthenticated body must be capped
        # up front (DoS guard; env-tunable for big browser uploads)
        max_post = int(os.environ.get("MINIO_TPU_MAX_POST_SIZE",
                                      str(64 << 20)))
        declared = int(self.hdr.get("content-length", "0") or 0)
        if declared > max_post:
            raise dt.EntityTooLarge(self.bucket, "")
        body = self._read_body()
        if len(body) > max_post:
            raise dt.EntityTooLarge(self.bucket, "")
        blob = (b"Content-Type: " + self.hdr["content-type"].encode() +
                b"\r\n\r\n" + body)
        msg = email.parser.BytesParser(
            policy=email_policy.default).parsebytes(blob)
        fields: dict[str, str] = {}
        file_bytes = b""
        filename = ""
        for part in msg.iter_parts():
            cd = part.get("Content-Disposition", "")
            m = remod.search(r'name="([^"]*)"', cd)
            if not m:
                continue
            name = m.group(1)
            if name == "file":
                payload = part.get_payload(decode=True) or b""
                file_bytes = payload
                fm = remod.search(r'filename="([^"]*)"', cd)
                filename = fm.group(1) if fm else ""
            else:
                fields[name.lower()] = str(
                    part.get_payload(decode=True).decode(
                        "utf-8", "replace"))
        policy_b64 = fields.get("policy", "")
        if not policy_b64:
            return self._error("AccessDenied",
                               "POST upload requires a policy", 403)
        if fields.get("x-amz-algorithm", "") != "AWS4-HMAC-SHA256":
            return self._error("InvalidArgument",
                               "unsupported x-amz-algorithm", 400)
        cred = fields.get("x-amz-credential", "")
        try:
            ak, scope_date, region, _service, _term = cred.split("/")
        except ValueError:
            return self._error("InvalidArgument",
                               "malformed x-amz-credential", 400)
        secret = self.s3.lookup_secret(ak)
        if secret is None:
            return self._error("InvalidAccessKeyId",
                               "access key not found", 403)
        key = signing_key(secret, scope_date, region)
        import hmac as hmacmod
        sig = hmacmod.new(key, policy_b64.encode(),
                          hashlib.sha256).hexdigest()
        if not hmacmod.compare_digest(sig,
                                      fields.get("x-amz-signature", "")):
            return self._error("SignatureDoesNotMatch",
                               "policy signature mismatch", 403)
        try:
            policy = jsonmod.loads(base64.b64decode(policy_b64))
        except Exception:  # noqa: BLE001
            return self._error("InvalidPolicyDocument", "bad policy", 400)
        # expiration + conditions (cmd/postpolicyform.go)
        import datetime as dtmod
        exp = policy.get("expiration", "")
        try:
            exp_t = dtmod.datetime.fromisoformat(
                exp.replace("Z", "+00:00")).timestamp()
        except ValueError:
            return self._error("InvalidPolicyDocument",
                               "bad expiration", 400)
        import time as tmod
        if exp_t < tmod.time():
            return self._error("AccessDenied", "policy expired", 403)
        key_field = fields.get("key", "")
        if "${filename}" in key_field:
            key_field = key_field.replace("${filename}", filename)
        if not key_field:
            return self._error("InvalidArgument", "missing key field", 400)
        # every form field must be authorized by a policy condition
        # (cmd/postpolicyform.go checkPostPolicy) — otherwise a signed
        # grant for one key lets the holder inject arbitrary metadata
        covered = {"policy", "x-amz-signature", "file"}
        for cond in policy.get("conditions", []):
            if isinstance(cond, dict):
                covered.update(k.lower() for k in cond)
            elif isinstance(cond, list) and len(cond) == 3:
                covered.add(str(cond[1]).lstrip("$").lower())
        for fname in fields:
            if fname in covered or fname.startswith("x-ignore-"):
                continue
            return self._error(
                "AccessDenied",
                f"form field {fname!r} not covered by the policy", 403)
        for cond in policy.get("conditions", []):
            if isinstance(cond, dict):
                for ck, cv in cond.items():
                    got = self.bucket if ck == "bucket" else \
                        fields.get(ck.lower(), "")
                    if ck == "key":
                        got = key_field
                    if got != cv:
                        return self._error(
                            "AccessDenied",
                            f"policy condition failed on {ck}", 403)
            elif isinstance(cond, list) and len(cond) == 3:
                op, name, val = cond
                if op == "content-length-range":
                    try:
                        lo, hi = int(name), int(val)
                    except (TypeError, ValueError):
                        return self._error(
                            "InvalidPolicyDocument",
                            "bad content-length-range bounds", 400)
                    if not (lo <= len(file_bytes) <= hi):
                        return self._error(
                            "EntityTooLarge" if len(file_bytes) > hi
                            else "EntityTooSmall",
                            "content-length-range violated", 400)
                    continue
                name = str(name).lstrip("$").lower()
                got = key_field if name == "key" else (
                    self.bucket if name == "bucket"
                    else fields.get(name, ""))
                if op == "eq" and got != val:
                    return self._error(
                        "AccessDenied",
                        f"policy eq condition failed on {name}", 403)
                if op == "starts-with" and not str(got).startswith(val):
                    return self._error(
                        "AccessDenied",
                        f"policy starts-with failed on {name}", 403)
        self._authorize(ak, "s3:PutObject", self.bucket, key_field)
        self.key = key_field
        import io as iomod
        opts = self._opts()
        meta = {k: v for k, v in fields.items()
                if k.startswith("x-amz-meta-")}
        ct = fields.get("content-type", "")
        if ct:
            meta["content-type"] = ct
        # the POST path enforces the SAME server policies as PUT: size cap,
        # quota, object-lock defaults, transparent compression
        if len(file_bytes) > MAX_PUT_SIZE:
            raise dt.EntityTooLarge(self.bucket, key_field)
        self._check_quota(len(file_bytes))
        from ..bucket import objectlock as olock
        lock_enabled, lock_default = self._lock_ctx()
        meta.update(olock.check_put_headers(
            fields, self.bucket, key_field, lock_enabled, lock_default))
        hr = HashReader(iomod.BytesIO(file_bytes), len(file_bytes))
        stream, put_size = hr, len(file_bytes)
        from ..utils import compress as cz
        if cz.should_compress(key_field, ct):
            meta[cz.META_COMPRESSION] = cz.algo()
            meta[cz.META_ACTUAL_SIZE] = str(len(file_bytes))
            stream, put_size = cz.compress_reader(hr), -1
            opts.etag_source = hr
        opts.user_defined = meta
        oi = self.s3.obj.put_object(self.bucket, key_field, stream,
                                    put_size, opts)
        try:
            status = int(fields.get("success_action_status", "204") or 204)
        except ValueError:
            status = 204
        if status not in (200, 201, 204):
            status = 204
        self._send(status, headers={"ETag": f'"{oi.etag}"'})
        self._notify("s3:ObjectCreated:Post", oi)

    def _object_op(self, m: str, ak: str):
        s = self
        if m == "PUT":
            if s.has_q("partNumber") and s.has_q("uploadId"):
                return s.put_part(ak)
            if s.has_q("tagging"):
                return s.put_object_tagging(ak)
            if s.has_q("retention"):
                return s.put_object_retention(ak)
            if s.has_q("legal-hold"):
                return s.put_object_legal_hold(ak)
            if "x-amz-copy-source" in s.hdr:
                return s.copy_object(ak)
            return s.put_object(ak)
        if m == "GET":
            if s.has_q("uploadId"):
                return s.list_parts(ak)
            if s.has_q("tagging"):
                return s.get_object_tagging(ak)
            if s.has_q("retention"):
                return s.get_object_retention(ak)
            if s.has_q("legal-hold"):
                return s.get_object_legal_hold(ak)
            return s.get_object(ak)
        if m == "HEAD":
            return s.head_object(ak)
        if m == "DELETE":
            if s.has_q("uploadId"):
                return s.abort_upload(ak)
            if s.has_q("tagging"):
                return s.delete_object_tagging(ak)
            return s.delete_object(ak)
        if m == "POST":
            if s.has_q("uploads"):
                return s.initiate_upload(ak)
            if s.has_q("uploadId"):
                return s.complete_upload(ak)
            if s.has_q("select") or s.q("select-type"):
                return s.select_object_content(ak)
            if s.has_q("restore"):
                return s.restore_object(ak)
        return s._error("MethodNotAllowed", f"bad object op {m}", 405)

    def select_object_content(self, ak):
        """SelectObjectContent (reference cmd/object-handlers.go:96 ->
        pkg/s3select): run the SQL over the object and stream event-stream
        frames. Encrypted objects are decrypted first (the reference does
        the same through GetObjectNInfo's decrypting reader)."""
        self._authorize(ak, "s3:GetObject")
        from ..s3select import S3SelectRequest, parse_select, run_select
        from ..s3select.sql import SQLError
        body = self._read_body()
        try:
            req = S3SelectRequest.parse(body)
            # validate the SQL BEFORE reading the object (a bad expression
            # must 400 without paying the read; frames stream chunked
            # after the 200, so late errors can only abort mid-stream)
            parsed = parse_select(req.expression)
        except (ET.ParseError, SQLError) as e:
            return self._error("InvalidRequest", str(e), 400)
        oi, body = self.s3.obj.get_object_n_info(self.bucket, self.key,
                                                 self._opts())
        sse = self._sse_read_ctx(oi)
        import io as iomod
        sink = iomod.BytesIO()
        # BytesScanned = input consumed from storage (ciphertext /
        # compressed); the engine reports the decoded size as
        # BytesProcessed (s3select/message.py events)
        scanned = oi.size
        # the SQL engine needs plaintext
        self._write_plain(body, oi, sse, sink)
        raw = sink.getvalue()
        self.send_response(200)
        self.send_header("Content-Type",
                         "application/vnd.amazon.eventstream")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        out = _ChunkedWriter(self.wfile)
        try:
            run_select(req, raw, out, parsed=parsed,
                       scanned_bytes=scanned)
        except Exception:  # noqa: BLE001 — mid-stream failure: cut the
            self.close_connection = True  # connection, the client sees EOF
            return
        out.close()

    # --- HTTP verbs ---------------------------------------------------------

    def send_response(self, code, message=None):  # noqa: N802
        self._last_status = code
        if getattr(self, "_t_first", None) is None:
            import time as _time
            self._t_first = _time.perf_counter()  # TTFB anchor
        super().send_response(code, message)
        # every response carries the request id (= trace id) and host id
        # (reference setAmzRequestID middleware: x-amz-request-id +
        # x-amz-id-2 on all paths, streams and errors included) so
        # client-reported slowness joins server-side traces
        rid = getattr(self, "_request_id", "")
        if rid:
            self.send_header("x-amz-request-id", rid)
            self.send_header("x-amz-id-2", host_id())

    def _admit(self):
        """Admission control (minio_tpu.qos.admission) ahead of routing:
        object/control-plane requests pass the per-class token bucket +
        bounded-wait concurrency gate or are answered ``503 SlowDown`` +
        ``Retry-After`` (reference AmzRequestsDeadline behavior of
        cmd/handler-api.go, with S3-semantic backpressure instead of
        silent thread pile-up). Health, metrics, admin and internal-RPC
        planes are exempt — an overloaded server must stay observable.
        Returns (proceed, release_cb)."""
        from ..qos import classify_request
        adm = getattr(self.s3, "qos_admission", None)
        # stashed for the finish-side tail-sampling budget: the trace
        # must be judged under the SAME class it was admitted under
        cls = self._qos_class = classify_request(
            self.command, self.path, internal=self.s3.internal)
        if adm is None or cls is None:
            return True, None
        grant = adm.admit(cls)
        if grant.ok:
            return True, lambda: adm.release(grant)
        from ..obs import metrics as mx
        mx.inc("minio_tpu_qos_admission_rejects_total",
               reason=grant.reason, **{"class": cls})
        # parse url/headers so the surrounding observability plane (per-
        # API 503 counters, trace, audit) attributes this rejection like
        # any other response; the body stays unread — close instead of
        # leaving the keep-alive connection mid-stream, and say so: a
        # client that is not told sends its next request into the close
        self._parse()
        self.close_connection = True
        self._send(
            503,
            xu.error_xml(
                "SlowDown",
                "request rate/concurrency limit exceeded; reduce "
                "your request rate", self.url_path,
                request_id=getattr(self, "_request_id", ""),
                host_id=host_id()),
            headers={"Retry-After": adm.retry_after_header(grant),
                     "Connection": "close"})
        return False, None

    def _span_exempt(self, path: str, query: str = "") -> bool:
        """Requests that never open a request-scoped trace:
        health/metrics probes (pure overhead), internal RPC (which
        instead JOINS the caller's trace via the traceparent header in
        _internal_rpc) — the same plane list admission control exempts
        — and long-poll streams (admin trace follows, bucket event
        listens) whose duration is client-chosen: they would breach any
        latency budget by design and churn genuinely slow traces out of
        the bounded store."""
        from ..qos.admission import plane_exempt
        if plane_exempt(path, internal=self.s3.internal):
            return True
        if path.startswith("/minio/admin/") and \
                path.rstrip("/").endswith("/trace"):
            return True
        if self.command == "GET" and "events=" in query and \
                not path.startswith("/minio/"):
            # ListenBucketNotification long-poll: a GET on a BUCKET
            # path with an events param — object GETs that merely carry
            # an events= value in some parameter stay traced
            parts = path.lstrip("/").split("/", 1)
            bucket_level = len(parts) < 2 or parts[1] == ""
            if bucket_level and "events" in urllib.parse.parse_qs(
                    query, keep_blank_values=True):
                return True
        return False

    def _handle(self):
        """Route one request wrapped in the observability plane
        (cmd/http-tracer.go httpTraceAll + cmd/http-stats.go): timing,
        metrics, trace pubsub, audit entry, request-scoped span root
        (obs/spans.py) with tail-sampled slow-trace capture. Admission
        rejections run INSIDE this wrapper so overload 503s land in the
        same per-API counters, trace stream and audit log as every
        other response."""
        import time as _time

        from ..obs import latency as _lt
        from ..obs import metrics as mx
        from ..obs import spans as sp
        from ..obs import trace as trc
        from ..obs.logger import log_sys
        self._last_status = 0
        self._t_first = None
        # the trace id IS the x-amz-request-id — minted before routing
        # so even admission 503s and parse errors carry it
        rid = sp.new_trace_id()
        self._request_id = rid
        root = tok = None
        raw_path, _, raw_query = self.path.partition("?")
        span_exempt = self._span_exempt(raw_path, raw_query)
        if sp.enabled() and not span_exempt:
            root, tok = sp.begin_request(rid)
        t0 = _time.perf_counter()
        sent_mark = getattr(self.wfile, "sent", 0)
        release = None
        from ..obs import profiler as _prof
        # the request's stage collector, from the socket to the reply
        # (obs/attribution.py): armed here, where the span root is, its
        # first end read when the request line came in (parse_request)
        self._api = None
        self._unit = unit = _attr.begin(
            rid, f"s3.{self.command.lower()}", getattr(self, "_head", None))
        try:
            with _stages.stage("admit"):
                proceed, release = self._admit()
            # per-thread QoS tag (obs/profiler.py): contextvars are not
            # visible cross-thread, so the sampling profiler joins this
            # worker's samples to its admitted class + op through the
            # ident-keyed tag registry instead
            _prof.set_task_tag(
                getattr(self, "_qos_class", None) or "control",
                f"s3.{self.command.lower()}")
            if proceed:
                self._route()
        finally:
            _prof.clear_task_tag()
            if release is not None:
                release()
            try:
                with _stages.stage("drain"):
                    self._drain_body()
            except Exception:  # noqa: BLE001
                self.close_connection = True
            epilogue = _stages.stage("epilogue")
            epilogue.__enter__()
            dur = _time.perf_counter() - t0
            status = getattr(self, "_last_status", 0)
            path = getattr(self, "url_path", self.path)
            api = self._plane_name() or f"s3.{self.command}"
            api_detail = api
            try:
                mx.inc("minio_tpu_requests_total", api=api,
                       code=str(status))
                mx.observe("minio_tpu_request_duration_seconds", dur,
                           api=api)
                ttfb = (self._t_first or _time.perf_counter()) - t0
                if api.startswith("s3."):
                    # per-API-name family (reference metrics-v2 label
                    # scheme: api="getobject"-style)
                    name = self._api or self._api_name()
                    api_detail = f"s3.{name}"
                    mx.inc("minio_tpu_s3_requests_total", api=name)
                    if status >= 400:
                        mx.inc("minio_tpu_s3_requests_errors_total",
                               api=name)
                    mx.observe("minio_tpu_s3_ttfb_seconds", ttfb, api=name)
                    # per-API window whose worst sample keeps its trace
                    # id — `top/api` links the tail to a span tree.
                    # Only TRACED requests feed it: span-exempt
                    # long-polls (trace follows, event listens) would
                    # otherwise park multi-second traceless samples as
                    # the window's worst and blank the exemplar row
                    if root is not None:
                        _lt.observe("api", dur, 0,
                                    trace_id=rid if root.sampled else "",
                                    api=name)
                    # per-bucket analytics (obs/bucketstats): request
                    # counts, traffic bytes, TTFB/wall windows keyed by
                    # the BOUNDED registry — long-polls stay out for
                    # the same client-chosen-duration reason as spans
                    bkt = getattr(self, "bucket", "")
                    if bkt and not span_exempt:
                        from ..obs import bucketstats as _bs
                        sent = getattr(self.wfile, "sent", 0)
                        _bs.record_request(
                            bkt, name, status, dur, ttfb_s=ttfb,
                            bytes_in=getattr(self, "_consumed", 0),
                            bytes_out=max(0, sent - sent_mark))
                elif api == "admin" and root is not None:
                    _lt.observe("api", dur, 0,
                                trace_id=rid if root.sampled else "",
                                api="admin")
                if api != "internal":
                    info = trc.TraceInfo(
                        node=f"{self.s3.address}:{self.s3.port}",
                        func=api, method=self.command,
                        path=path, query=getattr(self, "raw_query", ""),
                        status=status, duration_s=dur, ttfb_s=ttfb,
                        input_bytes=int(getattr(self, "hdr", {}).get(
                            "content-length", "0") or 0),
                        remote=self.client_address[0],
                        trace_id=rid,
                        span_id=root.span_id if root is not None else "")
                    trc.publish(info)
                    # audit entries join traces by trace_id/request_id
                    # and carry the response outcome (status + duration
                    # already ride the trace record)
                    entry = info.to_dict()
                    entry["request_id"] = rid
                    entry["api"] = api_detail
                    log_sys().audit(entry)
                # SLO plane LAST (it may take the config-registry lock
                # resolving objectives): admitted-class requests (and
                # admission 503s) burn their class's error budget;
                # exempt planes (health/metrics/admin/internal-RPC)
                # carry no objective so qcls is None for them, and
                # span-exempt long-polls (trace follows, event
                # listens) stay out — their duration is CLIENT-chosen,
                # so every poll would read as a multi-second latency
                # breach on an idle server (same rule as the per-API
                # window above, but independent of spans being on)
                qcls = getattr(self, "_qos_class", None)
                if qcls is not None and not span_exempt:
                    from ..obs import slo as _slo
                    _slo.record(
                        qcls, dur, status=status,
                        trace_id=rid if root is not None and
                        root.sampled else "",
                        bucket=getattr(self, "bucket", "")
                        if api.startswith("s3.") else "")
            except Exception:  # noqa: BLE001 — obs must never break serving
                pass
            if root is not None:
                try:
                    cls = getattr(self, "_qos_class", None) or "control"
                    sp.finish_request(
                        root, tok, name=api_detail, method=self.command,
                        path=path, status=status, duration_s=dur,
                        cls=cls,
                        node=f"{self.s3.address}:{self.s3.port}",
                        remote=self.client_address[0])
                    kept = sp.store().get(rid) if root.sampled else None
                    if kept is not None and any(
                            s.get("name", "").startswith("rpc.")
                            for s in kept.get("spans", ())):
                        # the trace was KEPT and fanned out over RPC:
                        # snapshot peer fragments now, before their
                        # small LRUs churn them out (bounded background
                        # worker — the response is already sent)
                        peers = getattr(self.s3, "peers",
                                        lambda: [])()
                        if peers:
                            sp.schedule_collect(rid, peers)
                except Exception:  # noqa: BLE001
                    pass
            epilogue.__exit__(None, None, None)
            self._head = None
            _attr.finish(
                unit, api_detail[3:] if api_detail.startswith("s3.")
                else api_detail, status,
                getattr(self, "_consumed", 0) + max(
                    0, getattr(self.wfile, "sent", 0) - sent_mark))

    def do_GET(self):  # noqa: N802
        self._handle()

    def do_PUT(self):  # noqa: N802
        self._handle()

    def do_POST(self):  # noqa: N802
        self._handle()

    def do_DELETE(self):  # noqa: N802
        self._handle()

    def do_HEAD(self):  # noqa: N802
        self._handle()

    # --- service ------------------------------------------------------------

    def list_buckets(self, ak):
        self._authorize(ak, "s3:ListAllMyBuckets")
        buckets = self.s3.obj.list_buckets()
        if self.s3.federation is not None:
            # the federated namespace is the union of every cluster's
            # buckets (cmd/bucket-handlers.go ListBuckets with etcd)
            have = {b.name for b in buckets}
            for name in sorted(self.s3.federation.list_buckets()):
                if name not in have:
                    buckets.append(dt.BucketInfo(name=name))
        self._send(200, xu.list_buckets_xml(buckets))

    # --- bucket -------------------------------------------------------------

    def put_bucket(self, ak):
        self._authorize(ak, "s3:CreateBucket")
        self.s3.create_bucket(
            self.bucket,
            object_lock=self.hdr.get(
                "x-amz-bucket-object-lock-enabled", "") == "true")
        self._send(200, headers={"Location": f"/{self.bucket}"})

    def head_bucket(self, ak):
        self._authorize(ak, "s3:ListBucket")
        self.s3.obj.get_bucket_info(self.bucket)
        self._send(200)

    def delete_bucket(self, ak):
        self._authorize(ak, "s3:DeleteBucket")
        force = self.hdr.get("x-minio-force-delete", "") == "true"
        self.s3.remove_bucket(self.bucket, force=force)
        self._send(204)

    @staticmethod
    def _display_sizes(r):
        """Listings must report the same size GET/HEAD do: for encrypted
        or compressed objects that is the plaintext size, not the stored
        stream length."""
        from ..bucket import transition as tx
        from ..crypto import META_SCHEME, plain_size_of
        from ..crypto.sse import META_MULTIPART
        from ..utils import compress as cz
        for oi in r.objects:
            if oi.internal.get(META_MULTIPART):
                oi.size = oi.actual_size    # the sum of its parts'
            elif oi.internal.get(META_SCHEME):
                oi.size = plain_size_of(oi.internal, oi.size)
            elif oi.internal.get(cz.META_COMPRESSION):
                oi.size = oi.actual_size
            elif tx.is_transitioned(oi) and oi.size == 0:
                oi.size = tx.transitioned_size(oi)
        return r

    def list_objects(self, ak):
        self._authorize(ak, "s3:ListBucket")
        prefix = self.q("prefix")
        delimiter = self.q("delimiter")
        max_keys = min(int(self.q("max-keys", "1000") or "1000"), 10_000)
        if self.q("list-type") == "2":
            marker = self.q("continuation-token") or self.q("start-after")
            r = self._display_sizes(self.s3.obj.list_objects(
                self.bucket, prefix, marker, delimiter, max_keys))
            return self._send(200, xu.list_objects_v2_xml(
                self.bucket, prefix, delimiter, max_keys, r,
                continuation_token=self.q("continuation-token")))
        marker = self.q("marker")
        r = self._display_sizes(self.s3.obj.list_objects(
            self.bucket, prefix, marker, delimiter, max_keys))
        self._send(200, xu.list_objects_v1_xml(
            self.bucket, prefix, delimiter, marker, max_keys, r))

    def list_versions(self, ak):
        self._authorize(ak, "s3:ListBucketVersions")
        prefix = self.q("prefix")
        delimiter = self.q("delimiter")
        max_keys = min(int(self.q("max-keys", "1000") or "1000"), 10_000)
        r = self._display_sizes(self.s3.obj.list_object_versions(
            self.bucket, prefix, self.q("key-marker"),
            self.q("version-id-marker"), delimiter, max_keys))
        self._send(200, xu.list_versions_xml(
            self.bucket, prefix, delimiter, max_keys, r))

    def put_versioning(self, ak):
        self._authorize(ak, "s3:PutBucketVersioning")
        self.s3.obj.get_bucket_info(self.bucket)
        body = self._read_body()
        enabled = xu.parse_versioning(body)
        was = self.s3.bucket_meta.get(self.bucket)
        if was.object_lock_enabled and not enabled:
            # suspending versioning would let WORM-retained versions be
            # hard-deleted via versionless deletes (AWS forbids changing
            # versioning state on object-lock buckets)
            raise dt.InvalidRequest(
                self.bucket, "",
                "cannot suspend versioning on an object-lock bucket")
        self.s3.bucket_meta.update(
            self.bucket, versioning_enabled=enabled,
            versioning_suspended=not enabled and
            (was.versioning_enabled or was.versioning_suspended))
        self._send(200)

    def get_versioning(self, ak):
        self._authorize(ak, "s3:GetBucketVersioning")
        self.s3.obj.get_bucket_info(self.bucket)
        meta = self.s3.bucket_meta.get(self.bucket)
        self._send(200, xu.versioning_xml(meta.versioning_enabled,
                                          meta.versioning_suspended))

    def put_bucket_tagging(self, ak):
        self._authorize(ak, "s3:PutBucketTagging")
        self.s3.obj.get_bucket_info(self.bucket)
        tags = xu.parse_tagging(self._read_body())
        self.s3.bucket_meta.update(self.bucket, tagging=tags)
        self._send(200)

    def get_bucket_tagging(self, ak):
        self._authorize(ak, "s3:GetBucketTagging")
        meta = self.s3.bucket_meta.get(self.bucket)
        if not meta.tagging:
            return self._error("NoSuchTagSet", "no tags", 404)
        self._send(200, xu.tagging_xml(meta.tagging))

    def delete_bucket_tagging(self, ak):
        self._authorize(ak, "s3:PutBucketTagging")
        self.s3.bucket_meta.update(self.bucket, tagging={})
        self._send(204)

    def put_bucket_policy(self, ak):
        self._authorize(ak, "s3:PutBucketPolicy")
        self.s3.obj.get_bucket_info(self.bucket)
        self.s3.bucket_meta.update(self.bucket,
                                   policy_json=self._read_body())
        self._send(204)

    def get_bucket_policy(self, ak):
        self._authorize(ak, "s3:GetBucketPolicy")
        meta = self.s3.bucket_meta.get(self.bucket)
        if not meta.policy_json:
            return self._error("NoSuchBucketPolicy", "no policy", 404)
        self._send(200, meta.policy_json, "application/json")

    def delete_bucket_policy(self, ak):
        self._authorize(ak, "s3:DeleteBucketPolicy")
        self.s3.bucket_meta.update(self.bucket, policy_json=b"")
        self._send(204)

    def listen_bucket_notification(self, ak):
        """Live event stream (the reference's ListenBucketNotification
        minio extension, cmd/bucket-notification-handlers.go): GET
        /bucket?events=<pattern>&prefix=&suffix= streams matching events
        as JSON lines over chunked encoding; blank keep-alive lines mark
        liveness. Needs no stored notification config — the filters ride
        the request. ?timeout bounds the stream (tests; clients normally
        hold it open)."""
        self._authorize(ak, "s3:ListenBucketNotification")
        self.s3.obj.get_bucket_info(self.bucket)
        # listening needs the event plane; attach it lazily with no
        # targets (queues only exist per target, listeners are free)
        notifier = self.s3.ensure_notifier()
        import json as _json
        import queue as qmod
        import time as _time
        events = tuple(v for vs in self.query.get("events", [])
                       for v in (vs.split(",") if vs else [])) or ("s3:*",)
        prefix = self.q("prefix")
        suffix = self.q("suffix")
        try:
            timeout = float(self.q("timeout", "86400") or "86400")
        except ValueError:
            timeout = -1.0
        if not timeout > 0:  # rejects 0, negatives AND NaN
            raise dt.InvalidRequest(self.bucket, "",
                                    "invalid listen timeout")
        sub = notifier.listen(self.bucket, prefix, suffix, events)
        try:  # from here every exit must unlisten, or the dead
            # subscription keeps collecting events forever
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            out = _ChunkedWriter(self.wfile)
            deadline = _time.monotonic() + timeout
            while _time.monotonic() < deadline:
                try:
                    rec = sub.q.get(timeout=min(
                        5.0, max(0.0, deadline - _time.monotonic())))
                except qmod.Empty:
                    out.write(b" \n")  # keep-alive (reference sends one)
                    self.wfile.flush()
                    continue
                out.write((_json.dumps(
                    {"Records": [rec]},
                    separators=(",", ":")) + "\n").encode())
                self.wfile.flush()
            out.close()
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass  # client went away: normal end of a listen stream
        finally:
            notifier.unlisten(sub)
            self.close_connection = True

    def put_bucket_notification(self, ak):
        self._authorize(ak, "s3:PutBucketNotification")
        self.s3.obj.get_bucket_info(self.bucket)
        body = self._read_body()
        from ..event import parse_notification_xml
        try:
            parsed = parse_notification_xml(body)
        except Exception:  # noqa: BLE001 — malformed XML
            return self._error("MalformedXML",
                               "invalid notification configuration", 400)
        if self.s3._notifier is not None and self.s3._notifier.targets:
            # a listener-only notifier (no configured targets) must not
            # reject every ARN — matching the pre-notifier behavior of
            # accepting and persisting the config
            unknown = self.s3._notifier.unknown_arns(parsed)
            if unknown:
                return self._error(
                    "InvalidArgument",
                    f"unknown notification target ARN(s): "
                    f"{', '.join(unknown)}", 400)
        self.s3.bucket_meta.update(self.bucket, notification_xml=body)
        if self.s3._notifier is not None:
            self.s3._notifier.invalidate(self.bucket)
        self._send(200)

    def get_bucket_notification(self, ak):
        self._authorize(ak, "s3:GetBucketNotification")
        meta = self.s3.bucket_meta.get(self.bucket)
        body = meta.notification_xml or \
            b'<?xml version="1.0" encoding="UTF-8"?>' \
            b'<NotificationConfiguration ' \
            b'xmlns="http://s3.amazonaws.com/doc/2006-03-01/"/>'
        self._send(200, body)

    def put_bucket_lifecycle(self, ak):
        self._authorize(ak, "s3:PutLifecycleConfiguration")
        self.s3.obj.get_bucket_info(self.bucket)
        self.s3.bucket_meta.update(self.bucket,
                                   lifecycle_xml=self._read_body())
        self._send(200)

    def get_bucket_lifecycle(self, ak):
        self._authorize(ak, "s3:GetLifecycleConfiguration")
        meta = self.s3.bucket_meta.get(self.bucket)
        if not meta.lifecycle_xml:
            return self._error("NoSuchLifecycleConfiguration",
                               "no lifecycle", 404)
        self._send(200, meta.lifecycle_xml)

    def delete_bucket_lifecycle(self, ak):
        self._authorize(ak, "s3:PutLifecycleConfiguration")
        self.s3.bucket_meta.update(self.bucket, lifecycle_xml=b"")
        self._send(204)

    def put_bucket_replication(self, ak):
        """PUT ?replication (reference PutBucketReplicationConfigHandler):
        rules validate before persisting — a rule without a destination
        would charge obligations nothing can ever pay."""
        self._authorize(ak, "s3:PutReplicationConfiguration")
        self.s3.obj.get_bucket_info(self.bucket)
        body = self._read_body()
        from ..bucket import replicate as repl
        try:
            repl.validate_replication(body)
        except (ET.ParseError, ValueError) as e:
            return self._error("MalformedXML", str(e), 400)
        self.s3.bucket_meta.update(self.bucket, replication_xml=body)
        self._send(200)

    def get_bucket_replication(self, ak):
        self._authorize(ak, "s3:GetReplicationConfiguration")
        meta = self.s3.bucket_meta.get(self.bucket)
        if not meta.replication_xml:
            return self._error("ReplicationConfigurationNotFoundError",
                               "no replication config", 404)
        self._send(200, meta.replication_xml)

    def delete_bucket_replication(self, ak):
        self._authorize(ak, "s3:PutReplicationConfiguration")
        self.s3.bucket_meta.update(self.bucket, replication_xml=b"")
        self._send(204)

    def delete_multiple(self, ak):
        self._authorize(ak, "s3:DeleteObject")
        self._last_ak = ak
        objs, quiet = xu.parse_delete_objects(self._read_body())
        versioned = self.s3.bucket_meta.versioning_enabled(self.bucket)
        # WORM: version deletes under retention/legal hold are refused
        # per key, not whole-request (reference DeleteObjects behavior)
        meta = self.s3.bucket_meta.get(self.bucket)
        locked_errs: list[tuple[int, str, str, BaseException]] = []
        if meta.object_lock_enabled:
            allowed = []
            for idx, obj in enumerate(objs):
                vid = "" if isinstance(obj, str) else obj.get(
                    "version_id", "")
                name = obj if isinstance(obj, str) else obj["object"]
                if vid:
                    try:
                        self._check_delete_lock(ObjectOptions(
                            version_id=vid, versioned=versioned), key=name)
                    except dt.ObjectAPIError as e:
                        # keep key+version so the <Error> entry names what
                        # was refused
                        locked_errs.append((idx, name, vid, e))
                        continue
                allowed.append(obj)
            objs = allowed
        deleted, errs = self.s3.obj.delete_objects(
            self.bucket, objs, ObjectOptions(versioned=versioned))
        for idx, name, vid, e in locked_errs:
            deleted.insert(idx, dt.DeletedObject(object_name=name,
                                                 version_id=vid))
            errs.insert(idx, e)
        ok_deleted = [d for d, e in zip(deleted, errs) if e is None]
        if quiet:
            # quiet mode reports only failures
            pairs = [(d, e) for d, e in zip(deleted, errs) if e is not None]
            deleted = [d for d, _ in pairs]
            errs = [e for _, e in pairs]
        self._send(200, xu.delete_result_xml(deleted, errs))
        self._notify_each("s3:ObjectRemoved:Delete", ok_deleted)

    def _notify_each(self, event, deleted):
        if self.s3.notify is None:
            return
        for d in deleted:
            if d is not None:
                self.s3.notify(event, self.bucket,
                               dt.ObjectInfo(bucket=self.bucket,
                                             name=d.object_name))

    # --- object -------------------------------------------------------------

    def _opts(self, versioned=None) -> ObjectOptions:
        if versioned is None:
            versioned = self.s3.bucket_meta.versioning_enabled(self.bucket)
        return ObjectOptions(version_id=self.q("versionId"),
                             versioned=versioned)

    def put_object(self, ak):
        self._authorize(ak, "s3:PutObject")
        size = int(self.hdr.get("content-length", "-1") or "-1")
        if self.hdr.get("x-amz-content-sha256", "") == STREAMING_PAYLOAD:
            size = int(self.hdr.get("x-amz-decoded-content-length",
                                    str(size)))
        if size < 0:
            # unbounded socket reads hang keep-alive connections
            return self._error("MissingContentLength",
                               "Content-Length required", 411)
        if size > MAX_PUT_SIZE:
            raise dt.EntityTooLarge(self.bucket, self.key)
        user_defined = self._user_meta()
        # object lock: validate headers / apply the bucket default
        from ..bucket import objectlock as olock
        lock_enabled, lock_default = self._lock_ctx()
        user_defined.update(olock.check_put_headers(
            self.hdr, self.bucket, self.key, lock_enabled, lock_default))
        # quota (reference cmd/bucket-quota.go: enforced from the data
        # usage snapshot, so it trails the scanner like the reference)
        self._check_quota(size)
        hr = self._hash_reader(size)
        from ..crypto import parse_sse_headers
        sse = parse_sse_headers(self.hdr, self.bucket, self.key)
        stream, put_size = hr, size
        sse_resp = {}
        opts = self._opts()
        if sse is not None:
            stream, put_size, sse_resp = self._encrypt_setup(
                sse, hr, size, user_defined)
        else:
            from ..utils import compress as cz
            if cz.should_compress(self.key,
                                  user_defined.get("content-type", "")):
                # compressed length is unknown up front: the object layer
                # streams to EOF (size=-1) and records the stored length;
                # ETag stays the PLAINTEXT md5 via etag_source
                user_defined[cz.META_COMPRESSION] = cz.algo()
                user_defined[cz.META_ACTUAL_SIZE] = str(size)
                stream, put_size = cz.compress_reader(hr), -1
                opts.etag_source = hr
        # replication charged at PUT: the status lands IN xl.meta with
        # the write itself (no post-write meta update to lose in a
        # crash window) — the notify chain enqueues the debt
        rs = getattr(self.s3, "replication_sys", None)
        if rs is not None and rs.heads_up(self.bucket, self.key) is not None:
            from ..bucket import replicate as repl
            user_defined[repl.META_REP_STATUS] = repl.PENDING
        opts.user_defined = user_defined
        oi = self.s3.obj.put_object(self.bucket, self.key, stream, put_size,
                                    opts)
        if stream is not hr:
            # everything downstream (response, event records) speaks
            # plaintext sizes; the stored (encrypted/compressed) length is
            # an internal detail
            oi.size = size
        self._send(200, headers={
            "ETag": f'"{oi.etag}"',
            "x-amz-version-id": oi.version_id or None,
            **sse_resp})
        self._notify("s3:ObjectCreated:Put", oi)

    def _sse_new_key(self, sse, user_defined: dict):
        """Envelope setup for a new encrypted object or multipart upload
        (cmd/encryption-v1.go EncryptRequest / newEncryptMetadata): random
        OEK sealed under the request key (SSE-C) or a KMS data key
        (SSE-S3, SSE-KMS); ``user_defined`` records everything a reader
        needs except the secret itself. Returns (OEK, base IV, package
        cipher, response headers)."""
        import base64
        import secrets

        from ..crypto import get_kms, seal_object_key, sse_kms_context
        from ..crypto.sse import (META_CIPHER, META_IV, META_KEY_MD5,
                                  META_KMS_BLOB, META_KMS_CONTEXT,
                                  META_KMS_KEY_ID, META_SCHEME, META_SEALED,
                                  default_cipher)
        oek = secrets.token_bytes(32)
        base_iv = secrets.token_bytes(12)
        cipher = default_cipher()
        user_defined[META_SCHEME] = sse.scheme
        user_defined[META_IV] = base64.b64encode(base_iv).decode()
        user_defined[META_CIPHER] = cipher
        if sse.scheme == "C":
            sealed = seal_object_key(oek, sse.key, self.bucket, self.key,
                                     cipher=cipher)
            user_defined[META_KEY_MD5] = sse.key_md5
            resp = {
                "x-amz-server-side-encryption-customer-algorithm": "AES256",
                "x-amz-server-side-encryption-customer-key-MD5":
                    sse.key_md5}
        elif sse.scheme == "KMS":
            kms = get_kms()
            key_id = sse.kms_key_id or kms.key_id
            ctx = sse_kms_context(self.bucket, self.key, sse.kms_context)
            dk, blob = self._kms_generate(kms, ctx, key_id)
            sealed = seal_object_key(oek, dk, self.bucket, self.key,
                                     cipher=cipher)
            user_defined[META_KMS_BLOB] = base64.b64encode(blob).decode()
            user_defined[META_KMS_KEY_ID] = key_id
            if sse.kms_context:
                user_defined[META_KMS_CONTEXT] = base64.b64encode(
                    sse.kms_context.encode()).decode()
            resp = {"x-amz-server-side-encryption": "aws:kms",
                    "x-amz-server-side-encryption-aws-kms-key-id": key_id}
        else:
            kms = get_kms()
            dk, blob = self._kms_generate(kms, f"{self.bucket}/{self.key}")
            sealed = seal_object_key(oek, dk, self.bucket, self.key,
                                     cipher=cipher)
            user_defined[META_KMS_BLOB] = base64.b64encode(blob).decode()
            resp = {"x-amz-server-side-encryption": "AES256"}
        user_defined[META_SEALED] = base64.b64encode(sealed).decode()
        return oek, base_iv, cipher, resp

    def _encrypt_setup(self, sse, hr, size: int, user_defined: dict):
        """A single PUT's cipher stream: (stream, encrypted size, response
        headers)."""
        from ..crypto import EncryptReader, enc_size
        from ..crypto.sse import META_PLAIN_SIZE
        oek, base_iv, cipher, resp = self._sse_new_key(sse, user_defined)
        user_defined[META_PLAIN_SIZE] = str(size)
        return (EncryptReader(hr, oek, base_iv, cipher=cipher),
                enc_size(size), resp)

    def _kms_generate(self, kms, ctx: str, key_id: str = ""):
        """generate_key with a KMS outage surfaced as a retryable 503
        (matching the read path) instead of a generic 500."""
        from ..crypto import KMSUnreachable
        try:
            return kms.generate_key(ctx, key_id=key_id)
        except KMSUnreachable as e:
            raise dt.KMSNotAvailable(self.bucket, self.key,
                                     extra=str(e)) from None

    def _sse_unseal(self, internal: dict):
        """Unseal the OEK of an encrypted object or multipart upload from
        its internal metadata with this request's credentials: (OEK,
        response headers, package cipher), or None when the metadata
        names no scheme. SSE-C requires the customer key headers
        (matching fingerprint: a wrong key MD5 403s BEFORE any package is
        read or opened), SSE-S3 and SSE-KMS unseal via the KMS
        (cmd/encryption-v1.go DecryptRequest)."""
        from ..crypto.sse import META_SCHEME
        scheme = internal.get(META_SCHEME, "")
        if not scheme:
            return None
        import base64

        from ..crypto import (get_kms, parse_sse_headers, sse_kms_context,
                              unseal_object_key)
        from ..crypto.sse import (META_KEY_MD5, META_KMS_BLOB,
                                  META_KMS_CONTEXT, META_KMS_KEY_ID,
                                  META_SEALED, cipher_of)
        sealed = base64.b64decode(internal.get(META_SEALED, ""))
        cipher = cipher_of(internal)
        if scheme == "C":
            req = parse_sse_headers(self.hdr, self.bucket, self.key)
            if req is None or req.scheme != "C":
                raise dt.SSEEncryptedObject(self.bucket, self.key)
            if req.key_md5 != internal.get(META_KEY_MD5, ""):
                raise dt.SSEKeyMismatch(self.bucket, self.key)
            oek = unseal_object_key(sealed, req.key, self.bucket, self.key,
                                    cipher=cipher)
            resp = {
                "x-amz-server-side-encryption-customer-algorithm": "AES256",
                "x-amz-server-side-encryption-customer-key-MD5":
                    req.key_md5}
            return oek, resp, cipher
        blob = base64.b64decode(internal.get(META_KMS_BLOB, ""))
        if scheme == "KMS":
            key_id = internal.get(META_KMS_KEY_ID, "")
            stored_ctx = ""
            if internal.get(META_KMS_CONTEXT, ""):
                stored_ctx = base64.b64decode(
                    internal[META_KMS_CONTEXT]).decode()
            ctx = sse_kms_context(self.bucket, self.key, stored_ctx)
            resp = {"x-amz-server-side-encryption": "aws:kms",
                    "x-amz-server-side-encryption-aws-kms-key-id": key_id}
        else:
            key_id, ctx = "", f"{self.bucket}/{self.key}"
            resp = {"x-amz-server-side-encryption": "AES256"}
        from ..crypto import KMSUnreachable
        try:
            dk = get_kms().unseal(blob, ctx, key_id=key_id)
        except KMSUnreachable as e:
            # transient KMS outage — not a wrong-key condition
            raise dt.KMSNotAvailable(self.bucket, self.key,
                                     extra=str(e)) from None
        except Exception:  # noqa: BLE001 — rotated/deleted master key
            raise dt.SSEKeyMismatch(self.bucket, self.key) from None
        oek = unseal_object_key(sealed, dk, self.bucket, self.key,
                                cipher=cipher)
        return oek, resp, cipher

    def _sse_read_ctx(self, oi):
        """For an encrypted object: its package streams under the OEK this
        request unsealed (``crypto.SSERead``: one stream for a single PUT,
        one per part for a multipart upload, docs/sse.md), the plaintext
        size and the response headers; None for plaintext objects."""
        unsealed = self._sse_unseal(oi.internal)
        if unsealed is None:
            return None
        import base64

        from ..crypto import SSERead, plain_size_of
        from ..crypto.sse import (META_IV, META_MULTIPART, PartStream,
                                  part_streams)
        oek, resp, cipher = unsealed
        if oi.internal.get(META_MULTIPART):
            streams = part_streams(oek, oi.parts, self.bucket, self.key)
            plain_size = sum(s.plain for s in streams)
        else:
            plain_size = plain_size_of(oi.internal, oi.size)
            streams = (PartStream(oek, base64.b64decode(
                oi.internal.get(META_IV, "")), plain_size),)
        return SSERead(streams, plain_size, resp, cipher)

    def _sse_write(self, sse, sink, offset: int, length: int, body):
        """Decrypt the plaintext range [offset, offset+length) of the
        object ``body`` holds (``get_object_n_info``'s handle for
        ``self.bucket/self.key``) into ``sink``: only the stored bytes
        that cover it are read, every package is verified before
        release."""
        from ..crypto import RangeDecryptWriter, plan_range
        enc_off, enc_len, segs = plan_range(sse.streams, offset, length)
        dw = RangeDecryptWriter(sink, segs, sse.cipher, self.bucket,
                                self.key)
        if enc_len > 0:
            body.read(dw, enc_off, enc_len)
        dw.finish()

    def _write_plain(self, body, oi, sse, sink, offset: int = 0,
                     length: int = -1):
        """The plaintext range [offset, offset+length) (-1: to the end) of
        the object ``get_object_n_info`` returned as ``oi`` and ``body``
        into ``sink``: opened if it is encrypted (``sse`` from
        ``_sse_read_ctx``), inflated if it is compressed."""
        from ..utils import compress as cz
        compressed = oi.internal.get(cz.META_COMPRESSION, "")
        if sse:
            if length < 0:
                length = sse.plain_size - offset
            self._sse_write(sse, sink, offset, length, body)
        elif compressed:
            # inflate the whole stored stream, trim to the requested
            # plaintext range (reference compressed-range behavior)
            dz = cz.decompress_writer(compressed, sink, skip=offset,
                                      limit=length)
            body.read(dz)
            dz.finish()
        else:
            body.read(sink, offset, length)

    def _hash_reader(self, size: int) -> HashReader:
        """Body reader verifying Content-MD5 / x-amz-content-sha256 on the
        fly — shared by PutObject and UploadPart so the two paths can't
        diverge."""
        sha = self.hdr.get("x-amz-content-sha256", "")
        sha_hex = sha if sha and sha not in (
            UNSIGNED_PAYLOAD, STREAMING_PAYLOAD) else ""
        md5_b64 = self.hdr.get("content-md5", "")
        md5_hex = ""
        if md5_b64:
            import base64
            import binascii
            try:
                decoded = base64.b64decode(md5_b64, validate=True)
            except (binascii.Error, ValueError) as e:
                raise dt.InvalidDigest(self.bucket, self.key) from e
            if len(decoded) != 16:
                raise dt.InvalidDigest(self.bucket, self.key)
            md5_hex = decoded.hex()
        return HashReader(self._body_stream(size), size, md5_hex, sha_hex)

    def _user_meta(self) -> dict[str, str]:
        out = {}
        ct = self.hdr.get("content-type")
        if not ct and self.key:
            # extension-based detection via the curated mimedb table
            # (reference pkg/mimedb; deterministic across containers,
            # stdlib mimetypes as fallback for exotic extensions)
            from ..utils.mimedb import content_type
            ct = content_type(self.key)
        if ct:
            out["content-type"] = ct
        for k, v in self.hdr.items():
            if k.startswith("x-amz-meta-"):
                out[k] = v
        for k in ("cache-control", "content-disposition",
                  "content-encoding", "content-language", "expires"):
            if k in self.hdr:
                out[k] = self.hdr[k]
        return out

    def _notify(self, event, oi):
        if self.s3.notify is not None:
            self.s3.notify(event, self.bucket, oi)

    def _obj_headers(self, oi) -> dict:
        h = {
            "ETag": f'"{oi.etag}"',
            "Last-Modified": xu.http_date(oi.mod_time),
            "Content-Type": oi.content_type or "application/octet-stream",
            "Accept-Ranges": "bytes",
            "x-amz-version-id": oi.version_id or None,
        }
        for k, v in oi.user_defined.items():
            if k.startswith("x-amz-meta-") or \
                    k.startswith("x-amz-object-lock-") or k in (
                    "cache-control", "content-disposition",
                    "content-encoding", "content-language", "expires"):
                h[k] = v
        return h

    def _parse_range(self, total: int):
        rng = self.hdr.get("range", "")
        if not rng.startswith("bytes="):
            return None
        spec = rng[len("bytes="):].split(",")[0].strip()
        start_s, _, end_s = spec.partition("-")
        try:
            if start_s == "":
                n = int(end_s)
                if n == 0:
                    raise dt.InvalidRange(self.bucket, self.key)
                start, end = max(0, total - n), total - 1
            else:
                start = int(start_s)
                end = int(end_s) if end_s else total - 1
        except ValueError:
            return None
        if start >= total or end < start:
            raise dt.InvalidRange(self.bucket, self.key)
        return start, min(end, total - 1)

    def get_object(self, ak):
        self._authorize(ak, "s3:GetObject")
        try:
            # one quorum metadata pass: the headers below and the body
            # behind them are the same version (reference GetObjectNInfo)
            oi, body = self.s3.obj.get_object_n_info(self.bucket, self.key,
                                                     self._opts())
        except dt.ObjectNotFound:
            # replication proxy: serve from the bucket's remote target
            # when the object hasn't replicated back yet
            pool = getattr(self.s3, "replication", None)
            res = pool.proxy_get(self.bucket, self.key,
                                 self.hdr.get("range", "")) \
                if pool is not None else None
            if res is None:
                raise
            status, chunks, hdrs, clen = res
            self.send_response(status)
            for k, v in hdrs.items():
                self.send_header(k, v)
            self.send_header("Content-Length", str(clen))
            self.send_header("x-minio-proxied-from-target", "true")
            self.end_headers()
            for chunk in chunks:  # streams: never fully resident
                if chunk:
                    self.wfile.write(chunk)
            return
        self._check_preconditions(oi)
        from ..bucket import transition as tx
        if tx.is_transitioned(oi) and oi.size == 0:
            # stub: read through from the tier (cmd/bucket-lifecycle.go
            # getTransitionedObjectReader)
            return self._get_transitioned(oi)
        sse = self._sse_read_ctx(oi)
        from ..utils import compress as cz
        compressed = oi.internal.get(cz.META_COMPRESSION, "")
        logical_size = sse.plain_size if sse else (
            oi.actual_size if compressed else oi.size)
        rng = self._parse_range(logical_size) if logical_size > 0 else None
        headers = self._obj_headers(oi)
        if sse:
            headers.update(sse.resp)
        if rng is None:
            offset, length = 0, logical_size
            status = 200
        else:
            offset, length = rng[0], rng[1] - rng[0] + 1
            status = 206
            headers["Content-Range"] = \
                f"bytes {rng[0]}-{rng[1]}/{logical_size}"
        headers["Content-Length"] = str(length)
        self._respond_head(status, headers)
        if length > 0:
            self._write_plain(body, oi, sse, self.wfile, offset, length)
        self._notify("s3:ObjectAccessed:Get", oi)

    def _get_transitioned(self, oi):
        from ..bucket import transition as tx
        try:
            data = self.s3.transition.read(oi)
        except Exception:  # noqa: BLE001 — tier unreachable
            return self._error("InvalidObjectState",
                               "transitioned object's tier unavailable",
                               403)
        rng = self._parse_range(len(data)) if data else None
        headers = self._obj_headers(oi)
        headers["x-amz-storage-class"] = oi.internal.get(tx.META_TIER, "")
        if rng is None:
            body, status = data, 200
        else:
            body, status = data[rng[0]:rng[1] + 1], 206
            headers["Content-Range"] = \
                f"bytes {rng[0]}-{rng[1]}/{len(data)}"
        headers["Content-Length"] = str(len(body))
        self._respond_head(status, headers)
        self.wfile.write(body)
        self._notify("s3:ObjectAccessed:Get", oi)

    def restore_object(self, ak):
        """POST ?restore (reference PostRestoreObjectHandler): bring a
        transitioned object's bytes back locally for Days days."""
        self._authorize(ak, "s3:RestoreObject")
        days = 1
        body = self._read_body()
        if body.strip():
            try:
                root = ET.fromstring(body)
                from ..bucket.objectlock import findtext
                days = int(findtext(root, "Days") or "1")
            except ET.ParseError as e:
                return self._error("MalformedXML", str(e), 400)
        oi = self.s3.obj.get_object_info(self.bucket, self.key,
                                         self._opts())
        from ..bucket import transition as tx
        if not tx.is_transitioned(oi):
            return self._error("InvalidObjectState",
                               "object is not archived", 403)
        if oi.size > 0 and tx.is_restored(oi):
            # already restored: just extend the expiry, no tier fetch
            self.s3.transition.extend_restore(self.bucket, oi, days)
        else:
            self.s3.transition.restore(self.bucket, oi, days)
        self._send(202)

    def head_object(self, ak):
        self._authorize(ak, "s3:GetObject")
        oi = self.s3.obj.get_object_info(self.bucket, self.key, self._opts())
        self._check_preconditions(oi)
        from ..bucket import transition as tx
        if tx.is_transitioned(oi):
            h = self._obj_headers(oi)
            h["Content-Length"] = str(tx.transitioned_size(oi))
            h["x-amz-storage-class"] = oi.internal.get(tx.META_TIER, "")
            if oi.size > 0 and tx.is_restored(oi):
                h["x-amz-restore"] = 'ongoing-request="false"'
            return self._respond_head(200, h)
        sse = self._sse_read_ctx(oi)
        h = self._obj_headers(oi)
        if sse:
            h.update(sse.resp)
            h["Content-Length"] = str(sse.plain_size)
        else:
            from ..utils import compress as cz
            h["Content-Length"] = str(
                oi.actual_size if oi.internal.get(cz.META_COMPRESSION)
                else oi.size)
        self._respond_head(200, h)

    def _respond_head(self, status: int, headers: dict) -> None:
        """Status line and headers of a reply whose body, if any, is
        streamed after them (stage ``respond``, as ``_send``)."""
        with _stages.stage("respond"):
            self.send_response(status)
            for k, v in headers.items():
                if v:
                    self.send_header(k, v)
            self.end_headers()

    def _check_preconditions(self, oi):
        inm = self.hdr.get("if-none-match", "")
        if inm and inm.strip('"') == oi.etag:
            raise dt.NotModified(self.bucket, self.key)
        im = self.hdr.get("if-match", "")
        if im and im.strip('"') != oi.etag:
            raise dt.PreconditionFailed(self.bucket, self.key)

    # --- object lock / retention / legal hold -------------------------------

    def put_object_lock_config(self, ak):
        self._authorize(ak, "s3:PutBucketObjectLockConfiguration")
        from ..bucket import objectlock as ol
        meta = self.s3.bucket_meta.get(self.bucket)
        if not meta.object_lock_enabled:
            raise dt.InvalidRequest(
                self.bucket, "",
                "object lock is not enabled on this bucket")
        body = self._read_body()
        try:
            ol.parse_lock_config(body)
        except (ET.ParseError, ValueError) as e:
            return self._error("MalformedXML", str(e), 400)
        self.s3.bucket_meta.update(self.bucket, object_lock_xml=body)
        self._send(200)

    def get_object_lock_config(self, ak):
        self._authorize(ak, "s3:GetBucketObjectLockConfiguration")
        from ..bucket import objectlock as ol
        meta = self.s3.bucket_meta.get(self.bucket)
        if not meta.object_lock_enabled:
            return self._error("ObjectLockConfigurationNotFoundError",
                               "object lock is not enabled", 404)
        dr = ol.DefaultRetention()
        if meta.object_lock_xml:
            dr = ol.parse_lock_config(meta.object_lock_xml)
        self._send(200, ol.lock_config_xml(True, dr))

    def _lock_ctx(self):
        from ..bucket import objectlock as ol
        meta = self.s3.bucket_meta.get(self.bucket)
        default = ol.DefaultRetention()
        if meta.object_lock_enabled and meta.object_lock_xml:
            try:
                default = ol.parse_lock_config(meta.object_lock_xml)
            except ValueError:
                pass
        return meta.object_lock_enabled, default

    def put_object_retention(self, ak):
        self._authorize(ak, "s3:PutObjectRetention")
        from ..bucket import objectlock as ol
        enabled, _ = self._lock_ctx()
        if not enabled:
            raise dt.InvalidRequest(self.bucket, self.key,
                                    "bucket has no object lock")
        try:
            root = ET.fromstring(self._read_body())
        except ET.ParseError as e:
            return self._error("MalformedXML", str(e), 400)
        mode = ol.findtext(root, "Mode").upper()
        until = ol.findtext(root, "RetainUntilDate")
        if mode not in (ol.GOVERNANCE, ol.COMPLIANCE) or not until:
            raise dt.InvalidRequest(self.bucket, self.key,
                                    "invalid retention")
        try:
            until_t = ol.parse_iso8601(until)
        except ValueError:
            raise dt.InvalidRequest(self.bucket, self.key,
                                    "invalid retain-until date") from None
        opts = self._opts()
        oi = self.s3.obj.get_object_info(self.bucket, self.key, opts)
        cur = ol.retention_of({**oi.user_defined})
        bypass = self.hdr.get(
            "x-amz-bypass-governance-retention", "") == "true"
        if bypass:
            # weakening GOVERNANCE retention needs its own permission,
            # same as the delete path
            self._authorize(ak, "s3:BypassGovernanceRetention")
        cur_t = 0.0
        if cur.active:
            try:
                cur_t = ol.parse_iso8601(cur.retain_until)
            except ValueError:
                cur_t = 0.0
        if cur.active and cur.mode == ol.COMPLIANCE:
            # COMPLIANCE can only be extended, never weakened
            if mode != ol.COMPLIANCE or until_t < cur_t:
                raise dt.ObjectLocked(self.bucket, self.key,
                                      "COMPLIANCE retention active")
        elif cur.active and cur.mode == ol.GOVERNANCE and not bypass:
            if until_t < cur_t:
                raise dt.ObjectLocked(self.bucket, self.key,
                                      "GOVERNANCE retention active")
        self._mutate_lock_meta(opts, {ol.META_MODE: mode,
                                      ol.META_RETAIN_UNTIL: until})
        self._send(200)

    def get_object_retention(self, ak):
        self._authorize(ak, "s3:GetObjectRetention")
        from ..bucket import objectlock as ol
        oi = self.s3.obj.get_object_info(self.bucket, self.key, self._opts())
        ret = ol.retention_of(oi.user_defined)
        if not ret.mode:
            return self._error("NoSuchObjectLockConfiguration",
                               "no retention on this object", 404)
        self._send(200, (f"<Retention><Mode>{ret.mode}</Mode>"
                         f"<RetainUntilDate>{ret.retain_until}"
                         f"</RetainUntilDate></Retention>").encode())

    def put_object_legal_hold(self, ak):
        self._authorize(ak, "s3:PutObjectLegalHold")
        from ..bucket import objectlock as ol
        enabled, _ = self._lock_ctx()
        if not enabled:
            raise dt.InvalidRequest(self.bucket, self.key,
                                    "bucket has no object lock")
        try:
            root = ET.fromstring(self._read_body())
        except ET.ParseError as e:
            return self._error("MalformedXML", str(e), 400)
        status = ol.findtext(root, "Status").upper()
        if status not in ("ON", "OFF"):
            raise dt.InvalidRequest(self.bucket, self.key,
                                    "invalid legal hold status")
        self._mutate_lock_meta(self._opts(), {ol.META_LEGAL_HOLD: status})
        self._send(200)

    def get_object_legal_hold(self, ak):
        self._authorize(ak, "s3:GetObjectLegalHold")
        from ..bucket import objectlock as ol
        oi = self.s3.obj.get_object_info(self.bucket, self.key, self._opts())
        status = ol.legal_hold_of(oi.user_defined)
        self._send(200,
                   f"<LegalHold><Status>{status}</Status></LegalHold>"
                   .encode())

    def _mutate_lock_meta(self, opts, updates: dict):
        """Merge object-lock keys into the version's metadata in place
        (the reference rewrites xl.meta the same way for retention)."""
        self.s3.obj.update_object_meta(self.bucket, self.key, updates, opts)

    def _check_quota(self, incoming: int):
        """Hard bucket quota from the data-usage snapshot
        (cmd/bucket-quota.go enforceBucketQuotaHard): best-effort like the
        reference — usage trails the scanner's last sweep. The snapshot is
        cached on the server with a short TTL so the hot write path
        doesn't re-read+parse the usage blob per request."""
        import time as _t
        meta = self.s3.bucket_meta.get(self.bucket)
        if meta.quota <= 0:
            return
        cached = getattr(self.s3, "_usage_cache", None)
        if cached is None or _t.monotonic() - cached[0] > 10.0:
            from ..scanner import usage as usage_mod
            cached = (_t.monotonic(), usage_mod.load_usage(self.s3.obj))
            self.s3._usage_cache = cached
        usage = cached[1]
        used = usage.get("buckets", {}).get(self.bucket, {}).get("size", 0)
        if used + max(incoming, 0) > meta.quota:
            raise dt.QuotaExceeded(
                self.bucket, self.key,
                f"quota {meta.quota} would be exceeded")

    def _check_delete_lock(self, opts, key: str | None = None):
        """WORM enforcement for version deletes (a versionless delete only
        writes a delete marker, which object lock permits)."""
        if not opts.version_id:
            return
        from ..bucket import objectlock as ol
        meta = self.s3.bucket_meta.get(self.bucket)
        if not meta.object_lock_enabled:
            return
        key = self.key if key is None else key
        try:
            oi = self.s3.obj.get_object_info(self.bucket, key, opts)
        except dt.ObjectAPIError:
            return  # nothing to protect
        bypass = self.hdr.get(
            "x-amz-bypass-governance-retention", "") == "true"
        if bypass:
            # bypass needs its own permission
            self._authorize(self._last_ak,
                            "s3:BypassGovernanceRetention", self.bucket,
                            key)
        ol.check_delete_allowed(oi.user_defined, self.bucket, key, bypass)

    def delete_object(self, ak):
        self._authorize(ak, "s3:DeleteObject")
        self._last_ak = ak
        opts = self._opts()
        self._check_delete_lock(opts)
        oi = self.s3.obj.delete_object(self.bucket, self.key, opts)
        self._send(204, headers={
            "x-amz-version-id": oi.version_id or None,
            "x-amz-delete-marker": "true" if oi.delete_marker else None})
        self._notify("s3:ObjectRemoved:Delete", oi)

    def copy_object(self, ak):
        self._authorize(ak, "s3:PutObject")
        src = urllib.parse.unquote(self.hdr["x-amz-copy-source"])
        src_vid = ""
        if "?versionId=" in src:
            src, _, src_vid = src.partition("?versionId=")
        src = src.lstrip("/")
        src_bucket, _, src_key = src.partition("/")
        # the caller must be allowed to READ the source, not just write the
        # destination (otherwise copy exfiltrates unreadable objects)
        self._authorize(ak, "s3:GetObject", src_bucket, src_key)
        src_opts = ObjectOptions(version_id=src_vid)
        # SSE copy (decrypt source / re-encrypt destination) is not wired
        # yet; refuse clearly instead of copying ciphertext as plaintext
        from ..crypto.sse import META_SCHEME
        si_probe = self.s3.obj.get_object_info(src_bucket, src_key, src_opts)
        if si_probe.internal.get(META_SCHEME) or \
                self.hdr.get("x-amz-server-side-encryption") or \
                self.hdr.get(
                    "x-amz-server-side-encryption-customer-algorithm"):
            raise dt.NotImplemented(self.bucket, self.key)
        self._check_quota(si_probe.size)  # destination bucket quota
        dst_opts = self._opts()
        # object lock applies to the new version exactly like a PUT:
        # request headers validated, else the bucket default
        from ..bucket import objectlock as olock
        lock_enabled, lock_default = self._lock_ctx()
        lock_meta = olock.check_put_headers(
            self.hdr, self.bucket, self.key, lock_enabled, lock_default)
        directive = self.hdr.get("x-amz-metadata-directive", "COPY")
        if directive == "REPLACE":
            dst_opts.user_defined = self._user_meta()
            dst_opts.metadata_replace = True
        else:
            dst_opts.user_defined = dict(si_probe.user_defined)
            if si_probe.content_type:
                dst_opts.user_defined["content-type"] = si_probe.content_type
        # the copy moves the STORED bytes, so the compression markers must
        # travel with them or the destination would serve raw deflate
        from ..utils import compress as cz
        for k in (cz.META_COMPRESSION, cz.META_ACTUAL_SIZE):
            if k in si_probe.internal:
                dst_opts.user_defined[k] = si_probe.internal[k]
        dst_opts.user_defined.update(lock_meta)
        oi = self.s3.obj.copy_object(src_bucket, src_key, self.bucket,
                                     self.key, None, src_opts, dst_opts)
        self._send(200, xu.copy_object_xml(oi.etag, oi.mod_time),
                   headers={"x-amz-version-id": oi.version_id or None})
        self._notify("s3:ObjectCreated:Copy", oi)

    # --- object tagging -----------------------------------------------------

    def put_object_tagging(self, ak):
        self._authorize(ak, "s3:PutObjectTagging")
        tags = xu.parse_tagging(self._read_body())
        self.s3.obj.put_object_tags(self.bucket, self.key,
                                    urllib.parse.urlencode(tags),
                                    self._opts())
        self._send(200)

    def get_object_tagging(self, ak):
        self._authorize(ak, "s3:GetObjectTagging")
        enc = self.s3.obj.get_object_tags(self.bucket, self.key,
                                          self._opts())
        self._send(200, xu.tagging_xml(dict(urllib.parse.parse_qsl(enc))))

    def delete_object_tagging(self, ak):
        self._authorize(ak, "s3:PutObjectTagging")
        self.s3.obj.delete_object_tags(self.bucket, self.key, self._opts())
        self._send(204)

    # --- multipart ----------------------------------------------------------

    def initiate_upload(self, ak):
        self._authorize(ak, "s3:PutObject")
        opts = self._opts()
        opts.user_defined = self._user_meta()
        from ..crypto import parse_sse_headers
        sse = parse_sse_headers(self.hdr, self.bucket, self.key)
        sse_resp = {}
        if sse is not None:
            # the OEK is made and sealed now and kept in the upload's own
            # metadata; every part is sealed under a key derived from it
            # (docs/sse.md). A backend that cannot hand that metadata
            # back to put_part must refuse: a request for SSE is never
            # answered by storing plaintext parts
            if not hasattr(self.s3.obj, "get_multipart_info"):
                raise dt.NotImplemented(self.bucket, self.key)
            from ..crypto.sse import META_MULTIPART
            _, _, _, sse_resp = self._sse_new_key(sse, opts.user_defined)
            opts.user_defined[META_MULTIPART] = "1"
        uid = self.s3.obj.new_multipart_upload(self.bucket, self.key, opts)
        self._send(200, xu.initiate_multipart_xml(self.bucket, self.key, uid),
                   headers=sse_resp)

    def _upload_info(self, uid: str):
        """The upload's record as CreateMultipartUpload stored it
        (``user_defined``: its metadata, internal keys included), None on
        a backend that keeps none (it refused SSE at initiate)."""
        info = getattr(self.s3.obj, "get_multipart_info", None)
        return info(self.bucket, self.key, uid) if info is not None else None

    def put_part(self, ak):
        self._authorize(ak, "s3:PutObject")
        if "x-amz-copy-source" in self.hdr:
            # UploadPartCopy is not wired (ROADMAP A6): the request has no
            # body, and storing that empty body as the part would be
            # silent wrong data
            raise dt.NotImplemented(self.bucket, self.key)
        part_id = int(self.q("partNumber"))
        uid = self.q("uploadId")
        size = int(self.hdr.get("content-length", "-1") or "-1")
        if self.hdr.get("x-amz-content-sha256", "") == STREAMING_PAYLOAD:
            size = int(self.hdr.get("x-amz-decoded-content-length",
                                    str(size)))
        if size < 0:
            return self._error("MissingContentLength",
                               "Content-Length required", 411)
        self._check_quota(size)  # quota applies to multipart traffic too
        # Verify Content-MD5 / x-amz-content-sha256 on part bodies exactly
        # like PutObject — otherwise corrupted parts are accepted and only
        # surface as a confusing InvalidPart at complete time.
        hr = self._hash_reader(size)
        stream, put_size, opts, sse_resp = hr, size, None, {}
        from ..crypto.sse import META_SCHEME
        # the one quorum metadata pass of this part: the object layer
        # writes the part from what `upload` holds
        upload = self._upload_info(uid)
        internal = upload.user_defined if upload is not None else {}
        scheme = internal.get(META_SCHEME, "")
        if scheme:
            stream, put_size, opts, sse_resp = self._encrypt_part(
                internal, part_id, hr, size)
        elif self.hdr.get(
                "x-amz-server-side-encryption-customer-algorithm"):
            raise dt.InvalidRequest(
                self.bucket, self.key,
                "the upload was not initiated with SSE-C")
        held = {} if upload is None else {"upload": upload}
        pi = self.s3.obj.put_object_part(self.bucket, self.key, uid,
                                         part_id, stream, put_size, opts,
                                         **held)
        from ..obs import metrics as mx
        mx.inc("minio_tpu_multipart_parts_total", sse=scheme or "none")
        self._send(200, headers={"ETag": f'"{pi.etag}"', **sse_resp})

    def _encrypt_part(self, internal: dict, part_id: int, hr, size: int):
        """One part of an encrypted upload (reference PutObjectPartHandler):
        the OEK unsealed with this request's credentials, the part's key
        derived from it and the part number, a fresh IV for this request
        (a part number uploaded twice never repeats a nonce under its
        key). Returns (stream, stored size, options, response headers)."""
        import base64
        import secrets

        from ..crypto import EncryptReader, derive_part_key, enc_size
        from ..crypto.sse import PART_IV, PART_NUMBER
        oek, resp, cipher = self._sse_unseal(internal)
        iv = secrets.token_bytes(12)
        # the part's ETag is of the stored stream; the plaintext digest is
        # kept only where the client sent one to verify
        hr.disable_payload_hash()
        stored = enc_size(size)
        stream = HashReader(
            EncryptReader(hr, derive_part_key(oek, part_id), iv,
                          cipher=cipher), stored, actual_size=size)
        opts = ObjectOptions(user_defined={
            PART_IV: base64.b64encode(iv).decode(),
            PART_NUMBER: str(part_id)})
        return stream, stored, opts, resp

    def list_parts(self, ak):
        self._authorize(ak, "s3:ListMultipartUploadParts")
        info = self.s3.obj.list_object_parts(
            self.bucket, self.key, self.q("uploadId"),
            int(self.q("part-number-marker", "0") or "0"),
            min(int(self.q("max-parts", "1000") or "1000"), 10_000))
        from ..crypto.sse import META_SCHEME
        upload = self._upload_info(self.q("uploadId"))
        if upload is not None and upload.user_defined.get(META_SCHEME):
            for p in info.parts:    # listings speak plaintext sizes
                p.size = p.actual_size
        self._send(200, xu.list_parts_xml(info))

    def list_uploads(self, ak):
        self._authorize(ak, "s3:ListBucketMultipartUploads")
        self.s3.obj.get_bucket_info(self.bucket)
        prefix = self.q("prefix")
        max_uploads = min(int(self.q("max-uploads", "1000") or "1000"),
                          10_000)
        info = self.s3.obj.list_multipart_uploads(self.bucket, prefix,
                                                  max_uploads)
        self._send(200, xu.list_uploads_xml(self.bucket, prefix, max_uploads,
                                            info))

    def abort_upload(self, ak):
        self._authorize(ak, "s3:AbortMultipartUpload")
        self.s3.obj.abort_multipart_upload(self.bucket, self.key,
                                           self.q("uploadId"))
        self._send(204)

    def complete_upload(self, ak):
        self._authorize(ak, "s3:PutObject")
        parts = xu.parse_complete_multipart(self._read_body())
        opts = self._opts()
        oi = self.s3.obj.complete_multipart_upload(
            self.bucket, self.key, self.q("uploadId"), parts, opts)
        from ..crypto.sse import META_MULTIPART, META_SCHEME
        from ..obs import metrics as mx
        mx.inc("minio_tpu_multipart_completes_total",
               sse=oi.internal.get(META_SCHEME, "") or "none")
        if oi.internal.get(META_MULTIPART):
            oi.size = oi.actual_size    # events speak plaintext sizes
        # multipart-complete is a replication charge point too; the
        # status rides a meta update since the parts were written long
        # before the obligation existed
        rs = getattr(self.s3, "replication_sys", None)
        if rs is not None and rs.heads_up(self.bucket, self.key) is not None:
            from ..bucket import replicate as repl
            try:
                self.s3.obj.update_object_meta(
                    self.bucket, self.key,
                    {repl.META_REP_STATUS: repl.PENDING})
            except Exception:  # noqa: BLE001 — charge still queues
                pass
        self._send(200, xu.complete_multipart_xml(
            f"{self.s3.endpoint()}/{self.bucket}/{self.key}",
            self.bucket, self.key, oi.etag),
            headers={"x-amz-version-id": oi.version_id or None})
        self._notify("s3:ObjectCreated:CompleteMultipartUpload", oi)


class _LenReader:
    """File-like with a known length: lets requests stream a proxied
    body at constant memory while still sending Content-Length."""

    def __init__(self, stream, size: int):
        self.stream = stream
        self._size = size

    def read(self, n: int = -1) -> bytes:
        return self.stream.read(n)

    def __len__(self):
        return self._size


class _CappedReader:
    """Bound a socket read to the declared Content-Length (socket streams
    never EOF on keep-alive connections); reports consumption back to the
    handler for end-of-request draining."""

    def __init__(self, raw, size: int, handler=None):
        self.raw = raw
        self.remaining = max(0, size) if size >= 0 else -1
        self.handler = handler

    def read(self, n: int = -1) -> bytes:
        if self.remaining == 0:
            return b""
        if self.remaining > 0:
            n = self.remaining if n < 0 else min(n, self.remaining)
        b = self.raw.read(n)
        if self.remaining > 0:
            self.remaining -= len(b)
        if self.handler is not None:
            self.handler._consumed += len(b)
        return b

    def readinto(self, view) -> int:
        """Zero-copy leg of the PUT ingest: the erasure pipeline's pooled
        block buffers reach the socket's BufferedReader directly, so body
        bytes are never materialized as per-block ``bytes`` objects."""
        if self.remaining == 0:
            return 0
        view = memoryview(view).cast("B")
        if 0 < self.remaining < len(view):
            view = view[: self.remaining]
        got = self.raw.readinto(view)
        got = got or 0
        if self.remaining > 0:
            self.remaining -= got
        if self.handler is not None:
            self.handler._consumed += got
        return got
