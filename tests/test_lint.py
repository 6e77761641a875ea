"""Tier-1 gate for graftlint (docs/static-analysis.md): the tree must
carry zero unbaselined findings, all checkers must be active, and
the suppression/baseline machinery must behave deterministically —
checked here against synthetic sources so a checker regression fails
loudly instead of silently passing a dirty tree."""
import ast
import json
import os
import sys
import textwrap

sys.path.insert(0, os.path.dirname(__file__))

from tools import graftlint  # noqa: E402
from tools.graftlint import checkers  # noqa: E402
from tools.graftlint.__main__ import main as lint_main  # noqa: E402


def ctx_for(src: str, path: str = "minio_tpu/_synthetic.py"):
    """FileCtx from inline source, bypassing the filesystem."""
    src = textwrap.dedent(src)
    tree = ast.parse(src)
    ctx = graftlint.FileCtx(path=path, abspath="/" + path, tree=tree,
                            lines=src.splitlines())
    ctx.scopes = graftlint._build_scopes(tree)
    return ctx


# --------------------------------------------------------------------------
# the gate


def test_tree_is_clean():
    """THE tier-1 gate: `python -m tools.graftlint minio_tpu` green.
    A new finding means either fix the site, pragma it with review
    sign-off, or deliberately add it to baseline.json — never ignore."""
    fresh, _old = graftlint.run(["minio_tpu"])
    assert not fresh, "unbaselined graftlint findings:\n" + "\n".join(
        f.render() for f in fresh)


def test_all_checkers_active():
    assert len(checkers.PER_FILE) + len(checkers.PROJECT) >= 10


def test_cli_clean_tree_exits_zero(capsys):
    # one clean subpackage, not the whole tree — test_tree_is_clean
    # already pays for the full pass; this asserts the CLI's exit-0
    # contract without a second one
    assert lint_main(["minio_tpu/obs"]) == 0


def test_cli_reports_findings_and_exits_one(tmp_path, capsys):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent("""
        import threading
        _lock = threading.Lock()
        def f():
            _lock.acquire()
            _lock.release()
    """))
    assert lint_main([str(p)]) == 1
    out = capsys.readouterr().out
    assert "GL003" in out and "bad.py" in out


# --------------------------------------------------------------------------
# per-checker positives / negatives


def test_gl001_wall_clock_duration_flagged():
    ctx = ctx_for("""
        import time
        def f():
            t0 = time.time()
            work()
            return time.time() - t0
    """)
    found = checkers.check_wall_duration(ctx)
    assert [f.checker for f in found] == ["GL001"]


def test_gl001_timestamps_and_monotonic_ok():
    ctx = ctx_for("""
        import time
        def stamp():
            return {"mtime": time.time()}   # timestamp: fine
        def dur():
            t0 = time.monotonic()
            return time.monotonic() - t0    # monotonic: fine
    """)
    assert not checkers.check_wall_duration(ctx)


def test_gl001_tracks_self_attr_dataflow():
    ctx = ctx_for("""
        import time
        class T:
            def start(self):
                self._t0 = time.time()
            def lap(self):
                return time.time() - self._t0
    """)
    assert len(checkers.check_wall_duration(ctx)) == 1


def test_gl002_blocking_under_lock_flagged():
    ctx = ctx_for("""
        import threading, time
        class T:
            def __init__(self):
                self._lock = threading.Lock()
            def f(self):
                with self._lock:
                    time.sleep(1)
    """)
    found = checkers.check_blocking_under_lock(ctx)
    assert len(found) == 1 and found[0].checker == "GL002"
    assert "time.sleep" in found[0].message


def test_gl002_cv_wait_on_held_condition_exempt():
    ctx = ctx_for("""
        import threading
        class T:
            def __init__(self):
                self._cv = threading.Condition()
            def f(self):
                with self._cv:
                    self._cv.wait()
    """)
    assert not checkers.check_blocking_under_lock(ctx)


def test_gl002_deferred_bodies_not_lock_scope():
    ctx = ctx_for("""
        import threading, time
        _lock = threading.Lock()
        def f():
            with _lock:
                def later():
                    time.sleep(1)   # runs after release — not a finding
                return later
    """)
    assert not checkers.check_blocking_under_lock(ctx)


def test_gl003_bare_acquire_flagged_with_ok():
    ctx = ctx_for("""
        import threading
        _lock = threading.Lock()
        def bad():
            _lock.acquire()
            try:
                pass
            finally:
                _lock.release()
        def good():
            with _lock:
                pass
    """)
    found = checkers.check_bare_acquire(ctx)
    assert {f.checker for f in found} == {"GL003"} and len(found) == 2


def test_gl004_undocumented_metric_flagged():
    ctx = ctx_for("""
        def f(store):
            store.inc("minio_tpu_totally_undocumented_total", 1)
    """)
    found = checkers.check_metrics_documented([ctx])
    assert len(found) == 1 and found[0].checker == "GL004"
    assert "minio_tpu_totally_undocumented_total" in found[0].message


def test_gl004_documented_metric_ok():
    ctx = ctx_for("""
        def f(store):
            store.inc("minio_tpu_dispatch_batches_total", 1)
    """)
    assert not checkers.check_metrics_documented([ctx])


def test_gl005_unwrapped_submit_flagged():
    ctx = ctx_for("""
        def fan_out(io_pool, fn):
            return io_pool.submit(fn, 1)
    """)
    found = checkers.check_submit_wrap(ctx)
    assert len(found) == 1 and found[0].checker == "GL005"


def test_gl005_wrap_ctx_forms_ok():
    ctx = ctx_for("""
        from minio_tpu.obs.spans import wrap_ctx
        def inline(io_pool, fn):
            return io_pool.submit(wrap_ctx(fn), 1)
        def bound(io_pool, fn):
            w = wrap_ctx(fn)
            return io_pool.submit(w, 1)
        def untraced(plain_executor, fn):
            return plain_executor.submit(fn)   # not a *pool* — out of scope
    """)
    assert not checkers.check_submit_wrap(ctx)


def test_gl006_storage_op_without_hook_flagged():
    ctx = ctx_for("""
        class XLStorage:
            def read_all(self, volume, path):
                return open(path).read()
            def stat_vol(self, volume):
                with self._op("statvol", volume):
                    return 1
    """, path="minio_tpu/storage/xlstorage.py")
    found = checkers.check_fault_hooks(ctx)
    assert [f.token for f in found] == ["read_all"]


def test_gl006_dispatch_unregistered_op_flagged():
    """ISSUE 8 extension: every op string submitted through _submit
    must be registered in _OP_NAME — the flush-boundary inject hook,
    kernel metrics and span naming all key on it."""
    ctx = ctx_for("""
        from .. import fault as _fault
        _OP_NAME = {"encode": "encode", "select_scan": "select_scan"}
        class DispatchQueue:
            def encode(self, codec, words):
                return self._submit(("k",), codec, "encode", words, None)
            def select_scan(self, words):
                return self._submit(("k",), None, "select_scan", words,
                                    None)
            def rogue_op(self, words):
                return self._submit(("k",), None, "mystery", words, None)
            def _flush(self, b, items):
                _fault.inject("kernel", "device", b.op)
    """, path="minio_tpu/runtime/dispatch.py")
    found = checkers.check_fault_hooks(ctx)
    assert [f.token for f in found] == ["mystery"]
    assert found[0].scope.endswith("rogue_op")


def test_gl006_dispatch_missing_inject_still_flagged():
    ctx = ctx_for("""
        _OP_NAME = {"encode": "encode"}
        class DispatchQueue:
            def encode(self, codec, words):
                return self._submit(("k",), codec, "encode", words, None)
    """, path="minio_tpu/runtime/dispatch.py")
    found = checkers.check_fault_hooks(ctx)
    assert [f.token for f in found] == ["kernel-flush"]


def test_gl007_bare_except_and_daemon_swallow():
    ctx = ctx_for("""
        import threading
        class Svc:
            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()
            def _loop(self):
                while True:
                    try:
                        step()
                    except Exception:
                        pass            # silent forever — finding
        def also_bad():
            try:
                step()
            except:                     # bare — finding anywhere
                pass
        def fine():
            try:
                step()
            except Exception as e:
                log(e)                  # handled — ok
    """)
    found = checkers.check_swallowed_exceptions(ctx)
    assert len(found) == 2
    assert {f.token for f in found} == {"swallow:_loop", "bare-except"}


def test_gl009_bare_replace_flagged():
    ctx = ctx_for("""
        import os
        def commit(tmp, dst):
            os.replace(tmp, dst)
        def legacy(a, b):
            os.rename(a, b)
    """)
    found = checkers.check_bare_replace(ctx)
    assert len(found) == 2
    assert all(f.checker == "GL009" for f in found)
    assert {f.scope for f in found} == {"commit", "legacy"}


def test_gl009_helper_module_and_foreign_paths_exempt():
    src = """
        import os
        def durable_replace(tmp, dst):
            os.replace(tmp, dst)
    """
    assert checkers.check_bare_replace(
        ctx_for(src, path="minio_tpu/storage/durability.py")) == []
    assert checkers.check_bare_replace(
        ctx_for(src, path="tools/somewhere.py")) == []


def test_gl010_host_hash_and_copies_flagged():
    ctx = ctx_for("""
        import hashlib
        def erasure_encode(stream, writers):
            h = hashlib.md5()
            def start_writes(shards):
                return shards[0].tobytes()
            def emit(x):
                return bytes(x), x.digest()
        def unrelated():
            return hashlib.sha256().hexdigest()
    """, path="minio_tpu/erasure/streaming.py")
    found = checkers.check_hot_path_host_copies(ctx)
    assert {f.checker for f in found} == {"GL010"}
    # md5() + tobytes() + bytes() + digest() inside the hot scope; the
    # module-level `unrelated` function is NOT registered -> not flagged
    assert len(found) == 4
    assert all(f.scope.startswith("erasure_encode") for f in found)


def test_gl010_sanctioned_fallback_and_foreign_paths_exempt():
    src = """
        import hashlib
        def erasure_encode(stream):
            def _plain_writes_fallback(shards):
                return hashlib.md5(shards[0].tobytes()).digest()
            return _plain_writes_fallback
    """
    assert checkers.check_hot_path_host_copies(
        ctx_for(src, path="minio_tpu/erasure/streaming.py")) == []
    # the same constructs in an unregistered module are free
    assert checkers.check_hot_path_host_copies(
        ctx_for(src.replace("erasure_encode", "whatever"),
                path="minio_tpu/erasure/bitrot.py")) == []


def test_gl010_workload_hot_paths_registered():
    """The device-workloads hot paths (ISSUE 8) are in the GL010
    registry: host hashing/copies inside them are findings."""
    ctx = ctx_for("""
        import hashlib
        class DecryptWriter:
            def write(self, b):
                return hashlib.md5(bytes(b)).digest()
        class EncryptReader:
            def readinto(self, buf):
                return self._chunks[0].tobytes()
    """, path="minio_tpu/crypto/sse.py")
    found = checkers.check_hot_path_host_copies(ctx)
    assert len(found) == 4  # md5() + bytes() + .digest() + .tobytes()
    assert {f.checker for f in found} == {"GL010"}
    ctx = ctx_for("""
        class DeviceScan:
            def rows(self):
                return bytes(self.data)
            def other(self):
                return bytes(self.data)   # unregistered — free
    """, path="minio_tpu/s3select/device.py")
    found = checkers.check_hot_path_host_copies(ctx)
    assert len(found) == 1
    assert found[0].scope == "DeviceScan.rows"


def test_gl011_flush_route_without_pairing_flagged():
    """ISSUE 9: every dispatch flush route must obtain the paired
    flight-recorder flush start/end callback from _tl_flush_cb."""
    ctx = ctx_for("""
        _OP_NAME = {"encode": "encode"}
        class DispatchQueue:
            def _tl_flush_cb(self, b, items, route, lanes=("cpu",)):
                _tl.record("flush_start", op=b.op)
                def done(_f):
                    _tl.record("flush_end", op=b.op)
                return done
            def _flush_cpu(self, b, items):
                tl_done = self._tl_flush_cb(b, items, "cpu")
            def _flush_device(self, b, items):
                pass   # no pairing call — finding
    """, path="minio_tpu/runtime/dispatch.py")
    found = checkers.check_timeline_flush_pairs(ctx)
    assert [f.token for f in found] == ["_flush_device"]
    assert all(f.checker == "GL011" for f in found)


def test_gl011_missing_helper_and_broken_pairing_flagged():
    # no helper at all
    ctx = ctx_for("""
        _OP_NAME = {"encode": "encode"}
        class DispatchQueue:
            def _flush_cpu(self, b, items):
                pass
    """, path="minio_tpu/runtime/dispatch.py")
    found = checkers.check_timeline_flush_pairs(ctx)
    assert "_tl_flush_cb" in {f.token for f in found}
    # helper present but emits only flush_start: pairing broken — and a
    # DOCSTRING naming both events must not mask the missing record()
    ctx = ctx_for('''
        _OP_NAME = {"encode": "encode"}
        class DispatchQueue:
            def _tl_flush_cb(self, b, items, route, lanes=("cpu",)):
                """Paired flush_start/flush_end events for GL011."""
                _tl.record("flush_start", op=b.op)
            def _flush_cpu(self, b, items):
                tl_done = self._tl_flush_cb(b, items, "cpu")
    ''', path="minio_tpu/runtime/dispatch.py")
    found = checkers.check_timeline_flush_pairs(ctx)
    assert [f.token for f in found] == ["_tl_flush_cb:flush_end"]


def test_gl011_paired_routes_and_foreign_paths_ok():
    src = """
        _OP_NAME = {"encode": "encode", "sse_xor": "sse_xor"}
        class DispatchQueue:
            def _tl_flush_cb(self, b, items, route, lanes=("cpu",)):
                _tl.record("flush_start", op=b.op)
                def done(_f):
                    _tl.record("flush_end", op=b.op)
                return done
            def _flush_cpu(self, b, items):
                tl_done = self._tl_flush_cb(b, items, "cpu")
            def _flush_device(self, b, items):
                tl_done = self._tl_flush_cb(b, items, "device",
                                            self._device_lanes())
    """
    assert not checkers.check_timeline_flush_pairs(
        ctx_for(src, path="minio_tpu/runtime/dispatch.py"))
    # the same shapes anywhere else are out of scope
    assert not checkers.check_timeline_flush_pairs(
        ctx_for("def _flush_cpu(): pass",
                path="minio_tpu/runtime/other.py"))


def test_gl004_wrapper_fed_metric_literals_seen():
    """GL004 recognizes families fed through the obs-shielded
    _metric/_workload wrappers the workload paths use."""
    ctx = ctx_for("""
        def scan():
            _metric("minio_tpu_fake_family_total", route="x")
    """)
    fams = [f for f, _ in checkers._metric_literals(ctx)]
    assert "minio_tpu_fake_family_total" in fams


def test_gl008_undocumented_dynamic_key_flagged():
    ctx = ctx_for("""
        SUB_SYSTEMS = {
            "scanner": {"nonexistent_knob_xyz": KV("1")},
        }
        DYNAMIC = {"scanner"}
    """, path="minio_tpu/config/kvs.py")
    found = checkers.check_config_keys_documented(ctx)
    assert len(found) == 1
    assert found[0].token == "scanner.nonexistent_knob_xyz"


# --------------------------------------------------------------------------
# suppression: pragma + baseline


def test_inline_pragma_suppresses(tmp_path):
    src = textwrap.dedent("""
        import threading
        _lock = threading.Lock()
        def f():
            _lock.acquire()  # graftlint: disable=GL003
            # graftlint: disable=GL003
            _lock.release()
    """)
    p = tmp_path / "pragma.py"
    p.write_text(src)
    fresh, old = graftlint.run([str(p)], use_baseline=False)
    assert not fresh and not old
    # and only the named checker is suppressed
    p.write_text(src.replace("GL003", "GL001"))
    fresh, _ = graftlint.run([str(p)], use_baseline=False)
    assert len(fresh) == 2


def test_finding_keys_are_line_stable():
    """Baseline identity must survive edits ABOVE the site."""
    src = """
        import threading
        _lock = threading.Lock()
        def f():
            _lock.acquire()
    """
    k1 = checkers.check_bare_acquire(ctx_for(src))[0].key
    k2 = checkers.check_bare_acquire(
        ctx_for("\n\n# shifted\n" + textwrap.dedent(src)))[0].key
    assert k1 == k2


def test_baseline_roundtrip_deterministic(tmp_path):
    ctx = ctx_for("""
        import threading
        _lock = threading.Lock()
        def f():
            _lock.acquire()
            _lock.release()
    """)
    findings = checkers.check_bare_acquire(ctx)
    bp = tmp_path / "baseline.json"
    graftlint.write_baseline(findings, path=str(bp))
    first = bp.read_bytes()
    graftlint.write_baseline(list(reversed(findings)), path=str(bp))
    assert bp.read_bytes() == first, "baseline output is order-dependent"
    doc = json.loads(first)
    keys = [e["key"] for e in doc["findings"]]
    assert keys == sorted(keys)
    # round-trip absorbs exactly `count` occurrences, extras still fail
    base = graftlint.load_baseline(str(bp))
    fresh, old = graftlint.split_baselined(findings, base)
    assert not fresh and len(old) == len(findings)
    fresh, _ = graftlint.split_baselined(findings + findings, base)
    assert len(fresh) == len(findings)


def test_real_baseline_file_is_sorted():
    doc = json.loads(open(graftlint.BASELINE_PATH).read())
    keys = [e["key"] for e in doc["findings"]]
    assert keys == sorted(keys)


# --------------------------------------------------------------------------
# GL012 — the SLO plane's method contract (ISSUE 10)


def test_gl012_ad_hoc_percentile_math_flagged():
    ctx = ctx_for("""
        import statistics
        from .latency import Window
        CLASSES = ("interactive",)
        def evaluate(samples):
            return statistics.quantiles(samples, n=100)[98]
    """, path="minio_tpu/obs/slo.py")
    found = checkers.check_slo_plane(ctx)
    assert any(f.token == "statistics.quantiles" for f in found)
    assert all(f.checker == "GL012" for f in found)
    # numpy spellings too
    ctx = ctx_for("""
        import numpy as np
        from .latency import Window
        CLASSES = ("interactive",)
        def evaluate(samples):
            return np.percentile(samples, 99)
    """, path="minio_tpu/obs/slo.py")
    assert any(f.token == "np.percentile"
               for f in checkers.check_slo_plane(ctx))


def test_gl012_window_shadow_and_missing_import_flagged():
    ctx = ctx_for("""
        CLASSES = ("interactive",)
        class Window:
            pass
        def cell():
            return Window()
    """, path="minio_tpu/obs/slo.py")
    tokens = {f.token for f in checkers.check_slo_plane(ctx)}
    assert "Window" in tokens           # local shadow
    assert "Window-import" in tokens    # Window() without .latency import


def test_gl012_undocumented_class_and_missing_registry_flagged():
    ctx = ctx_for("""
        from .latency import Window
        CLASSES = ("interactive", "totally-undocumented-class")
    """, path="minio_tpu/obs/slo.py")
    found = checkers.check_slo_plane(ctx)
    assert [f.token for f in found] == ["totally-undocumented-class"]
    # no CLASSES tuple at all: the taxonomy must be greppable
    ctx = ctx_for("from .latency import Window",
                  path="minio_tpu/obs/slo.py")
    assert [f.token for f in checkers.check_slo_plane(ctx)] == \
        ["CLASSES"]


def test_gl012_real_module_and_foreign_paths_clean():
    # the REAL obs/slo.py parses clean (CLASSES documented, windows
    # from obs/latency)
    real = graftlint.parse_file(os.path.join(
        graftlint.REPO_ROOT, "minio_tpu", "obs", "slo.py"))
    assert real is not None
    assert not checkers.check_slo_plane(real)
    # the same smells anywhere else are out of scope for GL012
    ctx = ctx_for("""
        import statistics
        def pct(samples):
            return statistics.quantiles(samples, n=100)
    """, path="minio_tpu/obs/other.py")
    assert not checkers.check_slo_plane(ctx)


# --------------------------------------------------------------------------
# GL013 — every dispatch op branch in _flush_device carries a mesh route


_GL013_OK = """
    _OP_NAME = {"encode": "encode", "masked": "reconstruct",
                "weird": "weird"}
    _MESH_SINGLE_DEVICE_OPS = frozenset({"weird"})
    class DispatchQueue:
        def _flush_device(self, b, items, lane=None):
            mesh = object_mesh()
            use_mesh = mesh is not None and lane is None
            if b.op == "weird":
                out = weird_launch(items)    # exempt: registry entry
            elif b.op == "encode":
                if use_mesh:
                    out = sharded_batched(b.codec._mm_batch, mesh,
                                          (False, True))(m, stack)
                else:
                    out = b.codec.encode_words_batch(stack)
            else:   # masked rides the else branch
                if mesh is not None:
                    out = sharded_batched(b.codec._mm_batch_per, mesh,
                                          (True, True))(masks, stack)
                else:
                    out = b.codec._mm_batch_per(masks, stack)
"""


def test_gl013_routed_and_exempt_ops_clean():
    ctx = ctx_for(_GL013_OK, path="minio_tpu/runtime/dispatch.py")
    assert not checkers.check_mesh_routes(ctx)
    # out of scope anywhere else
    assert not checkers.check_mesh_routes(
        ctx_for(_GL013_OK, path="minio_tpu/runtime/other.py"))


def test_gl013_device_only_branch_flagged():
    """The select_scan regression this checker exists for: an op branch
    that launches device-only (no sharded_batched under a mesh arm) and
    is NOT in the exemption registry."""
    ctx = ctx_for("""
        _OP_NAME = {"encode": "encode", "select_scan": "select_scan"}
        _MESH_SINGLE_DEVICE_OPS = frozenset()
        class DispatchQueue:
            def _flush_device(self, b, items):
                mesh = object_mesh()
                if b.op == "select_scan":
                    out = scan_fn(stack)     # device-only — finding
                else:
                    if mesh is not None:
                        out = sharded_batched(f, mesh, (True,))(stack)
                    else:
                        out = f(stack)
    """, path="minio_tpu/runtime/dispatch.py")
    found = checkers.check_mesh_routes(ctx)
    assert [f.token for f in found] == ["mesh-route:select_scan"]
    assert all(f.checker == "GL013" for f in found)


def test_gl013_unguarded_shard_call_and_missing_registry_flagged():
    # sharded_batched NOT under a mesh-guarded arm does not count, and
    # a dispatch module without the exemption registry is itself a
    # finding — exemptions must be an explicit reviewable literal
    ctx = ctx_for("""
        _OP_NAME = {"encode": "encode"}
        class DispatchQueue:
            def _flush_device(self, b, items):
                if b.op == "encode":
                    out = sharded_batched(f, m, (True,))(stack)
    """, path="minio_tpu/runtime/dispatch.py")
    tokens = {f.token for f in checkers.check_mesh_routes(ctx)}
    assert tokens == {"_MESH_SINGLE_DEVICE_OPS", "mesh-route:encode"}


def test_gl013_unhandled_registry_op_flagged():
    """An _OP_NAME op no branch (and no else) handles cannot have a
    mesh route — the new-op-PR failure mode caught at lint time."""
    ctx = ctx_for("""
        _OP_NAME = {"encode": "encode", "new_op": "new_op"}
        _MESH_SINGLE_DEVICE_OPS = frozenset()
        class DispatchQueue:
            def _flush_device(self, b, items):
                mesh = object_mesh()
                if b.op == "encode":
                    if mesh is not None:
                        out = sharded_batched(f, mesh, (True,))(stack)
                    else:
                        out = f(stack)
    """, path="minio_tpu/runtime/dispatch.py")
    found = checkers.check_mesh_routes(ctx)
    assert [f.token for f in found] == ["mesh-route:new_op"]


def test_gl013_real_dispatch_module_clean():
    real = graftlint.parse_file(os.path.join(
        graftlint.REPO_ROOT, "minio_tpu", "runtime", "dispatch.py"))
    assert real is not None
    assert not checkers.check_mesh_routes(real)


# --------------------------------------------------------------------------
# GL014 — dist/ RPC plane: chaos-reachable entry points, bounded waits


def test_gl014_unbounded_http_and_waits_flagged():
    ctx = ctx_for("""
        import requests
        class SomeClient:
            def fetch(self):
                return self._session.post(url, data=b"")   # no timeout

            def probe(self):
                return self._session.get(url, timeout=2)   # bounded: ok

            def park(self):
                self._stop.wait()                           # unbounded
                self._stop.wait(1.0)                        # bounded: ok
    """, path="minio_tpu/dist/newsvc.py")
    got = checkers.check_dist_rpc_bounds(ctx)
    tokens = sorted(f.token for f in got)
    assert "http:post" in tokens, tokens
    assert any(t.startswith("wait:") for t in tokens), tokens
    # the requests import outside rpc.py is itself a finding
    assert "requests-import" in tokens, tokens
    assert all(f.checker == "GL014" for f in got)
    # dict .get / plain calls never match
    assert not any("http:get" == t for t in tokens
                   if "session" not in t), tokens


def test_gl014_out_of_scope_and_rpc_py_import_clean():
    src = """
        import requests
        def f(session):
            return session.post(url, data=b"")
    """
    # outside dist/: not GL014's business
    assert not checkers.check_dist_rpc_bounds(
        ctx_for(src, path="minio_tpu/server/s3api.py"))
    # rpc.py may import requests (it IS the funnel), but its HTTP
    # calls still need timeouts
    got = checkers.check_dist_rpc_bounds(
        ctx_for(src, path="minio_tpu/dist/rpc.py"))
    assert [f.token for f in got] == ["http:post"]


def test_gl014_rpc_call_needs_both_fault_layers():
    missing_node = """
        class RPCClient:
            def call(self, method):
                _fault.inject("rpc", self.base, method)
                return self._session.post(url, timeout=5)
    """
    got = checkers.check_dist_rpc_bounds(
        ctx_for(missing_node, path="minio_tpu/dist/rpc.py"))
    assert [f.token for f in got] == ["hook:node"], got
    both = """
        class RPCClient:
            def call(self, method):
                _fault.inject("node", self.base, self.src)
                _fault.inject("rpc", self.base, method)
                return self._session.post(url, timeout=5)
    """
    assert not checkers.check_dist_rpc_bounds(
        ctx_for(both, path="minio_tpu/dist/rpc.py"))


def test_gl014_real_dist_modules_clean():
    for name in ("rpc", "storage_rest", "lock_rest", "peer", "dsync",
                 "harness"):
        real = graftlint.parse_file(os.path.join(
            graftlint.REPO_ROOT, "minio_tpu", "dist", f"{name}.py"))
        assert real is not None
        assert not checkers.check_dist_rpc_bounds(real), name


# --------------------------------------------------------------------------
# GL015 — interactive-class paths block only via the sanctioned helper


_GL015_BAD = """
    def erasure_heal(erasure, writers, readers, total_length):
        def emit(entry):
            kind, fut, b = entry
            res = fut.result()                 # bare blocking wait
            return res
        emit(None)

    def erasure_decode(erasure, writer, readers, offset, length, total):
        fut = erasure.decode_data_blocks_async([])
        return fut.result(30)                  # bare, with timeout

    def erasure_encode(erasure, stream, writers, quorum):
        return some_future.result()            # NOT a registered path
"""


def test_gl015_bare_result_in_interactive_paths_flagged():
    ctx = ctx_for(_GL015_BAD, path="minio_tpu/erasure/streaming.py")
    found = checkers.check_interactive_blocking(ctx)
    assert len(found) == 2, found
    assert all(f.checker == "GL015" for f in found)
    scopes = {f.scope for f in found}
    assert scopes == {"erasure_heal.emit", "erasure_decode"}, scopes
    # out of scope anywhere else — the registry is per-file
    assert not checkers.check_interactive_blocking(
        ctx_for(_GL015_BAD, path="minio_tpu/erasure/other.py"))


def test_gl015_helper_form_and_helper_module_clean():
    ok = """
        from ..runtime import completion as _compl

        def erasure_heal(erasure, writers, readers, total_length):
            def emit(entry):
                kind, fut, b = entry
                return _compl.await_result(fut, op="rebuild")
            emit(None)

        def erasure_decode(erasure, writer, readers, o, l, t):
            return _compl.await_result(make_future(), op="decode")
    """
    assert not checkers.check_interactive_blocking(
        ctx_for(ok, path="minio_tpu/erasure/streaming.py"))
    # the helper module itself is exempt by construction (it IS the
    # one sanctioned place that may call .result())
    helper = """
        def await_result(fut, op="", timeout=None):
            return fut.result(timeout)
    """
    assert not checkers.check_interactive_blocking(
        ctx_for(helper, path="minio_tpu/runtime/completion.py"))


def test_gl015_real_streaming_module_clean():
    real = graftlint.parse_file(os.path.join(
        graftlint.REPO_ROOT, "minio_tpu", "erasure", "streaming.py"))
    assert real is not None
    assert not checkers.check_interactive_blocking(real)
    # and the helper really exists where the checker points
    helper = graftlint.parse_file(os.path.join(
        graftlint.REPO_ROOT, "minio_tpu", "runtime", "completion.py"))
    assert helper is not None
    assert any(isinstance(n, ast.FunctionDef) and
               n.name == "await_result"
               for n in ast.walk(helper.tree))


# --------------------------------------------------------------------------
# GL016 — every thread construction under minio_tpu/ carries a name


def test_gl016_unnamed_thread_flagged():
    ctx = ctx_for("""
        import threading
        def spawn():
            t = threading.Thread(target=work, daemon=True)
            t.start()
            threading.Thread(target=work, args=(1,)).start()
    """)
    found = checkers.check_thread_names(ctx)
    assert [f.checker for f in found] == ["GL016", "GL016"]
    assert "name=" in found[0].message
    assert found[0].scope == "spawn"


def test_gl016_named_threads_and_subclasses_ok():
    ctx = ctx_for("""
        import threading

        class Worker(threading.Thread):
            def __init__(self):
                super().__init__(name="minio-tpu-worker", daemon=True)

        def spawn():
            threading.Thread(target=work, daemon=True,
                             name="minio-tpu-x").start()
            Worker().start()
            threading.Timer(0.2, work).start()   # not a Thread ctor
    """)
    assert not checkers.check_thread_names(ctx)


def test_gl016_out_of_scope_paths_ignored():
    src = """
        import threading
        threading.Thread(target=work).start()
    """
    assert not checkers.check_thread_names(
        ctx_for(src, path="tools/something.py"))
    assert not checkers.check_thread_names(
        ctx_for(src, path="tests/test_something.py"))


def test_gl016_registered_and_baseline_empty():
    """The satellite fix (ISSUE 14): GL016 is an active PER_FILE
    checker (so test_tree_is_clean already proves the shipped tree has
    every Thread construction named) and the baseline is EMPTY — no
    grandfathered unnamed threads."""
    assert checkers.check_thread_names in checkers.PER_FILE
    assert graftlint.load_baseline() == {}, \
        "GL016 must hold with an EMPTY baseline"

# --------------------------------------------------------------------------
# GL017 — every compile site routes through obs.device.tracked_jit


def test_gl017_untracked_jit_flagged():
    ctx = ctx_for("""
        import functools
        import jax

        def build(fn):
            w = jax.jit(fn, static_argnames=("interpret",))
            return w

        @jax.jit
        def bare(x):
            return x

        deco = functools.partial(jax.jit, donate_argnums=(0,))
    """)
    found = checkers.check_tracked_compiles(ctx)
    kinds = sorted(f.token for f in found)
    assert [f.checker for f in found] == ["GL017"] * 3
    assert kinds == ["jax.jit", "jax.jit", "partial(jax.jit)"]
    assert any(f.scope == "build" for f in found)
    assert all("tracked_jit" in f.message for f in found)


def test_gl017_untracked_pallas_call_flagged():
    ctx = ctx_for("""
        from jax.experimental import pallas as pl

        def kernel_builder(spec):
            return pl.pallas_call(kern, out_shape=spec)
    """)
    found = checkers.check_tracked_compiles(ctx)
    assert [f.checker for f in found] == ["GL017"]
    assert found[0].scope == "kernel_builder"


def test_gl017_wrapper_module_and_registry_exempt():
    # the wrapper module itself holds the one sanctioned jax.jit
    src = """
        import jax
        def _build(fn):
            return jax.jit(fn)
    """
    assert not checkers.check_tracked_compiles(
        ctx_for(src, path="minio_tpu/obs/device.py"))
    # pallas_call inside a registered tracked-jit scope is sanctioned
    pallas = """
        from jax.experimental import pallas as pl

        def gf_matmul_pallas(a, b, interpret=False):
            return pl.pallas_call(kern, out_shape=shp)(a, b)
    """
    assert not checkers.check_tracked_compiles(
        ctx_for(pallas, path="minio_tpu/ops/rs_pallas.py"))
    # ...but the SAME site in an unregistered scope is a finding
    moved = pallas.replace("gf_matmul_pallas", "new_unreviewed_kernel")
    assert checkers.check_tracked_compiles(
        ctx_for(moved, path="minio_tpu/ops/rs_pallas.py"))
    # out-of-scope paths (tools/, tests/) are never checked
    assert not checkers.check_tracked_compiles(
        ctx_for(src, path="tools/bench_helper.py"))


def test_gl017_tracked_sites_ok():
    ctx = ctx_for("""
        import functools
        from ..obs.device import tracked_jit

        def build(fn):
            return tracked_jit(fn, op="xla.gf_matmul")

        @functools.partial(tracked_jit, op="pallas.encode",
                           static_argnames=("interpret",))
        def run(words):
            return words
    """)
    assert not checkers.check_tracked_compiles(ctx)


def test_gl017_registered_and_baseline_empty():
    """GL017 is an active PER_FILE checker (so test_tree_is_clean
    proves every live compile site in the shipped tree routes through
    tracked_jit or a reviewed registry entry) with an EMPTY baseline —
    no grandfathered untracked compiles."""
    assert checkers.check_tracked_compiles in checkers.PER_FILE
    assert graftlint.load_baseline() == {}, \
        "GL017 must hold with an EMPTY baseline"
    # the registry only names scopes that actually exist in the tree
    for relpath, scopes in checkers._GL017_PALLAS_SCOPES.items():
        ctx = graftlint.parse_file(
            os.path.join(graftlint.REPO_ROOT, relpath))
        assert ctx is not None, relpath
        for s in scopes:
            leaf = s.rsplit(".", 1)[-1]
            assert any(isinstance(n, ast.FunctionDef) and
                       n.name == leaf for n in ast.walk(ctx.tree)), \
                f"{relpath}: registered scope {s} no longer exists"


def test_gl018_raw_emitter_kwarg_flagged():
    ctx = ctx_for("""
        from .obs import metrics as mx

        def handler(bucket, key):
            mx.inc("minio_tpu_x_total", bucket=bucket)
            mx.observe("minio_tpu_y_seconds", 0.1, key=key)
    """)
    found = checkers.check_bounded_request_labels(ctx)
    assert [f.checker for f in found] == ["GL018", "GL018"]
    assert "bucket=bucket" in found[0].token
    assert "key=key" in found[1].token


def test_gl018_raw_fstring_label_flagged():
    ctx = ctx_for('''
        def collect(rows):
            out = []
            for b, size in rows:
                out.append(
                    f'minio_tpu_x_bytes{{bucket="{b}"}} {size}')
            return out
    ''')
    found = checkers.check_bounded_request_labels(ctx)
    assert [f.checker for f in found] == ["GL018"]
    assert "bucket" in found[0].token


def test_gl018_folded_and_constant_labels_ok():
    """fold_label calls, names bound from one, and constants all pass —
    both the kwarg and the f-string surface (the `lab = fold_label(b);
    f'...{_esc(lab)}...'` bind-then-interpolate idiom included)."""
    ctx = ctx_for('''
        from .obs import metrics as mx
        from .obs.bucketstats import fold_label

        def handler(bucket):
            mx.inc("minio_tpu_x_total", bucket=fold_label(bucket))
            mx.inc("minio_tpu_x_total", bucket="_all_")
            mx.inc("minio_tpu_x_total", target=bucket)  # not sensitive

        def collect(rows):
            out = []
            for b, size in rows:
                lab = fold_label(b)
                out.append(
                    f'minio_tpu_x_bytes{{bucket="{_esc(lab)}"}} {size}')
            return out
    ''')
    assert not checkers.check_bounded_request_labels(ctx)


def test_gl018_home_module_and_foreign_paths_exempt():
    src = """
        from . import metrics as mx

        def charge(bucket):
            mx.inc("minio_tpu_x_total", bucket=bucket)
    """
    # the fold helper's own module IS the bound — exempt
    assert not checkers.check_bounded_request_labels(
        ctx_for(src, path="minio_tpu/obs/bucketstats.py"))
    # outside minio_tpu/ (tools, tests) out of scope
    assert not checkers.check_bounded_request_labels(
        ctx_for(src, path="tools/graftlint/checkers.py"))
    # same source elsewhere under minio_tpu/ is a finding
    assert checkers.check_bounded_request_labels(
        ctx_for(src, path="minio_tpu/obs/health.py"))


def test_gl018_registered_and_baseline_empty():
    """GL018 is an active PER_FILE checker (so test_tree_is_clean
    proves every live emission site folds request-derived labels) with
    an EMPTY baseline — no grandfathered cardinality leaks."""
    assert checkers.check_bounded_request_labels in checkers.PER_FILE
    assert graftlint.load_baseline() == {}, \
        "GL018 must hold with an EMPTY baseline"


# --------------------------------------------------------------------------
# GL019 — replication/lifecycle async planes: bounded, chaos-reachable


def test_gl019_unbounded_ship_calls_flagged():
    """Network calls in the async-plane modules without timeout= are
    findings: peer-RPC ship methods, generic .call, and requests-style
    HTTP all hang the worker forever on a wedged target."""
    ctx = ctx_for("""
        def ship(peer, sess, bucket, key, blob):
            peer.replicate_object(bucket, key, blob)
            peer.call("ReplicateDelete", bucket=bucket, key=key)
            sess.http.post("http://tier/x", data=blob)
    """, path="minio_tpu/bucket/replicate.py")
    found = checkers.check_async_plane_bounds(ctx)
    assert [f.checker for f in found] == ["GL019"] * 3
    assert {f.token for f in found} == \
        {"net:replicate_object", "net:call", "net:post"}


def test_gl019_bounded_and_out_of_scope_ok():
    src = """
        def ship(peer, sess, bucket, key, blob):
            peer.replicate_object(bucket, key, blob, timeout=10.0)
            sess.http.post("http://tier/x", data=blob, timeout=5)
    """
    # timeout= present -> clean in an async-plane module
    assert not checkers.check_async_plane_bounds(
        ctx_for(src, path="minio_tpu/bucket/tiers.py"))
    # the same calls WITHOUT timeout are fine outside the plane
    bare = """
        def ship(peer, bucket, key, blob):
            peer.replicate_object(bucket, key, blob)
    """
    assert not checkers.check_async_plane_bounds(
        ctx_for(bare, path="minio_tpu/server/s3api.py"))


def test_gl019_tier_class_missing_hook_and_deadline_flagged():
    """A Tier* data-path class with no fault.inject("disk", ...) hook
    and no deadline surfaces BOTH findings; TierRegistry (pure
    bookkeeping, no IO) is exempt by name."""
    ctx = ctx_for("""
        class TierNFS:
            def get(self, key):
                return open(self.root + key, "rb").read()

        class TierRegistry:
            def lookup(self, name):
                return self.tiers[name]
    """, path="minio_tpu/bucket/tiers.py")
    found = checkers.check_async_plane_bounds(ctx)
    assert {f.token for f in found} == \
        {"hook:TierNFS", "deadline:TierNFS"}


def test_gl019_tier_class_with_hook_and_deadline_ok():
    ctx = ctx_for("""
        from .. import fault

        class TierFS:
            def get(self, key):
                fault.inject("disk", self.name, "tier_get")
                return _bounded(self._read, key)
    """, path="minio_tpu/bucket/tiers.py")
    assert not checkers.check_async_plane_bounds(ctx)


def test_gl019_registered_and_baseline_empty():
    """GL019 is an active PER_FILE checker (so test_tree_is_clean
    proves every live ship/tier site is bounded + chaos-reachable)
    with an EMPTY baseline, and its file set still exists on disk."""
    assert checkers.check_async_plane_bounds in checkers.PER_FILE
    assert graftlint.load_baseline() == {}, \
        "GL019 must hold with an EMPTY baseline"
    for relpath in checkers._GL019_FILES:
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            relpath)), f"GL019 covers missing file {relpath}"


# --------------------------------------------------------------------------
# GL020/GL021/GL022 — whole-program engine (tools/graftlint/program.py)


from tools.graftlint.program import build_program, check_whole_program  # noqa: E402,E501


def _wp(*srcs_paths):
    """Findings from the whole-program checkers over synthetic files,
    with pragma suppression applied exactly as run() applies it."""
    ctxs = [ctx_for(s, p) for s, p in srcs_paths]
    fs = check_whole_program(ctxs)
    return [f for f in fs if not graftlint._ctx_suppressed(ctxs, f)]


def test_whole_program_checkers_registered():
    from tools.graftlint.program import check_whole_program as wp
    assert wp in checkers.PROJECT


GL020_POS = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self.x = 0          # __init__ write never counts
        def a(self):
            with self._lock:
                self.x = 1
        def b(self):
            with self._lock:
                self.x = 2
        def c(self):
            with self._lock:
                self.x = 3
        def d(self):
            with self._lock:
                self.x = 4
        def e(self):
            self.x = 5          # 4/5 guarded -> this site is flagged
"""


def test_gl020_unguarded_minority_write_flagged():
    fs = [f for f in _wp((GL020_POS, "minio_tpu/_synthetic.py"))
          if f.checker == "GL020"]
    assert len(fs) == 1
    assert "self.x" in fs[0].message and "self._lock" in fs[0].message
    assert "4/5" in fs[0].message
    assert fs[0].scope == "C.e"


def test_gl020_below_threshold_and_unanimous_quiet():
    src = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
            def a(self):
                with self._lock:
                    self.x = 1
            def b(self):
                with self._lock:
                    self.x = 2
            def c(self):
                with self._lock:
                    self.x = 3
            def d(self):
                self.x = 4      # 3/4 = 75% < threshold: GIL-atomic idiom
            def e(self):
                with self._lock:
                    self.y = 1  # unanimous guard: clean
            def f(self):
                with self._lock:
                    self.y = 2
    """
    assert not [f for f in _wp((src, "minio_tpu/_synthetic.py"))
                if f.checker == "GL020"]


def test_gl020_entry_held_private_helper_counts_as_guarded():
    """The `_refill_locked` convention: a private method whose every
    intra-class call site holds the lock runs under it — its writes are
    guarded, not 4/5 findings."""
    src = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
            def a(self):
                with self._lock:
                    self.x = 1
            def b(self):
                with self._lock:
                    self.x = 2
            def c(self):
                with self._lock:
                    self.x = 3
            def d(self):
                with self._lock:
                    self.x = 4
            def e(self):
                with self._lock:
                    self._bump()
            def _bump(self):
                self.x = 5
    """
    assert not [f for f in _wp((src, "minio_tpu/_synthetic.py"))
                if f.checker == "GL020"]
    # ...but a helper ALSO called without the lock inherits nothing
    src_bad = src + """
        def g(c):
            c2 = C()
            c2._bump()
    """
    # the unlocked external call only breaks inference for self-calls
    # within the class; module-level calls are not counted — add an
    # in-class unlocked call site instead
    src_bad = src.replace(
        "            def _bump(self):",
        "            def f(self):\n"
        "                self._bump()\n"
        "            def _bump(self):")
    fs = [f for f in _wp((src_bad, "minio_tpu/_synthetic.py"))
          if f.checker == "GL020"]
    assert len(fs) == 1 and fs[0].scope == "C._bump"


def test_gl020_condition_alias_counts_as_backing_lock():
    src = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
            def a(self):
                with self._lock:
                    self.x = 1
            def b(self):
                with self._lock:
                    self.x = 2
            def c(self):
                with self._lock:
                    self.x = 3
            def d(self):
                with self._lock:
                    self.x = 4
            def e(self):
                with self._cv:
                    self.x = 5   # guarded via the alias -> 5/5, clean
    """
    assert not [f for f in _wp((src, "minio_tpu/_synthetic.py"))
                if f.checker == "GL020"]


GL021_CHAIN = """
    import threading
    import time

    _lock = threading.Lock()

    def a():
        with _lock:
            b()

    def b():
        c()

    def c():
        time.sleep(1)
"""


def test_gl021_blocking_reached_through_call_chain():
    fs = [f for f in _wp((GL021_CHAIN, "minio_tpu/_synthetic.py"))
          if f.checker == "GL021"]
    assert len(fs) == 1
    assert "a -> b -> c" in fs[0].message
    assert "time.sleep" in fs[0].message


def test_gl021_chain_deeper_than_bound_quiet():
    src = """
        import threading
        import time

        _lock = threading.Lock()

        def a():
            with _lock:
                b()

        def b():
            c()

        def c():
            d()

        def d():
            e()

        def e():
            time.sleep(1)
    """
    assert not [f for f in _wp((src, "minio_tpu/_synthetic.py"))
                if f.checker == "GL021"]


def test_gl021_cv_wait_on_own_condition_exempt():
    src = """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._other = threading.Lock()
            def ok(self):
                with self._cv:
                    self._drain()   # wait releases the held lock
            def bad(self):
                with self._other:
                    self._drain()   # convoys _other behind the wait
            def _drain(self):
                self._cv.wait()
    """
    fs = [f for f in _wp((src, "minio_tpu/_synthetic.py"))
          if f.checker == "GL021"]
    assert len(fs) == 1
    assert fs[0].scope == "W.bad"
    assert "self._cv.wait()" in fs[0].message


def test_gl021_pragma_suppresses():
    src = GL021_CHAIN.replace(
        "            b()",
        "            b()  # graftlint: disable=GL021")
    assert not [f for f in _wp((src, "minio_tpu/_synthetic.py"))
                if f.checker == "GL021"]


BUFPOOL_STUB = ("""
    class BufferPool:
        def get(self, n):
            return bytearray(n)
        def put(self, arr):
            pass
""", "minio_tpu/runtime/bufpool.py")


def _gl022(consumer_src):
    return [f for f in _wp(BUFPOOL_STUB,
                           (consumer_src, "minio_tpu/_synthetic.py"))
            if f.checker == "GL022"]


def test_gl022_bufpool_verdicts():
    header = """
        from minio_tpu.runtime.bufpool import BufferPool

        class C:
            def __init__(self):
                self._pool = BufferPool()
    """
    # discarded result: can never be released
    fs = _gl022(header + """
            def f(self):
                self._pool.get(1 << 20)
    """)
    assert len(fs) == 1 and "discarded" in fs[0].message
    # bound but never released and never escaping
    fs = _gl022(header + """
            def f(self):
                arr = self._pool.get(1 << 20)
                arr[0] = 1
    """)
    assert len(fs) == 1 and "never released" in fs[0].message
    # released only on the happy path with risky calls in between
    fs = _gl022(header + """
            def f(self, stream):
                arr = self._pool.get(1 << 20)
                stream.readinto(arr)
                self._pool.put(arr)
    """)
    assert len(fs) == 1 and "exception edge" in fs[0].message
    # release in a finally: clean
    fs = _gl022(header + """
            def f(self, stream):
                arr = self._pool.get(1 << 20)
                try:
                    stream.readinto(arr)
                finally:
                    self._pool.put(arr)
    """)
    assert not fs
    # immediate escape via return: ownership transfer, clean
    fs = _gl022(header + """
            def f(self):
                arr = self._pool.get(1 << 20)
                return arr
    """)
    assert not fs


def test_gl022_ledger_release_on_exception_edge():
    device_stub = ("""
        def ledger_acquire(n):
            return object()

        def ledger_release(tok):
            pass
    """, "minio_tpu/obs/device.py")
    header = """
        from minio_tpu.obs import device as _dev
    """
    fs = [f for f in _wp(device_stub, (header + """
        def f(submit, n):
            tok = _dev.ledger_acquire(n)
            try:
                submit(tok)
            except BaseException:
                _dev.ledger_release(tok)
                raise
    """, "minio_tpu/_synthetic.py")) if f.checker == "GL022"]
    assert not fs   # handler release covers the raise edge
    fs = [f for f in _wp(device_stub, (header + """
        def f(work, n):
            tok = _dev.ledger_acquire(n)
            work()
            _dev.ledger_release(tok)
    """, "minio_tpu/_synthetic.py")) if f.checker == "GL022"]
    assert len(fs) == 1 and "exception edge" in fs[0].message


def test_program_build_deterministic():
    files = graftlint.iter_py_files(["minio_tpu/event"])
    ctxs = [c for c in map(graftlint.parse_file, files) if c]
    p1 = build_program(ctxs, cache_path=None)
    p2 = build_program(ctxs, cache_path=None)
    assert p1.to_json() == p2.to_json()


def test_summary_cache_hits_on_second_build(tmp_path):
    from tools.graftlint import program as prog_mod
    files = graftlint.iter_py_files(["minio_tpu/event"])
    ctxs = [c for c in map(graftlint.parse_file, files) if c]
    cp = str(tmp_path / "cache.json")
    p1 = build_program(ctxs, cache_path=cp)
    assert prog_mod.LAST_BUILD_STATS["cache_hits"] == 0
    p2 = build_program(ctxs, cache_path=cp)
    assert prog_mod.LAST_BUILD_STATS["cache_hits"] == len(ctxs)
    assert p1.to_json() == p2.to_json()


def test_cli_json_roundtrip(tmp_path, capsys):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent("""
        import threading
        _lock = threading.Lock()
        def f():
            _lock.acquire()
            _lock.release()
    """))
    assert lint_main([str(p), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["findings"]
    f = doc["findings"][0]
    assert set(f) == {"file", "line", "id", "severity", "message", "key"}
    assert f["id"] == "GL003" and f["file"].endswith("bad.py")
    assert f["severity"] == "error" and isinstance(f["line"], int)


def test_gl020_pragma_suppresses():
    src = GL020_POS.replace(
        "            self.x = 5",
        "            self.x = 5  # graftlint: disable=GL020")
    assert not [f for f in _wp((src, "minio_tpu/_synthetic.py"))
                if f.checker == "GL020"]


def test_gl022_pragma_suppresses():
    src = """
        from minio_tpu.runtime.bufpool import BufferPool

        class C:
            def __init__(self):
                self._pool = BufferPool()
            def f(self):
                # graftlint: disable=GL022
                self._pool.get(1 << 20)
    """
    assert not _gl022(src)
