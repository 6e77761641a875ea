"""The graftlint checkers (GL001-GL019).

Each per-file checker takes a ``FileCtx`` and yields ``Finding``s; the
project-wide checkers take the full list of parsed files (cross-file
contracts: emitted metrics vs docs). All analysis is pure AST + source
text — nothing in the checked tree is imported.

| id    | invariant                                                    |
|-------|--------------------------------------------------------------|
| GL001 | no wall-clock (``time.time``) values in duration arithmetic  |
| GL002 | no blocking call (sleep/IO/RPC/flush/result) under a lock    |
| GL003 | locks acquired only via ``with`` — no bare acquire/release   |
| GL004 | every emitted ``minio_tpu_*`` metric documented in           |
|       | docs/observability.md                                        |
| GL005 | pool submits on traced paths wrap the callable in            |
|       | ``spans.wrap_ctx``                                           |
| GL006 | storage/rpc/kernel op entry points carry a fault-inject hook |
| GL007 | no bare ``except:`` / swallowed exceptions in daemon threads |
| GL008 | every dynamic config KVS key documented in docs/             |
| GL009 | no bare ``os.replace``/``os.rename`` — commits go through    |
|       | ``storage.durability.durable_replace`` (fsync policy)        |
| GL010 | no host hashing / bytes copies on the PUT/GET hot path       |
|       | outside the sanctioned ``*_fallback`` helpers (zero-copy     |
|       | pipeline invariant)                                          |
| GL011 | every dispatch flush route (``_flush_device`` /              |
|       | ``_flush_cpu``) emits paired flight-recorder flush           |
|       | start/end events via ``_tl_flush_cb`` (keyed on the          |
|       | ``_OP_NAME`` registry, like GL006)                           |
| GL012 | the SLO plane's contract: every objective class in           |
|       | ``obs/slo.py``'s ``CLASSES`` appears in                      |
|       | docs/observability.md, and every SLO-evaluated window        |
|       | comes from ``obs/latency.Window`` — no ad-hoc percentile     |
|       | math (statistics/numpy quantiles, local Window shadows)      |
| GL013 | every ``b.op`` branch in ``_flush_device`` calls             |
|       | ``sharded_batched`` under a ``mesh``-guarded arm or its ops  |
|       | appear in the ``_MESH_SINGLE_DEVICE_OPS`` exemption          |
|       | registry — a new dispatch op cannot silently ship            |
|       | device-only without a mesh route                             |
| GL014 | the dist/ RPC plane is chaos-reachable and bounded: every    |
|       | HTTP call carries a ``timeout=``, no unbounded ``.wait()``/  |
|       | ``.recv()``, ``requests`` is imported only by ``rpc.py``     |
|       | (every client funnels through ``RPCClient.call``), and       |
|       | ``RPCClient.call`` carries BOTH the per-call ``rpc`` and     |
|       | whole-peer ``node`` fault-injection hooks                    |
| GL015 | interactive-class code paths (heal-shard rebuild,            |
|       | degraded-GET reconstruct) never call blocking                |
|       | ``.result()`` on a future — every wait goes through the      |
|       | sanctioned async-completion helper                           |
|       | ``runtime/completion.await_result`` so lane waits are        |
|       | counted/timed and the latency tier stays enforceable         |
| GL016 | every ``threading.Thread(...)`` created under minio_tpu/     |
|       | passes a ``name=`` — the continuous profiler's thread-role   |
|       | classification (``obs/profiler.py``) keys on thread names,   |
|       | and an unnamed thread can only ever classify as "other"      |
| GL017 | every ``jax.jit`` / ``pl.pallas_call`` construction under    |
|       | minio_tpu/ routes through the device plane's tracked-compile |
|       | wrapper (``obs/device.tracked_jit``) or carries an explicit  |
|       | registry/pragma exemption — compile counting (and the        |
|       | compile-storm detector riding it) must not silently lose     |
|       | coverage as new ops land                                     |
| GL018 | request-derived Prometheus labels (bucket/key/user/tenant/   |
|       | object) must flow through the bounded-cardinality fold       |
|       | helper ``obs/bucketstats.fold_label`` — a raw request string |
|       | as a label value is an unbounded time-series cardinality     |
|       | leak (one series per tenant-chosen name)                     |
| GL019 | the replication + lifecycle async planes are bounded and     |
|       | chaos-reachable (GL014 extended): every network/ship call    |
|       | in the plane modules carries ``timeout=``, and every         |
|       | ``Tier*`` data-path class carries a disk-layer fault hook    |
|       | plus a deadline — a wedged target/tier parks the obligation  |
|       | for retry instead of hanging the worker or scanner           |
"""
from __future__ import annotations

import ast
import os
import re

from . import FileCtx, Finding, REPO_ROOT

# --------------------------------------------------------------------------
# shared AST helpers


def dotted(expr: ast.AST) -> str:
    """Dotted name of a Name/Attribute chain ('' when dynamic)."""
    parts: list[str] = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
    elif isinstance(expr, ast.Call):
        inner = dotted(expr.func)
        parts.append(f"{inner}()" if inner else "()")
    else:
        return ""
    return ".".join(reversed(parts))


def _unparse(node: ast.AST, limit: int = 60) -> str:
    try:
        s = ast.unparse(node)
    except Exception:  # pragma: no cover - defensive
        s = type(node).__name__
    s = re.sub(r"\s+", " ", s)
    return s if len(s) <= limit else s[:limit - 1] + "…"


def _walk_shallow(node: ast.AST):
    """Walk, but do not descend into nested function/class/lambda bodies
    (their execution is deferred — a lock held here is not held there)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(n))


_LOCK_CTORS = {"threading.Lock", "threading.RLock", "threading.Condition",
               "Lock", "RLock", "Condition"}
_LOCK_NAME_RE = re.compile(r"(^|_)(lock|mutex|cv|cond)s?$")


def _lockish_symbols(tree: ast.AST) -> set[str]:
    """Dotted targets assigned from threading.Lock/RLock/Condition()
    anywhere in the file ('self.X' kept as written — good enough for
    matching use sites inside the same class)."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            ctor = dotted(node.value.func)
            if ctor in _LOCK_CTORS:
                for t in node.targets:
                    d = dotted(t)
                    if d:
                        out.add(d)
    return out


def _is_lock_expr(expr: ast.AST, lockish: set[str]) -> bool:
    d = dotted(expr)
    if not d:
        return False
    if d in lockish:
        return True
    return bool(_LOCK_NAME_RE.search(d.rsplit(".", 1)[-1]))


# --------------------------------------------------------------------------
# GL001 — wall clock in duration arithmetic


def check_wall_duration(ctx: FileCtx) -> list[Finding]:
    tree = ctx.tree
    module_wall: set[str] = set()
    class_wall: set[str] = set()   # 'self.X' attrs assigned time.time()

    def is_time_time(node: ast.AST) -> bool:
        return isinstance(node, ast.Call) and \
            dotted(node.func) == "time.time"

    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and is_time_time(node.value):
            for t in node.targets:
                d = dotted(t)
                if not d:
                    continue
                if d.startswith("self."):
                    class_wall.add(d)
                else:
                    module_wall.add(d)

    # local names per function scope
    func_wall: dict[str, set[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            names = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) and is_time_time(sub.value):
                    for t in sub.targets:
                        if isinstance(t, ast.Name):
                            names.add(t.id)
            func_wall[f"{node.lineno}:{node.name}"] = names

    all_local = set().union(*func_wall.values()) if func_wall else set()

    def is_wall(e: ast.AST) -> bool:
        if is_time_time(e):
            return True
        d = dotted(e)
        if not d:
            return False
        return d in module_wall or d in class_wall or d in all_local

    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub) \
                and is_wall(node.left) and is_wall(node.right):
            out.append(Finding(
                ctx.path, node.lineno, "GL001",
                "wall-clock duration: both operands of '-' derive from "
                f"time.time() ({_unparse(node)}) — use time.monotonic() "
                "so an NTP step cannot distort the measurement",
                token=_unparse(node, 40),
                scope=ctx.scope_at(node.lineno)))
    return out


# --------------------------------------------------------------------------
# GL002 — blocking call under a held lock

_BLOCKING_DOTTED = {
    "time.sleep", "os.fsync", "os.fdatasync", "os.sync",
    "subprocess.run", "subprocess.check_output", "subprocess.check_call",
    "subprocess.call", "futures.wait", "concurrent.futures.wait",
    "urllib.request.urlopen", "request.urlopen", "socket.create_connection",
}
_BLOCKING_ATTRS = {
    "result", "block_until_ready", "urlopen", "getresponse", "recv",
    "sendall", "connect", "flush", "fsync", "shutdown", "map",
}
_MAYBE_BLOCKING_ATTRS = {"get", "put"}    # only with timeout=/block=
_IO_ATTRS = {"read", "write", "readinto", "read_at", "readline",
             "read_framed"}


def _is_blocking_call(call: ast.Call, with_expr_dump: str) -> str | None:
    """Reason string when this call can block, else None."""
    d = dotted(call.func)
    attr = d.rsplit(".", 1)[-1] if d else ""
    if d in _BLOCKING_DOTTED:
        return d
    if d == "open":
        return "open()"
    if attr == "wait":
        # cv.wait() inside `with cv` releases that same lock — fine
        if isinstance(call.func, ast.Attribute) and \
                ast.dump(call.func.value) == with_expr_dump:
            return None
        return f"{d}()"
    if attr == "join":
        # distinguish thread.join([timeout]) from str.join(iterable)
        if not call.args and not call.keywords:
            return f"{d}()"
        if len(call.args) == 1 and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, (int, float)):
            return f"{d}(timeout)"
        if any(k.arg == "timeout" for k in call.keywords):
            return f"{d}(timeout)"
        return None
    if attr in _MAYBE_BLOCKING_ATTRS:
        if any(k.arg in ("timeout", "block") for k in call.keywords):
            return f"{d}(timeout=…)"
        return None
    if attr in _BLOCKING_ATTRS or attr in _IO_ATTRS:
        return f"{d}()"
    return None


def check_blocking_under_lock(ctx: FileCtx) -> list[Finding]:
    lockish = _lockish_symbols(ctx.tree)
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.With):
            continue
        lock_items = [it for it in node.items
                      if _is_lock_expr(it.context_expr, lockish)]
        if not lock_items:
            continue
        wdump = ast.dump(lock_items[0].context_expr)
        lock_name = dotted(lock_items[0].context_expr)
        for body_stmt in node.body:
            if isinstance(body_stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                continue    # deferred body directly under the with
            for sub in _walk_shallow(body_stmt):
                if not isinstance(sub, ast.Call):
                    continue
                reason = _is_blocking_call(sub, wdump)
                if reason is None:
                    continue
                out.append(Finding(
                    ctx.path, sub.lineno, "GL002",
                    f"blocking call {reason} inside `with {lock_name}` — "
                    "move the blocking work outside the critical section",
                    token=f"{lock_name}|{_unparse(sub.func, 40)}",
                    scope=ctx.scope_at(sub.lineno)))
    return out


# --------------------------------------------------------------------------
# GL003 — bare acquire()/release() on locks


def check_bare_acquire(ctx: FileCtx) -> list[Finding]:
    lockish = _lockish_symbols(ctx.tree)
    out = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and
                isinstance(node.func, ast.Attribute)):
            continue
        if node.func.attr not in ("acquire", "release"):
            continue
        if not _is_lock_expr(node.func.value, lockish):
            continue
        d = dotted(node.func)
        out.append(Finding(
            ctx.path, node.lineno, "GL003",
            f"bare {d}() — acquire locks only via `with` so no "
            "exception path can leak a held lock",
            token=d, scope=ctx.scope_at(node.lineno)))
    return out


# --------------------------------------------------------------------------
# GL004 — every emitted metric documented (project-wide)

_METRIC_RE = re.compile(r"^minio_tpu_[a-z0-9_]+")
_TYPE_LINE_RE = re.compile(r"#\s*(?:TYPE|HELP)\s+(minio_tpu_[a-z0-9_]+)")


def _metric_literals(ctx: FileCtx) -> list[tuple[str, int]]:
    """(family, line) pairs this file emits: first args of inc()/
    observe(), families inside '# TYPE'/'# HELP' literals, and — in
    obs/metrics.py, whose generators build sample lines directly —
    every leading minio_tpu_* string/f-string fragment."""
    out: list[tuple[str, int]] = []
    is_metrics_mod = ctx.path.endswith("obs/metrics.py")

    def from_str(s: str, line: int):
        for m in _TYPE_LINE_RE.finditer(s):
            out.append((m.group(1), line))
        if is_metrics_mod and not s.lstrip().startswith("#"):
            m = _METRIC_RE.match(s)
            if m:
                out.append((m.group(0), line))

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            fn = dotted(node.func)
            # _metric is the obs-shielded wrapper the workload hot
            # paths use — its literal first arg is an emitted family
            # all the same (sse.py's _workload passes op strings, not
            # families; its inner inc() calls are caught directly)
            if fn.rsplit(".", 1)[-1] in ("inc", "observe",
                                         "_metric") and node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Constant) and \
                        isinstance(a0.value, str):
                    m = _METRIC_RE.match(a0.value)
                    if m:
                        out.append((m.group(0), node.lineno))
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            from_str(node.value, node.lineno)
        elif isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.Constant) and \
                        isinstance(v.value, str):
                    from_str(v.value, node.lineno)
    return out


def check_metrics_documented(ctxs: list[FileCtx]) -> list[Finding]:
    doc_path = os.path.join(REPO_ROOT, "docs", "observability.md")
    try:
        with open(doc_path, encoding="utf-8") as f:
            doc = f.read()
    except OSError:
        doc = ""
    seen: dict[str, tuple[str, int, str]] = {}
    for ctx in ctxs:
        for fam, line in _metric_literals(ctx):
            if fam not in seen:
                seen[fam] = (ctx.path, line, ctx.scope_at(line))
    out = []
    for fam in sorted(seen):
        if fam in doc:
            continue
        path, line, scope = seen[fam]
        out.append(Finding(
            path, line, "GL004",
            f"metric family {fam} is emitted but not documented in "
            "docs/observability.md",
            token=fam, scope=scope))
    return out


# --------------------------------------------------------------------------
# GL005 — pool submits on traced paths must wrap_ctx the callable

_POOL_RE = re.compile(r"pool", re.IGNORECASE)


def _is_traced_pool(recv: ast.AST) -> bool:
    """meta_pool()/io_pool()/encode_pool() results or *pool* attributes —
    the shared executors traced fan-outs ride."""
    if isinstance(recv, ast.Call):
        return bool(_POOL_RE.search(dotted(recv.func)))
    d = dotted(recv)
    return bool(d and _POOL_RE.search(d.rsplit(".", 1)[-1]))


def check_submit_wrap(ctx: FileCtx) -> list[Finding]:
    # names assigned from wrap_ctx(...) anywhere in the file count as
    # wrapped (the bind-at-enqueue pattern: w = wrap_ctx(fn); submit(w))
    wrapped_names: set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call) and \
                dotted(node.value.func).rsplit(".", 1)[-1] == "wrap_ctx":
            wrapped_names.update(d for d in (dotted(t)
                                             for t in node.targets) if d)
    out = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and
                isinstance(node.func, ast.Attribute) and
                node.func.attr == "submit" and node.args):
            continue
        if not _is_traced_pool(node.func.value):
            continue
        a0 = node.args[0]
        if isinstance(a0, ast.Call) and \
                dotted(a0.func).rsplit(".", 1)[-1] == "wrap_ctx":
            continue
        if dotted(a0) in wrapped_names:
            continue
        out.append(Finding(
            ctx.path, node.lineno, "GL005",
            f"pool submit of {_unparse(a0, 40)} without spans.wrap_ctx — "
            "contextvars (span context) do not cross thread-pool "
            "submissions on their own",
            token=_unparse(a0, 40), scope=ctx.scope_at(node.lineno)))
    return out


# --------------------------------------------------------------------------
# GL006 — fault-injection hooks on storage/rpc/kernel entry points

#: XLStorage public methods that are pure in-memory accessors — no I/O,
#: nothing to inject.
_XL_NON_IO = {"endpoint", "get_disk_id", "set_disk_id"}


def _contains_hook(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            tail = d.rsplit(".", 1)[-1]
            if tail in ("inject", "_op") or tail.startswith("_op"):
                return True
            # delegating wrappers: self.<name>_inner / _<name> helpers
            # are covered because ast.walk sees the call, not the body —
            # require the hook in THIS function or a with self._op(...)
    return False


def check_fault_hooks(ctx: FileCtx) -> list[Finding]:
    out = []
    if ctx.path == "minio_tpu/storage/xlstorage.py":
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) and node.name == "XLStorage":
                for fn in node.body:
                    if not isinstance(fn, ast.FunctionDef):
                        continue
                    if fn.name.startswith("_") or fn.name in _XL_NON_IO:
                        continue
                    if _contains_hook(fn):
                        continue
                    out.append(Finding(
                        ctx.path, fn.lineno, "GL006",
                        f"storage op XLStorage.{fn.name} has no "
                        "fault-injection hook (self._op(...) span or "
                        "_fault.inject) — chaos tests cannot reach it",
                        token=fn.name, scope=ctx.scope_at(fn.lineno + 1)))
    elif ctx.path == "minio_tpu/dist/rpc.py":
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) and node.name == "RPCClient":
                for fn in node.body:
                    if isinstance(fn, ast.FunctionDef) and \
                            fn.name == "call" and not _contains_hook(fn):
                        out.append(Finding(
                            ctx.path, fn.lineno, "GL006",
                            "RPCClient.call has no fault-injection hook",
                            token="call",
                            scope=ctx.scope_at(fn.lineno + 1)))
    elif ctx.path == "minio_tpu/runtime/dispatch.py":
        if not any(isinstance(n, ast.Call) and
                   dotted(n.func).endswith("inject")
                   for n in ast.walk(ctx.tree)):
            out.append(Finding(
                ctx.path, 1, "GL006",
                "dispatch has no kernel-layer fault-injection hook "
                "(_fault.inject('kernel', ...) at the flush boundary)",
                token="kernel-flush"))
        # every dispatch entry point funnels through _submit with an op
        # registered in _OP_NAME — that is what guarantees the flush-
        # boundary inject hook (and the kernel metrics/trace naming)
        # covers it; an unregistered op string is a new entry point that
        # dodged the funnel's contracts
        op_names: set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and \
                    any(dotted(t) == "_OP_NAME" for t in node.targets) \
                    and isinstance(node.value, ast.Dict):
                op_names = {k.value for k in node.value.keys
                            if isinstance(k, ast.Constant)}
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and
                    dotted(node.func).endswith("_submit")):
                continue
            if len(node.args) >= 3 and \
                    isinstance(node.args[2], ast.Constant) and \
                    node.args[2].value not in op_names:
                out.append(Finding(
                    ctx.path, node.lineno, "GL006",
                    f"dispatch entry point submits op "
                    f"{node.args[2].value!r} that is not registered in "
                    "_OP_NAME — fault-injection coverage, kernel "
                    "metrics and trace naming all key on it",
                    token=str(node.args[2].value),
                    scope=ctx.scope_at(node.lineno)))
    return out


# --------------------------------------------------------------------------
# GL007 — no bare/swallowed exceptions in daemon threads

_DAEMON_FN_RE = re.compile(r"(^|\.)(_?run|_?loop|[a-z0-9_]*_loop|"
                           r"_worker|_probe_loop)$")
_BROAD = {"Exception", "BaseException"}


def _daemon_targets(tree: ast.AST) -> set[str]:
    """Function names passed as Thread(target=...) in this module."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                dotted(node.func).endswith("Thread"):
            for kw in node.keywords:
                if kw.arg == "target":
                    d = dotted(kw.value)
                    if d:
                        out.add(d.rsplit(".", 1)[-1])
    return out


def _catches_broad(h: ast.ExceptHandler) -> bool:
    t = h.type
    if t is None:
        return False
    names = [t] if not isinstance(t, ast.Tuple) else t.elts
    return any(dotted(n).rsplit(".", 1)[-1] in _BROAD for n in names)


def check_swallowed_exceptions(ctx: FileCtx) -> list[Finding]:
    daemons = _daemon_targets(ctx.tree)
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            out.append(Finding(
                ctx.path, node.lineno, "GL007",
                "bare `except:` also swallows KeyboardInterrupt/"
                "SystemExit — catch Exception (and handle or log it)",
                token="bare-except", scope=ctx.scope_at(node.lineno)))
            continue
        if not _catches_broad(node):
            continue
        body_is_noop = all(isinstance(s, (ast.Pass, ast.Continue))
                           for s in node.body)
        if not body_is_noop:
            continue
        scope = ctx.scope_at(node.lineno)
        leaf = scope.rsplit(".", 1)[-1] if scope else ""
        in_daemon = any(seg in daemons for seg in scope.split(".")) or \
            bool(_DAEMON_FN_RE.search(leaf))
        if in_daemon:
            out.append(Finding(
                ctx.path, node.lineno, "GL007",
                "daemon thread swallows Exception with a bare pass — a "
                "persistent failure loops silently forever; log or "
                "count it",
                token=f"swallow:{leaf}", scope=scope))
    return out


# --------------------------------------------------------------------------
# GL008 — every dynamic config KVS key documented


def check_config_keys_documented(ctx: FileCtx) -> list[Finding]:
    if ctx.path != "minio_tpu/config/kvs.py":
        return []
    subsystems: dict[str, list[tuple[str, str, int]]] = {}
    dynamic: set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign):
            names = {dotted(t) for t in node.targets}
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            names = {dotted(node.target)}
        else:
            continue
        if "SUB_SYSTEMS" in names and isinstance(node.value, ast.Dict):
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant) and
                        isinstance(v, ast.Dict)):
                    continue
                entries = []
                for kk, vv in zip(v.keys, v.values):
                    if not isinstance(kk, ast.Constant):
                        continue
                    env = ""
                    if isinstance(vv, ast.Call):
                        for kw in vv.keywords:
                            if kw.arg == "env" and \
                                    isinstance(kw.value, ast.Constant):
                                env = kw.value.value
                    entries.append((kk.value, env, kk.lineno))
                subsystems[k.value] = entries
        elif "DYNAMIC" in names and isinstance(node.value, ast.Set):
            dynamic = {e.value for e in node.value.elts
                       if isinstance(e, ast.Constant)}
    docs = []
    docs_dir = os.path.join(REPO_ROOT, "docs")
    try:
        for f in sorted(os.listdir(docs_dir)):
            if f.endswith(".md"):
                with open(os.path.join(docs_dir, f),
                          encoding="utf-8") as fh:
                    docs.append(fh.read())
    except OSError:
        pass
    doc_text = "\n".join(docs)
    out = []
    for subsys in sorted(dynamic):
        for key, env, line in subsystems.get(subsys, []):
            if f"{subsys}.{key}" in doc_text or \
                    (env and env in doc_text):
                continue
            out.append(Finding(
                ctx.path, line, "GL008",
                f"dynamic config key {subsys}.{key} (env {env or '—'}) "
                "is not documented anywhere under docs/",
                token=f"{subsys}.{key}"))
    return out


# --------------------------------------------------------------------------
# GL009 — bare os.replace/os.rename outside the durable commit helper

#: the one module allowed to rename directly — it IS the policy point
_DURABILITY_HELPER = "minio_tpu/storage/durability.py"


def check_bare_replace(ctx: FileCtx) -> list[Finding]:
    """Every commit-by-rename in minio_tpu/ must ride
    ``storage.durability.durable_replace`` so the dynamic fsync policy
    (``durability.fsync`` / ``MINIO_TPU_FSYNC``) applies to it — a bare
    ``os.replace`` silently opts its data out of the durability plane
    (docs/durability.md)."""
    if not ctx.path.startswith("minio_tpu/") or \
            ctx.path == _DURABILITY_HELPER:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d not in ("os.replace", "os.rename"):
            continue
        out.append(Finding(
            ctx.path, node.lineno, "GL009",
            f"bare {d}() — commit through storage.durability."
            "durable_replace so the fsync policy (durability.fsync / "
            "MINIO_TPU_FSYNC) covers this write",
            token=_unparse(node, 40), scope=ctx.scope_at(node.lineno)))
    return out


# --------------------------------------------------------------------------
# GL010 — the zero-copy invariant: no host hashing / bytes copies on the
# PUT/GET hot path

#: The registered data-plane hot functions (nested defs inherit via
#: qualname prefix). The zero-copy PUT/GET pipeline's contract is that
#: these never construct a hashlib object, call .digest()/.hexdigest(),
#: or materialize payload copies via bytes()/.tobytes() — payload hashing
#: belongs to the device/native pipeline, and the ONLY host escape is a
#: helper whose name carries the ``_fallback`` marker (or HashReader's
#: _ingest compat funnel), which this checker exempts by construction.
_HOT_PATH_FUNCS: dict[str, tuple[str, ...]] = {
    "minio_tpu/erasure/streaming.py": (
        "erasure_encode", "erasure_decode", "_read_full",
        "_read_full_into", "_ParallelReader.read_block",
    ),
    "minio_tpu/utils/hashreader.py": (
        "HashReader.read", "HashReader.readinto",
    ),
    "minio_tpu/objectlayer/erasure_objects.py": (
        "ErasureObjects._put_object_inner",
        "ErasureObjects._get_object_inner",
    ),
    "minio_tpu/objectlayer/multipart.py": (
        "MultipartMixin.put_object_part",
    ),
    # device-workloads hot paths (ISSUE 8): SSE package streams and the
    # Select scan consumer — crypto/hash work belongs to the dispatch
    # lane (chacha kernel + batched numpy poly), not ad-hoc host calls
    "minio_tpu/crypto/sse.py": (
        "EncryptReader.readinto", "EncryptReader._fill",
        "DecryptWriter.write", "DecryptWriter.feed", "DecryptWriter._open",
        "DecryptWriter.drain", "RangeDecryptWriter.write",
        "_Staging.put", "_Staging.release",
    ),
    "minio_tpu/s3select/device.py": (
        "DeviceScan.rows", "DeviceScan._codes_for",
    ),
}


def check_hot_path_host_copies(ctx: FileCtx) -> list[Finding]:
    """GL010: the zero-copy PUT/GET invariant is enforced, not
    conventional — host-side ``hashlib`` constructions, ``.digest()`` /
    ``.hexdigest()`` calls, and ``bytes()`` / ``.tobytes()`` payload
    copies are banned inside the registered hot-path functions. Host
    hashing lives in the sanctioned fallback helpers (``*_fallback``
    nested helpers, HashReader's ``_ingest`` funnel, the bitrot module)
    which stay OUTSIDE the registry (docs/static-analysis.md)."""
    hot = _HOT_PATH_FUNCS.get(ctx.path)
    if not hot:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        scope = ctx.scope_at(node.lineno)
        if not scope or not any(
                scope == h or scope.startswith(h + ".") for h in hot):
            continue
        if "_fallback" in scope.rsplit(".", 1)[-1] or "_fallback." in scope:
            continue  # sanctioned nested fallback helper
        bad = None
        if isinstance(node.func, ast.Attribute):
            # attr-name match, not dotted(): the receiver may be a
            # subscript (shards[i].tobytes()) dotted() can't resolve
            attr = node.func.attr
            d = dotted(node.func) or f"….{attr}"
            if d.startswith("hashlib."):
                bad = f"host hash construction {d}()"
            elif attr in ("digest", "hexdigest"):
                bad = f"host digest call {d}()"
            elif attr == "tobytes":
                bad = f"{d}() payload copy"
        else:
            d = dotted(node.func)
            if d == "bytes" and node.args:
                bad = "bytes() payload copy"
        if bad is None:
            continue
        out.append(Finding(
            ctx.path, node.lineno, "GL010",
            f"{bad} on the PUT/GET hot path — hash/copy work belongs to "
            "the device/native pipeline; host escapes go through a "
            "sanctioned *_fallback helper (docs/static-analysis.md)",
            token=_unparse(node, 40), scope=scope))
    return out


# --------------------------------------------------------------------------
# GL011 — dispatch flush routes must emit paired timeline flush events

#: the flush route functions every _OP_NAME op flows through — each
#: must hand its items the paired flush_start/flush_end callback
_FLUSH_ROUTES = ("_flush_cpu", "_flush_device")
#: the sanctioned pairing helper (emits flush_start inline, flush_end
#: from the last item's done callback)
_TL_HELPER = "_tl_flush_cb"


def check_timeline_flush_pairs(ctx: FileCtx) -> list[Finding]:
    """GL011: the flight recorder's core invariant — every op registered
    in ``_OP_NAME`` executes through ``_flush_cpu``/``_flush_device``,
    so BOTH route functions must obtain the paired timeline callback
    from ``_tl_flush_cb`` (which itself must emit the ``flush_start``
    and ``flush_end`` literals). A route that skips the pairing leaves
    holes in the exported timeline and under-integrates that lane's
    busy ratio — silently wrong utilization, not a crash, which is why
    it's a lint and not a test."""
    if ctx.path != "minio_tpu/runtime/dispatch.py":
        return []
    out = []
    op_names: set[str] = set()
    helper: ast.FunctionDef | None = None
    routes: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and \
                any(dotted(t) == "_OP_NAME" for t in node.targets) and \
                isinstance(node.value, ast.Dict):
            op_names = {k.value for k in node.value.keys
                        if isinstance(k, ast.Constant)}
        elif isinstance(node, ast.FunctionDef):
            if node.name == _TL_HELPER:
                helper = node
            elif node.name in _FLUSH_ROUTES:
                routes[node.name] = node
    if not op_names:
        return []  # no registry: GL006 reports the real problem
    if helper is None:
        out.append(Finding(
            ctx.path, 1, "GL011",
            f"dispatch has no {_TL_HELPER} helper — the flush routes "
            "cannot emit paired timeline flush start/end events for "
            f"the registered ops {sorted(op_names)}",
            token=_TL_HELPER))
    else:
        # only literals passed to record()-shaped calls count — the
        # helper's DOCSTRING mentions both event names, and a deleted
        # record("flush_end", ...) must not hide behind it
        lits: set[str] = set()
        for n in ast.walk(helper):
            if isinstance(n, ast.Call) and \
                    dotted(n.func).rsplit(".", 1)[-1] == "record":
                lits.update(a.value for a in n.args
                            if isinstance(a, ast.Constant) and
                            isinstance(a.value, str))
        missing = {"flush_start", "flush_end"} - lits
        if missing:
            out.append(Finding(
                ctx.path, helper.lineno, "GL011",
                f"{_TL_HELPER} does not emit {sorted(missing)} — flush "
                "pairing is broken for every route that relies on it",
                token=f"{_TL_HELPER}:{'+'.join(sorted(missing))}",
                scope=ctx.scope_at(helper.lineno + 1)))
    for name in _FLUSH_ROUTES:
        fn = routes.get(name)
        if fn is None:
            continue  # a missing route function is not this checker's
        if any(isinstance(n, ast.Call) and
               dotted(n.func).rsplit(".", 1)[-1] == _TL_HELPER
               for n in ast.walk(fn)):
            continue
        out.append(Finding(
            ctx.path, fn.lineno, "GL011",
            f"flush route {name} never calls {_TL_HELPER} — its "
            "flushes leave no paired flush_start/flush_end timeline "
            "events, so the exported timeline has holes and the lane "
            "busy-ratio under-integrates",
            token=name, scope=ctx.scope_at(fn.lineno + 1)))
    return out


# --------------------------------------------------------------------------
# GL012 — the SLO plane's method contract

#: the one module that evaluates SLOs
_SLO_MODULE = "minio_tpu/obs/slo.py"
#: call names that smell like ad-hoc percentile math — SLO evaluation
#: must ride obs/latency.Window so the method can never diverge from
#: every other online percentile in the tree. Matching is by call LEAF
#: name (`statistics.quantiles`, `np.percentile`, a local `median`
#: helper) — flagging every statistics/numpy call would be broader
#: than the documented contract and fail unrelated math.
_PERCENTILE_CALLS = {"quantiles", "quantile", "percentile", "median",
                     "median_low", "median_high", "nanpercentile",
                     "nanquantile"}


def check_slo_plane(ctx: FileCtx) -> list[Finding]:
    """GL012: (a) every objective class name in ``CLASSES`` must appear
    in docs/observability.md — the SLO taxonomy is operator-facing and
    an undocumented class renders as unexplained metric labels; (b) the
    module must take its windows from ``obs/latency.Window`` (imported
    from ``.latency``) and must not shadow it or compute percentiles
    with statistics/numpy helpers — two percentile methods in one tree
    means the SLO verdict and the latency metrics can disagree about
    the same request."""
    if ctx.path != _SLO_MODULE:
        return []
    out = []
    classes: list[tuple[str, int]] = []
    imports_latency_window = False
    calls_window = None
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and \
                any(dotted(t) == "CLASSES" for t in node.targets) and \
                isinstance(node.value, (ast.Tuple, ast.List)):
            classes = [(e.value, e.lineno) for e in node.value.elts
                       if isinstance(e, ast.Constant) and
                       isinstance(e.value, str)]
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[-1] == "latency" and \
                    any(a.name == "Window" for a in node.names):
                imports_latency_window = True
        elif isinstance(node, ast.ClassDef) and node.name == "Window":
            out.append(Finding(
                ctx.path, node.lineno, "GL012",
                "local class Window shadows obs/latency.Window — SLO "
                "windows must be the shared sliding-window histogram, "
                "not a lookalike",
                token="Window", scope=ctx.scope_at(node.lineno)))
        elif isinstance(node, ast.Call):
            fn = dotted(node.func)
            leaf = fn.rsplit(".", 1)[-1]
            if leaf in _PERCENTILE_CALLS:
                out.append(Finding(
                    ctx.path, node.lineno, "GL012",
                    f"ad-hoc percentile math ({fn}) in the SLO plane — "
                    "evaluate from obs/latency.Window so the SLO "
                    "verdict and the latency metrics share one method",
                    token=fn, scope=ctx.scope_at(node.lineno)))
            elif fn == "Window" and calls_window is None:
                calls_window = node.lineno
    if not classes:
        out.append(Finding(
            ctx.path, 1, "GL012",
            "obs/slo.py declares no module-level CLASSES tuple — the "
            "objective taxonomy must be a greppable literal",
            token="CLASSES"))
    else:
        doc_path = os.path.join(REPO_ROOT, "docs", "observability.md")
        try:
            with open(doc_path, encoding="utf-8") as f:
                doc = f.read()
        except OSError:
            doc = ""
        for name, line in classes:
            if name not in doc:
                out.append(Finding(
                    ctx.path, line, "GL012",
                    f"SLO objective class {name!r} is not documented "
                    "in docs/observability.md",
                    token=name, scope=ctx.scope_at(line)))
    if calls_window is not None and not imports_latency_window:
        out.append(Finding(
            ctx.path, calls_window, "GL012",
            "Window(...) used without importing Window from "
            ".latency — SLO windows must come from obs/latency.py",
            token="Window-import",
            scope=ctx.scope_at(calls_window)))
    return out


# --------------------------------------------------------------------------
# GL013 — every dispatch op branch in _flush_device carries a mesh route

#: the exemption registry _flush_device's ops may opt out through — an
#: EXPLICIT set literal in dispatch.py, so shipping a device-only op is
#: a visible, reviewable line, not an accident (the way select_scan
#: shipped without a mesh route in PR 8)
_MESH_EXEMPT_NAME = "_MESH_SINGLE_DEVICE_OPS"


def _op_branch_consts(test: ast.AST) -> set[str] | None:
    """The op constants a ``b.op == 'x'`` / ``b.op in (...)`` test
    selects, or None when the test is not an op dispatch."""
    if not (isinstance(test, ast.Compare) and len(test.ops) == 1 and
            dotted(test.left).endswith(".op")):
        return None
    cmp = test.comparators[0]
    if isinstance(test.ops[0], ast.Eq) and isinstance(cmp, ast.Constant) \
            and isinstance(cmp.value, str):
        return {cmp.value}
    if isinstance(test.ops[0], ast.In) and \
            isinstance(cmp, (ast.Tuple, ast.List, ast.Set)):
        vals = {e.value for e in cmp.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)}
        if vals:
            return vals
    return None


def check_mesh_routes(ctx: FileCtx) -> list[Finding]:
    """GL013: the mesh-route contract for the dispatch plane — every
    ``b.op`` branch inside ``_flush_device`` must either call
    ``sharded_batched`` under an arm whose condition involves the mesh
    (``if mesh is not None`` / ``if use_mesh``), or every op the branch
    handles must appear in the ``_MESH_SINGLE_DEVICE_OPS`` exemption
    registry. Ops not matched by any explicit test are attributed to
    the chain's ``else`` branch. Without this gate a new op PR ships
    device-only silently (select_scan did exactly that in PR 8 — the
    8-chip mesh carried zero Select traffic for two rounds and nothing
    failed)."""
    if ctx.path != "minio_tpu/runtime/dispatch.py":
        return []
    op_names: set[str] = set()
    exempt: set[str] | None = None
    exempt_line = 1
    flush_fn: ast.FunctionDef | None = None
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.Assign, ast.AnnAssign)) and \
                node.value is not None:
            names = {dotted(t) for t in node.targets} \
                if isinstance(node, ast.Assign) else {dotted(node.target)}
            if "_OP_NAME" in names and isinstance(node.value, ast.Dict):
                op_names = {k.value for k in node.value.keys
                            if isinstance(k, ast.Constant)}
            elif _MESH_EXEMPT_NAME in names:
                exempt = {sub.value for sub in ast.walk(node.value)
                          if isinstance(sub, ast.Constant) and
                          isinstance(sub.value, str)}
                exempt_line = node.lineno
        elif isinstance(node, ast.FunctionDef) and \
                node.name == "_flush_device":
            flush_fn = node
    if not op_names or flush_fn is None:
        return []  # GL006/GL011 report the real problem
    out = []
    if exempt is None:
        out.append(Finding(
            ctx.path, exempt_line, "GL013",
            f"dispatch declares no {_MESH_EXEMPT_NAME} registry — "
            "single-device exemptions must be an explicit, reviewable "
            "set literal",
            token=_MESH_EXEMPT_NAME))
        exempt = set()
    # collect the op-dispatch branches: each If whose test compares
    # b.op, chains of elifs walked, the trailing else attributed to
    # every registry op no explicit test claims
    branches: list[tuple[set[str] | None, list, int]] = []
    tested: set[str] = set()
    consumed: set[int] = set()

    def walk_chain(if_node: ast.If) -> bool:
        ops = _op_branch_consts(if_node.test)
        if ops is None:
            return False
        consumed.add(id(if_node))
        branches.append((ops, if_node.body, if_node.body[0].lineno))
        tested.update(ops)
        rest = if_node.orelse
        if len(rest) == 1 and isinstance(rest[0], ast.If) and \
                walk_chain(rest[0]):
            return True
        if rest:
            branches.append((None, rest, rest[0].lineno))
        return True

    for node in ast.walk(flush_fn):
        if isinstance(node, ast.If) and id(node) not in consumed:
            walk_chain(node)

    def has_mesh_sharded(stmts: list) -> bool:
        for st in stmts:
            for sub in ast.walk(st):
                if isinstance(sub, ast.If) and \
                        "mesh" in _unparse(sub.test, 200):
                    for inner in ast.walk(sub):
                        if isinstance(inner, ast.Call) and \
                                dotted(inner.func).rsplit(".", 1)[-1] == \
                                "sharded_batched":
                            return True
        return False

    default_ops = op_names - tested
    saw_default = any(ops is None for ops, _, _ in branches)
    for ops, body, line in branches:
        ops = default_ops if ops is None else ops & op_names
        if not ops or has_mesh_sharded(body):
            continue
        for op in sorted(ops - exempt):
            out.append(Finding(
                ctx.path, line, "GL013",
                f"dispatch op {op!r} branch in _flush_device has no "
                "mesh route — call sharded_batched under a "
                "mesh-guarded arm or register the op in "
                f"{_MESH_EXEMPT_NAME}",
                token=f"mesh-route:{op}",
                scope=ctx.scope_at(line)))
    if default_ops and not saw_default:
        # registry ops no branch handles at all: same contract
        for op in sorted(default_ops - exempt):
            out.append(Finding(
                ctx.path, flush_fn.lineno, "GL013",
                f"dispatch op {op!r} is registered in _OP_NAME but no "
                "_flush_device branch (and no else) handles it — it "
                "cannot have a mesh route",
                token=f"mesh-route:{op}",
                scope=ctx.scope_at(flush_fn.lineno + 1)))
    return out


# --------------------------------------------------------------------------
# GL014 — dist/ RPC plane: chaos-reachable entry points, bounded waits

_GL014_HTTP_VERBS = {"post", "get", "put", "delete", "request", "head"}
_GL014_HTTP_RECV_RE = re.compile(r"(^|[._])(session|http|requests)($|[._])",
                                 re.IGNORECASE)


def check_dist_rpc_bounds(ctx: FileCtx) -> list[Finding]:
    """GL014: the node fault layer (docs/fault.md) injects at
    ``RPCClient.call`` — so every dist/ client entry point must funnel
    through it (no direct ``requests`` use outside rpc.py), every HTTP
    call must carry a bounded ``timeout=`` (a partitioned peer must
    fail the caller, not hang it), ``.wait()``/``.recv()`` must be
    bounded, and ``RPCClient.call`` itself must consult BOTH the
    ``rpc`` (per-call) and ``node`` (whole-peer) fault layers."""
    if not ctx.path.startswith("minio_tpu/dist/"):
        return []
    out: list[Finding] = []
    is_rpc_py = ctx.path == "minio_tpu/dist/rpc.py"
    if not is_rpc_py:
        for node in ast.walk(ctx.tree):
            mods: list[str] = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            if any(m == "requests" or m.startswith("requests.")
                   for m in mods):
                if ctx.suppressed(node.lineno, "GL014"):
                    continue
                out.append(Finding(
                    ctx.path, node.lineno, "GL014",
                    "direct `requests` use outside dist/rpc.py — dist "
                    "clients must funnel through RPCClient.call so the "
                    "node-layer fault hooks and offline marking cover "
                    "them", token="requests-import",
                    scope=ctx.scope_at(node.lineno)))
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        tail = d.rsplit(".", 1)[-1]
        recv = d.rsplit(".", 1)[0] if "." in d else ""
        if tail in _GL014_HTTP_VERBS and \
                _GL014_HTTP_RECV_RE.search(recv):
            if not any(kw.arg == "timeout" for kw in node.keywords):
                if ctx.suppressed(node.lineno, "GL014"):
                    continue
                out.append(Finding(
                    ctx.path, node.lineno, "GL014",
                    f"HTTP call `{_unparse(node.func)}(...)` without a "
                    "timeout= — a hung peer would pin this caller "
                    "forever (no unbounded waits on the dist plane)",
                    token=f"http:{tail}",
                    scope=ctx.scope_at(node.lineno)))
        if tail in ("wait", "recv") and not node.args and \
                not node.keywords and recv:
            if ctx.suppressed(node.lineno, "GL014"):
                continue
            out.append(Finding(
                ctx.path, node.lineno, "GL014",
                f"unbounded `{_unparse(node.func)}()` on the dist "
                "plane — pass a timeout so a dead peer cannot park "
                "this thread forever",
                token=f"wait:{recv}", scope=ctx.scope_at(node.lineno)))
    if is_rpc_py:
        layers: set[str] = set()
        call_fn = None
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) and node.name == "RPCClient":
                for fn in node.body:
                    if isinstance(fn, ast.FunctionDef) and \
                            fn.name == "call":
                        call_fn = fn
        if call_fn is not None:
            for node in ast.walk(call_fn):
                if isinstance(node, ast.Call) and \
                        dotted(node.func).endswith("inject") and \
                        node.args and isinstance(node.args[0],
                                                 ast.Constant):
                    layers.add(node.args[0].value)
        for layer in ("rpc", "node"):
            if call_fn is not None and layer not in layers:
                out.append(Finding(
                    ctx.path, call_fn.lineno, "GL014",
                    f"RPCClient.call carries no {layer!r}-layer fault "
                    "hook — the chaos matrix cannot reach the "
                    f"{'whole-peer' if layer == 'node' else 'per-call'}"
                    " injection point",
                    token=f"hook:{layer}",
                    scope=ctx.scope_at(call_fn.lineno + 1)))
    return out


# --------------------------------------------------------------------------
# GL015 — interactive-class code paths block only through the sanctioned
# async-completion helper

#: registered interactive-class code paths (nested defs inherit via
#: qualname prefix): the heal-shard rebuild and degraded-GET reconstruct
#: consumers that the interactive device lane (ISSUE 13) keeps
#: latency-bounded. A bare ``.result()`` here is an UNOBSERVED blocking
#: wait on the latency tier — the exact failure shape that hid the 20 s
#: device heal-p99 behind "rebuild" wall time until PR 9's attribution
#: split it. Every wait goes through
#: ``runtime/completion.await_result`` (counted + timed per op).
_GL015_INTERACTIVE_PATHS: dict[str, tuple[str, ...]] = {
    "minio_tpu/erasure/streaming.py": (
        "erasure_heal", "erasure_decode", "_ParallelReader.read_block",
    ),
}
#: the sanctioned helper's module — exempt by construction (it IS the
#: one place those paths may block)
_GL015_HELPER_MODULE = "minio_tpu/runtime/completion.py"
_GL015_HELPER = "await_result"


def check_interactive_blocking(ctx: FileCtx) -> list[Finding]:
    """GL015: inside the registered interactive-class functions
    (including their nested defs), any ``X.result(...)`` attribute call
    is a finding — the code must wait via
    ``runtime.completion.await_result`` instead. Calls to the helper
    itself obviously don't match (it isn't spelled ``.result``), and
    the helper module is out of scope."""
    if ctx.path == _GL015_HELPER_MODULE:
        return []
    hot = _GL015_INTERACTIVE_PATHS.get(ctx.path)
    if not hot:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and
                isinstance(node.func, ast.Attribute) and
                node.func.attr == "result"):
            continue
        scope = ctx.scope_at(node.lineno)
        if not scope or not any(
                scope == h or scope.startswith(h + ".") for h in hot):
            continue
        out.append(Finding(
            ctx.path, node.lineno, "GL015",
            f"blocking {_unparse(node.func, 40)}() on an "
            "interactive-class code path — wait via "
            f"runtime.completion.{_GL015_HELPER}(...) (the sanctioned "
            "async-completion helper) so the wait is counted and timed "
            "on the latency tier",
            token=_unparse(node.func, 40), scope=scope))
    return out


# --------------------------------------------------------------------------
# GL016 — every thread construction carries a name


def check_thread_names(ctx: FileCtx) -> list[Finding]:
    """GL016: the continuous profiler (``obs/profiler.py``) classifies
    every sample by thread ROLE, resolved through a name registry — an
    unnamed ``threading.Thread`` can only ever classify as ``other``,
    silently degrading every profile and the subsystem shares built
    on it. Any ``Thread(...)`` construction under
    ``minio_tpu/`` without a ``name=`` keyword is a finding (Thread
    SUBCLASS constructions pass their name to ``super().__init__`` and
    are matched by their own class name, so they stay out of scope)."""
    if not ctx.path.startswith("minio_tpu/"):
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d.rsplit(".", 1)[-1] != "Thread":
            continue
        if any(kw.arg == "name" for kw in node.keywords):
            continue
        out.append(Finding(
            ctx.path, node.lineno, "GL016",
            f"unnamed thread {_unparse(node, 40)} — pass name= so the "
            "profiler's thread-role classification (obs/profiler.py) "
            "can attribute its samples",
            token=_unparse(node.func, 40),
            scope=ctx.scope_at(node.lineno)))
    return out


# --------------------------------------------------------------------------
# GL017 — every compile site routes through the tracked-jit wrapper


#: the wrapper's own module: the ONE sanctioned jax.jit construction
#: site, exempt by construction
_GL017_WRAPPER_MODULE = "minio_tpu/obs/device.py"
#: pallas_call registry: the kernels live INSIDE tracked-jit-compiled
#: functions (the enclosing jit wrapper is the counted compile unit, so
#: the inner pallas_call can never compile untracked) — path ->
#: sanctioned enclosing-scope qualnames. A pallas_call anywhere else is
#: a finding until its scope is registered here (a reviewed decision,
#: like GL010's _HOT_PATH_FUNCS) or pragma-suppressed.
_GL017_PALLAS_SCOPES: dict[str, tuple[str, ...]] = {
    "minio_tpu/ops/rs_pallas.py": (
        "gf_matmul_pallas", "_gf_matmul_batched", "_static_call.mm",
        "_static_batch_call.mm"),
    "minio_tpu/ops/scan_pallas.py": ("scan_fn_for.run",),
    "minio_tpu/ops/chacha_pallas.py": ("_jitted.run",
                                       "multi_fn_for.run"),
    "minio_tpu/ops/mur3_pallas.py": ("_jitted.run",),
}
_GL017_JIT_NAMES = {"jax.jit", "jit"}


def _gl017_finding(ctx: FileCtx, lineno: int, what: str,
                   token: str) -> Finding:
    return Finding(
        ctx.path, lineno, "GL017",
        f"untracked compile site {what} — route it through "
        "obs.device.tracked_jit so the device plane counts and times "
        "the compilation (or register/suppress the site explicitly)",
        token=token, scope=ctx.scope_at(lineno))


def check_tracked_compiles(ctx: FileCtx) -> list[Finding]:
    """GL017: any ``jax.jit(...)`` call, ``functools.partial(jax.jit,
    ...)`` configuration, bare ``@jax.jit`` decorator, or
    ``pl.pallas_call(...)`` under ``minio_tpu/`` that is not the
    wrapper module itself is a finding — except pallas_call sites whose
    enclosing scope is registered in ``_GL017_PALLAS_SCOPES`` (kernels
    compiled inside a tracked-jit function)."""
    if not ctx.path.startswith("minio_tpu/") or \
            ctx.path == _GL017_WRAPPER_MODULE:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        # bare @jax.jit decorators are Attribute/Name nodes, not Calls
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call) and \
                        dotted(dec) in _GL017_JIT_NAMES:
                    out.append(_gl017_finding(
                        ctx, dec.lineno, f"@{dotted(dec)} decorator",
                        dotted(dec)))
            continue
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d in _GL017_JIT_NAMES:
            out.append(_gl017_finding(ctx, node.lineno, f"{d}(...)", d))
            continue
        if d.rsplit(".", 1)[-1] == "partial" and node.args and \
                dotted(node.args[0]) in _GL017_JIT_NAMES:
            out.append(_gl017_finding(
                ctx, node.lineno, "functools.partial(jax.jit, ...)",
                "partial(jax.jit)"))
            continue
        if d.rsplit(".", 1)[-1] == "pallas_call":
            scope = ctx.scope_at(node.lineno)
            allowed = _GL017_PALLAS_SCOPES.get(ctx.path, ())
            if scope in allowed or any(
                    scope.startswith(a + ".") for a in allowed):
                continue
            out.append(_gl017_finding(
                ctx, node.lineno, f"{d}(...) outside the registered "
                "tracked-jit scopes", d))
    return out


# --------------------------------------------------------------------------
# GL018 — request-derived metric labels fold through bucketstats.fold_label

#: label keys whose values are tenant-chosen strings: a raw one creates
#: one Prometheus series per distinct request value (unbounded).
_GL018_SENSITIVE = {"bucket", "key", "user", "tenant", "object"}

#: metric-emitting call leaves whose keyword args become label pairs
_GL018_EMITTERS = {"inc", "observe", "_metric"}

#: the fold helper itself (and its home module, which is exempt — it IS
#: the cardinality bound)
_GL018_FOLD = "fold_label"
_GL018_HOME = "minio_tpu/obs/bucketstats.py"

_GL018_FRAG_RE = re.compile(
    r"(?P<label>" + "|".join(sorted(_GL018_SENSITIVE)) + r')="$')


def _gl018_folded_names(tree: ast.AST) -> set[str]:
    """Names assigned from ``fold_label(...)`` anywhere in the file count
    as folded (the bind-then-interpolate pattern: ``lab =
    fold_label(b)``; ``f'...bucket="{_esc(lab)}"...'``) — same
    assignment-tracking shape GL005 uses for ``wrap_ctx``."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call) and \
                dotted(node.value.func).rsplit(".", 1)[-1] == _GL018_FOLD:
            out.update(d for d in (dotted(t) for t in node.targets) if d)
    return out


def _gl018_is_folded(expr: ast.AST, folded: set[str]) -> bool:
    """True when ``expr``'s subtree routes through the fold helper: a
    ``fold_label(...)`` call or a Name previously bound to one."""
    for n in ast.walk(expr):
        if isinstance(n, ast.Call) and \
                dotted(n.func).rsplit(".", 1)[-1] == _GL018_FOLD:
            return True
        if isinstance(n, (ast.Name, ast.Attribute)) and \
                dotted(n) in folded:
            return True
    return False


def check_bounded_request_labels(ctx: FileCtx) -> list[Finding]:
    """GL018: two surfaces leak request strings into metric labels —
    (a) emitter keyword args (``mx.inc(..., bucket=b)``) and (b)
    hand-rendered exposition f-strings (``f'...bucket="{b}"...'``, the
    collector-group idiom). Both must pass a constant, a
    ``fold_label(...)`` call, or a name bound from one."""
    if not ctx.path.startswith("minio_tpu/") or ctx.path == _GL018_HOME:
        return []
    folded = _gl018_folded_names(ctx.tree)
    out = []
    for node in ast.walk(ctx.tree):
        # Rule A — emitter call kwargs
        if isinstance(node, ast.Call) and \
                dotted(node.func).rsplit(".", 1)[-1] in _GL018_EMITTERS:
            for kw in node.keywords:
                if kw.arg not in _GL018_SENSITIVE:
                    continue
                if isinstance(kw.value, ast.Constant):
                    continue
                if _gl018_is_folded(kw.value, folded):
                    continue
                out.append(Finding(
                    ctx.path, node.lineno, "GL018",
                    f'request-derived label {kw.arg}='
                    f"{_unparse(kw.value, 40)} without "
                    "bucketstats.fold_label — unbounded series "
                    "cardinality (one per tenant-chosen name)",
                    token=f"{kw.arg}={_unparse(kw.value, 40)}",
                    scope=ctx.scope_at(node.lineno)))
        # Rule B — exposition f-strings: a text fragment ending in
        # `bucket="` etc. labels the NEXT interpolated value
        if isinstance(node, ast.JoinedStr):
            vals = node.values
            for i, frag in enumerate(vals[:-1]):
                if not (isinstance(frag, ast.Constant) and
                        isinstance(frag.value, str)):
                    continue
                m = _GL018_FRAG_RE.search(frag.value)
                if m is None:
                    continue
                nxt = vals[i + 1]
                if not isinstance(nxt, ast.FormattedValue):
                    continue
                if _gl018_is_folded(nxt.value, folded):
                    continue
                out.append(Finding(
                    ctx.path, node.lineno, "GL018",
                    f'f-string label {m.group("label")}='
                    f'"{{{_unparse(nxt.value, 40)}}}" without '
                    "bucketstats.fold_label — unbounded series "
                    "cardinality (one per tenant-chosen name)",
                    token=f'{m.group("label")}={_unparse(nxt.value, 40)}',
                    scope=ctx.scope_at(node.lineno)))
    return out


# --------------------------------------------------------------------------
# GL019 — replication/lifecycle async planes: bounded, chaos-reachable

#: the async-plane modules GL019 covers (GL014's contract extended
#: beyond dist/): replication shipping + the ILM tier targets
_GL019_FILES = {
    "minio_tpu/bucket/replicate.py",
    "minio_tpu/bucket/replication.py",
    "minio_tpu/bucket/tiers.py",
    "minio_tpu/bucket/transition.py",
    "minio_tpu/bucket/lifecycle.py",
}

#: network-shipping attribute calls that must carry an explicit
#: ``timeout=`` (the peer RPC's default would silently unbound them
#: if someone removed the kwarg at a call site)
_GL019_SHIP_CALLS = {"replicate_object", "replicate_delete",
                     "replication_stats", "call", "urlopen"}


def check_async_plane_bounds(ctx: FileCtx) -> list[Finding]:
    """GL019: the replication + lifecycle planes stay bounded and
    chaos-reachable. Every network call (requests-style HTTP, the peer
    RPC ship methods, urlopen) carries ``timeout=`` — a wedged target
    must park the obligation for retry, never hang the worker or the
    scanner cycle. Every ``Tier*`` data-path class carries a
    fault-injection hook (``fault.inject("disk", <tier>, ...)`` — the
    chaos matrix kills tiers through the disk layer) and a deadline
    (``timeout=`` or the ``_bounded`` reaper helper)."""
    if ctx.path not in _GL019_FILES:
        return []
    out: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        tail = d.rsplit(".", 1)[-1]
        recv = d.rsplit(".", 1)[0] if "." in d else ""
        http_like = tail in _GL014_HTTP_VERBS and \
            _GL014_HTTP_RECV_RE.search(recv)
        ship_like = tail in _GL019_SHIP_CALLS and recv
        if not http_like and not ship_like:
            continue
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        if ctx.suppressed(node.lineno, "GL019"):
            continue
        out.append(Finding(
            ctx.path, node.lineno, "GL019",
            f"async-plane network call `{_unparse(node.func)}(...)` "
            "without a timeout= — a hung replication target or tier "
            "would pin the worker forever (the obligation must park "
            "for retry instead)",
            token=f"net:{tail}", scope=ctx.scope_at(node.lineno)))
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ClassDef) or \
                not node.name.startswith("Tier") or \
                node.name == "TierRegistry":
            continue
        has_hook = False
        has_deadline = False
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            d = dotted(sub.func)
            if d.endswith("inject") and sub.args and \
                    isinstance(sub.args[0], ast.Constant) and \
                    sub.args[0].value == "disk":
                has_hook = True
            if any(kw.arg == "timeout" for kw in sub.keywords) or \
                    "timeout" in d or d.endswith("_bounded"):
                has_deadline = True
        if not has_hook and not ctx.suppressed(node.lineno, "GL019"):
            out.append(Finding(
                ctx.path, node.lineno, "GL019",
                f"tier class {node.name} has no disk-layer fault hook "
                "(`fault.inject(\"disk\", <tier>, ...)`): the chaos "
                "matrix cannot fail its IO, so transition/restore "
                "retry paths are untestable",
                token=f"hook:{node.name}",
                scope=ctx.scope_at(node.lineno + 1)))
        if not has_deadline and not ctx.suppressed(node.lineno, "GL019"):
            out.append(Finding(
                ctx.path, node.lineno, "GL019",
                f"tier class {node.name} carries no deadline "
                "(timeout= kwarg or the _bounded reaper): a dead "
                "cold-storage mount would wedge the scanner cycle",
                token=f"deadline:{node.name}",
                scope=ctx.scope_at(node.lineno + 1)))
    return out


PER_FILE = [
    check_wall_duration,
    check_blocking_under_lock,
    check_bare_acquire,
    check_submit_wrap,
    check_fault_hooks,
    check_swallowed_exceptions,
    check_config_keys_documented,
    check_bare_replace,
    check_hot_path_host_copies,
    check_timeline_flush_pairs,
    check_slo_plane,
    check_mesh_routes,
    check_dist_rpc_bounds,
    check_interactive_blocking,
    check_thread_names,
    check_tracked_compiles,
    check_bounded_request_labels,
    check_async_plane_bounds,
]
from .program import check_whole_program  # noqa: E402 — needs Finding above

PROJECT = [check_metrics_documented, check_whole_program]
