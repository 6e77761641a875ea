"""Device-mesh execution for the batched erasure kernels (SURVEY.md §2.2
parallelism table; scaling model per the sharding recipe: pick a mesh,
annotate shardings, let XLA insert collectives).

Two first-class axes:

- **objects** — concurrent erasure blocks (the dispatch queue's batch
  dimension). EC math has no cross-object reduction, so sharding the batch
  axis over all local chips is embarrassingly parallel: XLA compiles one
  SPMD program with zero collectives and each chip encodes B/n blocks.
  This is the production path — ``DispatchQueue`` wraps every device
  flush in :func:`sharded_batched` when more than one device is visible.
- **shards** — the k data shards of one object split across devices, with
  the GF(256) XOR-accumulation completed by an ``all_gather`` + combine
  over ICI (tensor-parallel analogue). Used by :func:`build_sharded_step`,
  the full sharded encode+reconstruct step the driver's multichip dryrun
  compiles and runs.

Single-device hosts (a one-chip v5e) bypass all of this —
``object_mesh()`` returns None and the dispatch queue behaves exactly as
before.
"""
from __future__ import annotations

import threading
import weakref

import numpy as np

_lock = threading.Lock()
_mesh = None
_mesh_built = False


def object_mesh():
    """The cached 1-D ("objects",) Mesh over this process's addressable
    devices, or None when only one (or no) device is available.
    local_devices, not devices: in a multi-process setup the dispatch
    queue must only target devices it can feed."""
    global _mesh, _mesh_built
    if _mesh_built:
        return _mesh
    with _lock:
        if _mesh_built:
            return _mesh
        try:
            import jax
            from jax.sharding import Mesh
            devs = jax.local_devices()
            _mesh = Mesh(np.array(devs), ("objects",)) \
                if len(devs) > 1 else None
        except Exception:  # noqa: BLE001 — no backend at all
            _mesh = None
        _mesh_built = True
    return _mesh


def mesh_size() -> int:
    m = object_mesh()
    return int(m.devices.size) if m is not None else 1


def mesh_device(lane: int):
    """The device backing flush lane ``lane`` (mesh order), or None when
    no multi-device mesh exists — per-lane flushes pin their inputs here
    via jax.device_put so one erasure set's traffic occupies exactly one
    chip while siblings serve other sets."""
    m = object_mesh()
    if m is None:
        return None
    devs = m.devices.flatten()
    return devs[lane % devs.size]


def put_replicated(arr, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(arr, NamedSharding(mesh, PartitionSpec()))


def replicated_for(obj, attr: str, arr, mesh):
    """Replicate a per-object constant (e.g. a codec's encode masks) onto
    the mesh once and cache it ON the owning object — re-broadcasting
    every flush would add a transfer per launch, and a global cache keyed
    by id() would serve stale data after id reuse and pin device memory
    past the owner's lifetime."""
    cached = getattr(obj, attr, None)
    if cached is None or cached[0] is not mesh:
        cached = (mesh, put_replicated(arr, mesh))
        setattr(obj, attr, cached)
    return cached[1]


#: jit(shard_map(fn)) wrappers are cached ON THE FUNCTION OBJECT
#: itself (an attribute holding {(mesh, batch_args, out_batch): w}):
#: the old module dict keyed on id(fn) served a stale jitted executable
#: for a DIFFERENT function once the original was GC'd and its id
#: reused, and grew without bound, pinning every compiled program it
#: ever built (the same hazard replicated_for's docstring calls out for
#: constants). The wrapper references fn, so the attribute forms a pure
#: reference CYCLE — the gc frees both together when the last external
#: reference drops (an lru-evicted kernel factory result takes its
#: sharded wrappers with it). A WeakKeyDictionary could NOT express
#: this: its values hold strong references, and value→key would pin
#: every entry forever. ``_cached_fns`` (weak) only counts live owners
#: for tests/telemetry.
_CACHE_ATTR = "__mesh_shard_cache__"
_cached_fns: "weakref.WeakSet" = weakref.WeakSet()
_shard_cache_lock = threading.Lock()


def shard_cache_len() -> int:
    """Live functions owning sharded-wrapper caches (tests pin the GC
    behavior: entries must die with their fn)."""
    return len(_cached_fns)


def sharded_batched(fn, mesh, batch_args: tuple[bool, ...],
                    out_batch: int = 1):
    """jit(shard_map(fn)) over the ("objects",) mesh: args with True in
    ``batch_args`` shard their leading (batch) axis, others replicate;
    outputs shard the batch axis (``out_batch`` > 1 for tuple outputs).

    shard_map — not bare sharded inputs — because the batched kernels may
    lower to pallas_call, which XLA cannot auto-partition; under shard_map
    each device runs the kernel on its local block, which is exactly the
    semantics the objects axis needs (no cross-shard math)."""
    key = (mesh, batch_args, out_batch)
    per_fn = getattr(fn, _CACHE_ATTR, None)
    if per_fn is not None:
        w = per_fn.get(key)
        if w is not None:
            return w
    import jax
    from jax.sharding import PartitionSpec as P
    in_specs = tuple(P("objects") if b else P() for b in batch_args)
    out_specs = P("objects") if out_batch == 1 \
        else tuple(P("objects") for _ in range(out_batch))
    sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    from ..obs.device import tracked_jit
    w = tracked_jit(sm, op=f"mesh.{getattr(fn, '__name__', 'fn')}")
    try:  # bound methods / exotic callables: build uncached —
        with _shard_cache_lock:  # correctness over reuse
            per_fn = getattr(fn, _CACHE_ATTR, None)
            if per_fn is None:
                per_fn = {}
                setattr(fn, _CACHE_ATTR, per_fn)
            per_fn[key] = w
        _cached_fns.add(fn)
    except (AttributeError, TypeError):
        pass
    return w


def build_sharded_step(K: int, M: int, n_devices: int, sp: int | None = None,
                       devices=None):
    """The full sharded erasure step over a 2-D ("objects", "shards") mesh:
    batched encode (parity) + reconstruct (decode) with the per-device
    partial GF products XOR-combined across the shard axis over ICI.

    Returns (jitted_step, mesh). The step signature is
    ``step(enc_masks, dec_masks, packed_words)`` with shapes
    enc [8, M, K], dec [8, K, K], words uint32 [B, K, W]; B must divide by
    the objects axis and K by the shards axis.
    """
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from ..ops import rs_jax

    # ``devices``: an explicit device list — a described (unattached)
    # topology's, when the step is compiled for a chip that is not there
    devs = list(devices if devices is not None else jax.devices())[:n_devices]
    if len(devs) != n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(devs)}")
    if sp is None:
        sp = 2 if n_devices % 2 == 0 else 1
    dp = n_devices // sp
    mesh = Mesh(np.asarray(devs).reshape(dp, sp), ("objects", "shards"))

    def step(enc_m, dec_m, x):
        # enc_m [8, M, K/sp], dec_m [8, K, K/sp], x [B/dp, K/sp, W]:
        # partial GF products over the local shard subset...
        part_par = jax.vmap(rs_jax.gf_matmul_packed, (None, 0))(enc_m, x)
        part_dec = jax.vmap(rs_jax.gf_matmul_packed, (None, 0))(dec_m, x)
        # ...XOR-combined across the shard axis (GF addition) over ICI
        gp = jax.lax.all_gather(part_par, "shards")  # [sp, B/dp, M, W]
        gd = jax.lax.all_gather(part_dec, "shards")
        parity, decoded = gp[0], gd[0]
        for t in range(1, gp.shape[0]):
            parity = parity ^ gp[t]
            decoded = decoded ^ gd[t]
        return parity, decoded

    in_specs = (P(None, None, "shards"), P(None, None, "shards"),
                P("objects", "shards", None))
    out_specs = (P("objects", None, None), P("objects", None, None))
    smapped = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    from ..obs.device import tracked_jit
    return tracked_jit(smapped, op="mesh.sharded_step"), mesh
