#!/usr/bin/env python
"""North-star benchmark: erasure encode/reconstruct GiB/s at 16+4, 1 MiB
block, plus p99 heal-shard latency — ALL FIVE configs of BASELINE.md:

  1. 4+2, 1 MiB block, single PutObject end-to-end (object layer -> bitrot
     -> disk), plus the same for 16+4.
  2. 8+4 encode-only block-size sweep, 64 KiB - 4 MiB.
  3. 16+4 two-shard-loss reconstruct, batch 128.
  4. 16+4 FUSED HighwayHash verify + reconstruct (per-chunk digests checked
     on device in the same launch as the rebuild).
  5. 32-drive-style batched heal: 128 concurrent objects, mixed loss
     patterns, per-element rebuild matrices.
  plus: p50/p99 latency of a single 16+4 heal-shard rebuild THROUGH the
     dispatch queue at 1/8/128 concurrent requesters.

`--chaos` additionally arms a 1-slow-disk + 1-dead-disk fault profile at
16+4 (docs/fault.md) and reports GET / heal-shard p50/p99 for the clean
and degraded runs side by side under `extra.chaos`.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": "GiB/s", "vs_baseline": N, "extra": {...}}

vs_baseline divides TPU device throughput by a locally measured CPU AVX2
single-core encode (the same nibble-shuffle galois kernel the reference
uses via klauspost/reedsolomon; see minio_tpu/native/gf256_simd.cpp).

Timing note: device kernel time is measured DEVICE-RESIDENT: one jitted
lax.fori_loop dispatch runs the kernel N times with a carried scalar
dependency (so XLA can't hoist the loop-invariant call), and the
per-iteration time is the slope between N=1 and N=1025, which cancels the
fixed dispatch + fetch cost. Latency percentiles are wall-clock through the
dispatch queue and therefore INCLUDE the host<->device link — they are what
a caller of this deployment actually observes.

Device numbers come from a TPU backend only: on any other backend main()
refuses to run (no CPU fallback), and the JSON names the device it ran on
(platform, device_kind, device_count). The `benchmark` PR replaces this
script; until then it is kept runnable, not trusted — nothing in it has
been re-measured on the current tree.
"""
from __future__ import annotations

import io
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def measure_slope(fn, n_hi: int = 1025, reps: int = 3) -> float:
    """Per-iteration device seconds: slope between a 1-iteration and an
    n_hi-iteration run. fn(n) runs the kernel n times (device-resident
    loop) and hard-syncs; the slope cancels the fixed dispatch + fetch cost.
    """
    t1 = min(fn(1) for _ in range(reps))
    tn = min(fn(n_hi) for _ in range(max(1, reps - 1)))
    return max((tn - t1) / (n_hi - 1), 1e-9)


def cpu_baseline(rng) -> float:
    """Single-core AVX2 GF(256) encode at 16+4 / 1 MiB (the reference's
    klauspost/reedsolomon per-core shape)."""
    from minio_tpu import native
    from minio_tpu.ops import gf256
    K, M, BLOCK = 16, 4, 1 << 20
    pmat = gf256.build_matrix(K, M)[K:]
    data1 = rng.integers(0, 256, (K, BLOCK // K), dtype=np.uint8)
    native.cpu_encode(pmat, data1, M)  # warm
    n = 100
    t0 = time.perf_counter()
    for _ in range(n):
        native.cpu_encode(pmat, data1, M)
    gibs = BLOCK * n / (time.perf_counter() - t0) / (1 << 30)
    log(f"cpu avx2 encode 16+4 @1MiB: {gibs:.2f} GiB/s "
        f"(avx2={native.load_gf256().gf256_has_avx2()})")
    return gibs


def device_configs(rng) -> dict:
    """Device-kernel configs 2/3/4/5 via the production kernels: encode
    rides the static-specialized pallas kernel (what encode_words_batch /
    the dispatch queue run), reconstruct/heal/fused the dynamic-mask one.

    Each config is timed as ONE jitted lax.fori_loop whose body re-runs the
    kernel with a carried scalar folded into its inputs (masks ^ c, or the
    static kernel's c hook) — a data dependency XLA cannot hoist, so N
    iterations really execute on device and the dispatch + fetch cost
    appears once, not N times.
    """
    import jax
    import jax.numpy as jnp
    from minio_tpu.native import highwayhash as hhn
    from minio_tpu.ops import fused as fused_mod
    from minio_tpu.ops import gf256, rs_jax
    log(f"jax backend: {jax.default_backend()} devices: {jax.devices()}")
    _, mm_batch, mm_batch_per = rs_jax._resolve_backend("auto")
    out: dict = {}

    def bench_loop(label, nbytes_per_elem, body, *args):
        """body(c, *args) -> output array; carried scalar c = out[...0]."""
        @jax.jit
        def loop(n, *a):
            def it(_, c):
                return body(c, *a).reshape(-1)[0]
            return jax.lax.fori_loop(0, n, it, jnp.uint32(0))

        # sync by fetching the carried scalar (it depends on every
        # iteration); the fetch cost cancels in the N=1 vs N=1025 slope
        _ = jax.device_get(loop(1, *args))  # compile + warm

        def run(n):
            t0 = time.perf_counter()
            _ = jax.device_get(loop(n, *args))
            return time.perf_counter() - t0

        per = measure_slope(run)
        gibs = nbytes_per_elem / per / (1 << 30)
        log(f"{label}: {per*1e6:.0f} us/batch -> {gibs:.1f} GiB/s")
        return gibs

    K, M, BLOCK, B = 16, 4, 1 << 20, 128
    shard = BLOCK // K
    data = rng.integers(0, 256, (B, K, shard), dtype=np.uint8)
    w = jnp.asarray(rs_jax.pack_shards(data))
    codec = rs_jax.get_codec(K, M)

    def enc_body(codec):
        if codec._static_encode:
            from minio_tpu.ops import rs_pallas
            return lambda c, xs: rs_pallas.gf_matmul_static_batch(
                codec.parity_rows, xs, c)
        masks = jnp.asarray(gf256.coeff_masks(codec.parity_rows))
        return lambda c, xs: mm_batch(masks ^ c, xs)

    out["encode_16p4_1MiB_b128"] = bench_loop(
        f"tpu encode 16+4 @1MiB x{B}", B * BLOCK, enc_body(codec), w)

    present = tuple(i for i in range(K + M) if i not in (2, 9))[:K]
    rec_masks = jnp.asarray(codec.target_masks_np(present, (2, 9)))
    out["reconstruct_2loss_16p4_b128"] = bench_loop(
        f"tpu reconstruct 16+4 2-loss @1MiB x{B}", B * BLOCK,
        lambda c, ms, xs: mm_batch(ms ^ c, xs), rec_masks, w)

    # config 2: 8+4 encode sweep 64 KiB - 4 MiB (batch sized to keep ~128
    # MiB of source data per launch), through the production encode kernel
    sweep = {}
    codec84 = rs_jax.get_codec(8, 4)
    for bs in (1 << 16, 1 << 18, 1 << 20, 1 << 22):
        bsz = max(1, (128 << 20) // bs)
        d = rng.integers(0, 256, (bsz, 8, bs // 8), dtype=np.uint8)
        ws = jnp.asarray(rs_jax.pack_shards(d))
        sweep[f"{bs >> 10}KiB"] = round(bench_loop(
            f"tpu encode 8+4 @{bs >> 10}KiB x{bsz}", bsz * bs,
            enc_body(codec84), ws), 2)
    out["encode_sweep_8p4"] = sweep

    # config 4: fused bitrot verify + 2-loss reconstruct, 16 KiB chunks —
    # measured with BOTH device hashes: MUR3X256 (u32-native, the
    # framework default) and HighwayHash (u64-emulated, reference-parity)
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.native import mur3py
    from minio_tpu.ops import hh_jax, mur3_jax
    C = 16384
    nc = shard // C
    rec_masks_np = codec.target_masks_np(present, (2, 9))  # [8, o=2, K]
    rec_masks_b = jnp.asarray(np.broadcast_to(
        rec_masks_np, (B,) + rec_masks_np.shape))
    for algo_name, algo_id, batch_hash, key_fn in (
            ("mur3", 1, mur3py.hash256_batch, mur3_jax._key_words),
            ("hh", 0, hhn.hash256_batch, hh_jax._key_words)):
        digs_np = np.stack([
            batch_hash(HIGHWAY_KEY,
                       data[b].reshape(K * nc, C)).reshape(K, nc * 32)
            for b in range(B)])
        digs = jnp.asarray(digs_np.view(np.uint32).reshape(B, K, nc * 8))
        # the PRODUCTION kernel resolution (fused_fn_for): mur3 rides the
        # Pallas hash kernel unless pipeline.device_hash=jnp routes back
        fused_fn = fused_mod.fused_fn_for(HIGHWAY_KEY, shard,
                                          mm_batch_per, C, algo_id)

        def body_fused(c, ms, xs, dg, fused_fn=fused_fn):
            # the hash verify is jnp (not pallas), and xs/dg are loop
            # constants: unless the DATA depends on the carry, XLA hoists
            # the whole verify subgraph out of the loop and times only the
            # rebuild (this made HH read 174 GiB/s, 17x its real rate).
            # xs ^ c forces a re-hash per iteration (~0.3 ms of extra
            # elementwise traffic, <10% of the fused time); summing v
            # keeps every verdict lane live
            o, v = fused_fn(ms, xs ^ c, dg)
            return o.reshape(-1)[0] + jnp.sum(v.astype(jnp.uint32))

        out[f"fused_verify_reconstruct_16p4_b128_{algo_name}"] = bench_loop(
            f"tpu FUSED {algo_name}-verify+reconstruct 16+4 x{B}",
            B * BLOCK, body_fused, rec_masks_b, w, digs)
    out["fused_verify_reconstruct_16p4_b128"] = \
        out["fused_verify_reconstruct_16p4_b128_mur3"]

    # PUT-side device hash lane: fused encode+hash (parity + per-chunk
    # digests of all k+m shards in one launch — what the dispatch queue's
    # encode_hashed flush runs)
    enc_hash_fn = fused_mod.encode_hashed_fn_for(
        HIGHWAY_KEY, shard, codec.encode_words_batch, C, 1)

    def body_enc_hash(c, xs):
        par, dg = enc_hash_fn(xs ^ c)
        return par.reshape(-1)[0] + jnp.sum(dg.astype(jnp.uint32))

    out["fused_encode_hash_16p4_b128"] = bench_loop(
        f"tpu FUSED encode+hash 16+4 x{B}", B * BLOCK, body_enc_hash, w)

    # config 5: batched heal rebuild — per-element masks, mixed loss
    heal_masks = np.stack([
        codec.target_masks_np(
            tuple(j for j in range(K + M) if j not in (i % K, K + i % M))[:K],
            (i % K, K + i % M))
        for i in range(B)])
    out["batched_heal_rebuild_b128"] = bench_loop(
        f"tpu batched heal rebuild 16+4 x{B} mixed-loss", B * BLOCK,
        lambda c, ms, xs: mm_batch_per(ms ^ c, xs),
        jnp.asarray(heal_masks), w)
    return out


def bench_dir() -> str | None:
    """Backing dir for the e2e disks: MINIO_TPU_BENCH_DIR, else /dev/shm
    when it has headroom (the e2e configs measure the framework data plane,
    not the speed of whatever disk backs /tmp), else the default tmp."""
    env = os.environ.get("MINIO_TPU_BENCH_DIR")
    if env:
        return env
    try:
        st = os.statvfs("/dev/shm")
        if st.f_bavail * st.f_frsize > (4 << 30):
            return "/dev/shm"
    except OSError:
        pass
    return None


def host_profile(rng) -> dict:
    """Primitive single-thread rates that bound the e2e configs on this
    host: the serial PUT chain is read + MD5(ETag) + fused encode+hash +
    framed file write, so on an N-core host the achievable ceiling is
    roughly min(stage rates) (pipelined) or 1/sum(1/rates) on one core.
    Recorded so the e2e numbers are interpretable against the hardware."""
    import tempfile as tf
    import time as tm
    out = {"cpus": os.cpu_count()}
    buf = rng.integers(0, 256, 32 << 20, dtype=np.uint8).tobytes()
    import hashlib
    h = hashlib.md5()
    t0 = tm.perf_counter()
    h.update(buf)
    out["md5_gibs"] = round(len(buf) / (tm.perf_counter() - t0) / (1 << 30), 2)
    d = tf.mkdtemp(dir=bench_dir())
    try:
        t0 = tm.perf_counter()
        with open(os.path.join(d, "f"), "wb") as f:
            f.write(buf)
        out["file_write_gibs"] = round(
            len(buf) / (tm.perf_counter() - t0) / (1 << 30), 2)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    try:
        from minio_tpu import native
        from minio_tpu.ops import gf256
        pmat = gf256.build_matrix(4, 2)[4:]
        native.put_block(buf[:1 << 20], 1 << 20, pmat, 4, 2, 1 << 18,
                         16384, b"\x00" * 32)
        t0 = tm.perf_counter()
        for i in range(16):
            native.put_block(buf[i << 20:(i + 1) << 20], 1 << 20, pmat,
                             4, 2, 1 << 18, 16384, b"\x00" * 32)
        out["native_put_block_gibs"] = round(
            16 * (1 << 20) / (tm.perf_counter() - t0) / (1 << 30), 2)
    except Exception:  # noqa: BLE001 — no native build
        pass
    log(f"host: {out}")
    return out


def _host_profile_summary(snap) -> dict:
    """Continuous-profiler window -> the compact ``host_profile``
    bench leaf (ISSUE 14): top-10 folded host frames + subsystem
    shares — the evidence channel for where host CPU goes during the
    measured window (docs/observability.md "Continuous profiling").
    A DELTA over the always-on base sampler: the measured section pays
    nothing beyond the standing base rate, so the headline numbers it
    rides beside stay untaxed. Leaves here are registered NON_HEADLINE
    in tools/bench_compare.py: shares shift with host load and must
    inform, not gate."""
    from minio_tpu.obs import profiler as prof
    rep = prof.delta_report(snap, n=10)
    return {"samples": rep["samples"],
            "sample_hz": rep["sample_hz"],
            "top_frames": rep.get("top_frames", []),
            "subsystems": rep["subsystems"],
            "roles": rep["roles"],
            "lockwait_share": rep["lockwait_share"]}


def e2e_put(rng) -> dict:
    """Config 1: end-to-end PutObject through object layer -> erasure ->
    bitrot writers -> local disks, 4+2 and 16+4, serial and 8-way
    parallel. Each block reads into a pooled buffer (zero-copy ingest)
    and runs the fused native pipeline (split+encode+hash+frame+pwrite in
    one GIL-releasing mt_put_block_fds call); the ETag is the fused
    pipeline hash (md5 over the bitrot digest stream, ~0.2% of payload),
    so no host stage hashes payload bytes — the ceiling is the native
    block rate and the file-write bound, not the old single-CPU MD5.
    ``put_stage_breakdown`` attributes one serial PUT's seconds per
    stage."""
    import threading
    from minio_tpu.obs import stages as obstages
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.storage import XLStorage
    out = {}
    obj_size = 64 << 20
    body = rng.integers(0, 256, obj_size, dtype=np.uint8).tobytes()
    for k, m in ((4, 2), (16, 4)):
        root = tempfile.mkdtemp(prefix=f"bench{k}p{m}-", dir=bench_dir())
        try:
            disks = [XLStorage(os.path.join(root, f"d{i}"))
                     for i in range(k + m)]
            ol = ErasureObjects(disks, default_parity=m)
            ol.make_bucket("b")
            ol.put_object("b", "warm", io.BytesIO(body[:1 << 20]), 1 << 20)
            reps = 3
            t0 = time.perf_counter()
            for r in range(reps):
                ol.put_object("b", f"o{r}", io.BytesIO(body), obj_size)
            dt = time.perf_counter() - t0
            gibs = obj_size * reps / dt / (1 << 30)
            # stage attribution for ONE serial PUT (satellite of ROADMAP
            # item 1): seconds spent in body-read / ETag / encode+hash /
            # shard-write, so pipeline wins are explainable stage by
            # stage across BENCH rounds (overlapped stages each charge
            # their own wall, so the sum may exceed the PUT wall)
            with obstages.collect() as stc:
                t0 = time.perf_counter()
                ol.put_object("b", "staged", io.BytesIO(body), obj_size)
                put_wall = time.perf_counter() - t0
            stage_brk = {"wall_s": round(put_wall, 4), **stc.snapshot()}
            log(f"e2e {k}+{m} put stages: {stage_brk}")
            t0 = time.perf_counter()
            assert ol.get_object_buffer("b", "o0") == body
            get_gibs = obj_size / (time.perf_counter() - t0) / (1 << 30)

            def worker(j):
                ol.put_object("b", f"p{j}", io.BytesIO(body), obj_size)

            threads = [threading.Thread(target=worker, args=(j,))
                       for j in range(8)]
            # host-CPU attribution of the 16+4 par8 PUT (ISSUE 14): a
            # base-aggregate delta over exactly the measured section —
            # the BENCH_r07 evidence for what bounds e2e PUT, at zero
            # added cost to the gating headline it rides beside
            prof_snap = None
            if (k, m) == (16, 4):
                from minio_tpu.obs import profiler as prof
                prof_snap = prof.agg_snapshot(full=True)
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            par = 8 * obj_size / (time.perf_counter() - t0) / (1 << 30)
            if prof_snap is not None:
                out["host_profile"] = _host_profile_summary(prof_snap)
                log(f"e2e 16+4 par8 host profile: "
                    f"{out['host_profile']['subsystems']}")

            read_errs: list = []

            def reader(j):
                try:
                    # zero-copy accessor: compares equal without the
                    # final full-object tobytes pass (get_object_bytes'
                    # GIL-held copy was a residual par8 serializer)
                    if ol.get_object_buffer("b", f"p{j}") != body:
                        raise AssertionError(f"p{j} bytes mismatch")
                except BaseException as e:  # noqa: BLE001
                    read_errs.append(e)

            threads = [threading.Thread(target=reader, args=(j,))
                       for j in range(8)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if read_errs:  # a thread failure must not inflate the number
                raise read_errs[0]
            gpar = 8 * obj_size / (time.perf_counter() - t0) / (1 << 30)
            log(f"e2e {k}+{m} 64MiB: put {gibs:.2f} get {get_gibs:.2f} "
                f"par8 {par:.2f} get_par8 {gpar:.2f} GiB/s")
            out[f"{k}p{m}"] = {"put": round(gibs, 2),
                               "get": round(get_gibs, 2),
                               "put_par8": round(par, 2),
                               "get_par8": round(gpar, 2),
                               "put_stage_breakdown": stage_brk}
        finally:
            shutil.rmtree(root, ignore_errors=True)
    return out


def fsync_put(rng) -> dict:
    """Durability tax (docs/durability.md): 8-way-parallel PUT GiB/s at
    16+4 / 1 MiB objects under fsync=off|batched|always. batched's wall
    time includes the flusher barrier so the number is the cost of
    durability actually achieved, not of deferring it past the
    measurement. Best-of-2 reps per mode after a discarded warmup pass:
    small-object par8 runs swing 2x run-to-run on this 1-core host, and
    a single sample can report a phantom 50% overhead (or a phantom
    speedup) that is pure scheduler noise."""
    import threading

    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.storage import XLStorage
    from minio_tpu.storage.durability import flusher
    K, M, OBJ, N_PER, REPS = 16, 4, 1 << 20, 16, 2
    body = rng.integers(0, 256, OBJ, dtype=np.uint8).tobytes()
    out: dict = {}
    prev = os.environ.get("MINIO_TPU_FSYNC")

    def one_rep(mode) -> float:
        root = tempfile.mkdtemp(prefix=f"benchfsync-{mode}-",
                                dir=bench_dir())
        try:
            disks = [XLStorage(os.path.join(root, f"d{i}"))
                     for i in range(K + M)]
            ol = ErasureObjects(disks, default_parity=M)
            ol.make_bucket("b")
            ol.put_object("b", "warm", io.BytesIO(body), OBJ)

            def worker(j):
                for i in range(N_PER):
                    ol.put_object("b", f"o{j}-{i}",
                                  io.BytesIO(body), OBJ)

            threads = [threading.Thread(target=worker, args=(j,))
                       for j in range(8)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if mode == "batched":
                flusher().flush(timeout=30.0)
            dt = time.perf_counter() - t0
            return 8 * N_PER * OBJ / dt / (1 << 30)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    try:
        os.environ["MINIO_TPU_FSYNC"] = "off"
        one_rep("off")  # warmup: first par8 run pays one-time init
        for mode in ("off", "batched", "always"):
            os.environ["MINIO_TPU_FSYNC"] = mode
            out[mode] = round(max(one_rep(mode) for _ in range(REPS)), 3)
        if out.get("off"):
            out["batched_overhead_pct"] = round(
                100.0 * (1.0 - out["batched"] / out["off"]), 1)
            out["always_overhead_pct"] = round(
                100.0 * (1.0 - out["always"] / out["off"]), 1)
        log(f"fsync par8 16+4 1MiB PUT GiB/s: {out}")
    finally:
        if prev is None:
            os.environ.pop("MINIO_TPU_FSYNC", None)
        else:
            os.environ["MINIO_TPU_FSYNC"] = prev
    return out


def heal_latency(rng) -> dict:
    """p50/p99 wall-clock latency of ONE 16+4 heal-shard rebuild (1 MiB
    block, 2 lost shards) through the dispatch queue, at 1/8/128 concurrent
    requesters — the north-star's latency half. Measured on BOTH routes
    (MINIO_TPU_DISPATCH_MODE=cpu and =device) so the deployment's actual
    choice is informed (link cost: re-measure on the attached chip)."""
    import threading

    import jax
    from minio_tpu.ops import rs_jax
    from minio_tpu.runtime.dispatch import global_queue
    K, M, BLOCK = 16, 4, 1 << 20
    shard = BLOCK // K
    codec = rs_jax.get_codec(K, M)
    q = global_queue()
    present = tuple(i for i in range(K + M) if i not in (3, 17))[:K]
    masks = codec.target_masks_np(present, (3, 17))
    words = rs_jax.pack_shards(
        rng.integers(0, 256, (K, shard), dtype=np.uint8))

    def run_mode(mode: str) -> dict:
        # percentiles come from the SAME last-minute sliding-window class
        # the server exports as minio_tpu_heal_shard_latency_p99_seconds
        # (minio_tpu/obs/latency.py) — bench numbers and production
        # metrics cannot diverge in method. Runs longer than the window
        # therefore report steady-state (last-minute) percentiles.
        from minio_tpu.obs import latency as obslat

        # warm every pow2 batch shape the timed runs can hit (a first-time
        # jit compile inside the timed region would own the p99)
        for warm_burst in (1, 2, 8, 16, 64, 128, 128):
            futs = [q.masked(codec, words, masks) for _ in range(warm_burst)]
            for f in futs:
                f.result()
        res = {}
        for conc in (1, 8, 128):
            n_ops = 40 if conc == 1 else max(conc * 3, 120)
            win = obslat.reset_window("kernel", op="heal_shard")

            def worker(count):
                for _ in range(count):
                    t0 = time.perf_counter()
                    q.masked(codec, words, masks).result()
                    obslat.observe("kernel", time.perf_counter() - t0,
                                   BLOCK, op="heal_shard")

            per_worker = max(1, n_ops // conc)
            threads = [threading.Thread(target=worker, args=(per_worker,))
                       for _ in range(conc)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            n_done = per_worker * conc
            ps = win.percentiles((0.5, 0.99))
            p50 = ps[0.5] * 1e3
            p99 = ps[0.99] * 1e3
            thr = n_done * BLOCK / wall / (1 << 30)
            log(f"heal-shard latency [{mode}] conc={conc}: p50={p50:.1f}ms "
                f"p99={p99:.1f}ms agg={thr:.2f} GiB/s ({n_done} ops, "
                f"{win.count()} in window)")
            res[f"conc{conc}"] = {"p50_ms": round(p50, 1),
                                  "p99_ms": round(p99, 1),
                                  "agg_gibs": round(thr, 2)}
        return res

    out = {}
    prev = os.environ.get("MINIO_TPU_DISPATCH_MODE")
    modes = ["cpu", "device"]  # main() refused any non-TPU backend
    # host-CPU attribution across the heal configs (ISSUE 14): where
    # the dispatcher/completer threads spend the heal-shard walls — a
    # base-aggregate delta, so the gating heal percentiles pay nothing
    from minio_tpu.obs import profiler as prof
    prof_snap = prof.agg_snapshot(full=True)
    try:
        for mode in modes:
            os.environ["MINIO_TPU_DISPATCH_MODE"] = mode
            out[mode] = run_mode(mode)
    finally:
        if prev is None:
            os.environ.pop("MINIO_TPU_DISPATCH_MODE", None)
        else:
            os.environ["MINIO_TPU_DISPATCH_MODE"] = prev
    out["host_profile"] = _host_profile_summary(prof_snap)
    log(f"heal host profile: {out['host_profile']['subsystems']}")
    st = q.stats()
    prof = q._get_profile()
    out["dispatch"] = {
        "batches": st["batches"], "cpu_batches": st["cpu_batches"],
        "device_batches": st["device_batches"],
        "cpu_items": st["cpu_items"], "device_items": st["device_items"],
        "hold_events": st["hold_events"],
        "hold_seconds": st["hold_seconds"],
        # QoS scheduler telemetry: forced-device runs through a slow
        # link are expected to SPILL most items back to the CPU
        # executor (bounded p99 instead of a multi-second backlog)
        "spilled_items": st["spilled_items"],
        "spilled_batches": st["spilled_batches"],
        "spill_reasons": st["spill_reasons"],
        "deadline_misses": st["deadline_misses"],
        # per-device flush lanes (ISSUE 11): diverts + residual queued
        # bytes per lane; the full mesh scaling story is MULTICHIP's
        # (__graft_entry__.multichip_bench), single-chip hosts report
        # an empty lane map here
        "lane_diverts": st["lane_diverts"],
        "lane_queued_bytes": st["lane_queued_bytes"],
        "avg_batch": round(st["avg_batch"], 2),
        "device_pipeline": __import__(
            "minio_tpu.runtime.dispatch",
            fromlist=["DEVICE_PIPELINE"]).DEVICE_PIPELINE,
        "completers": q.completer_count,
        "link_rt_ms": round(prof.rt_s * 1e3, 1) if prof else None,
        "link_up_gibs": round(prof.up_gibs, 3) if prof else None,
        "link_down_gibs": round(prof.down_gibs, 3) if prof else None,
        "link_cpu_gibs": round(prof.cpu_gibs, 2) if prof else None,
    }
    return out


def interactive_lane_extra(rng) -> dict:
    """ISSUE 13: heal-shard wall p50/p99 at conc=8 and conc=128 through
    BOTH device-lane disciplines — the bulk coalescing lane
    (``qos.device_stream(STREAM_BULK)``) vs the interactive lane
    (bounded <=8 batches on a dedicated dispatcher, deadline-aware
    sizing, async on_ready completion, donated inputs on TPU). Leaves
    are ``heal_p50_s``/``heal_p99_s`` (down-better headline metrics for
    tools/bench_compare). On a TPU host the acceptance target is device
    heal-shard p99 within 5x of CPU at conc=8 while bulk encode stays
    >=100 GiB/s (ROADMAP item 2); on a CPU-only host both lanes run the
    CPU route and the number documents the lane overheads instead."""
    import threading

    from minio_tpu import qos
    from minio_tpu.ops import rs_jax
    from minio_tpu.runtime.dispatch import global_queue
    K, M, BLOCK = 16, 4, 1 << 20
    shard = BLOCK // K
    codec = rs_jax.get_codec(K, M)
    q = global_queue()
    present = tuple(i for i in range(K + M) if i not in (3, 17))[:K]
    masks = codec.target_masks_np(present, (3, 17))
    words = rs_jax.pack_shards(
        rng.integers(0, 256, (K, shard), dtype=np.uint8))

    def pcts(vals: list[float]) -> dict:
        vs = sorted(vals)
        return {"heal_p50_s": round(vs[len(vs) // 2], 6),
                "heal_p99_s": round(
                    vs[min(len(vs) - 1, int(0.99 * len(vs)))], 6)}

    def run_leg(stream: str, conc: int) -> dict:
        # warm the pow2 batch shapes this leg can hit
        with qos.device_stream(stream):
            futs = [q.masked(codec, words, masks)
                    for _ in range(min(conc, 8))]
            for f in futs:
                f.result()
        n_ops = 64 if conc == 8 else 256
        per_worker = max(1, n_ops // conc)
        walls: list[float] = []
        wlock = threading.Lock()

        def worker():
            with qos.device_stream(stream):
                for _ in range(per_worker):
                    t0 = time.perf_counter()
                    q.masked(codec, words, masks).result()
                    dt = time.perf_counter() - t0
                    with wlock:
                        walls.append(dt)

        threads = [threading.Thread(target=worker)
                   for _ in range(conc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return pcts(walls)

    out: dict = {}
    for stream in (qos.STREAM_BULK, qos.STREAM_INTERACTIVE):
        leg: dict = {}
        for conc in (8, 128):
            leg[f"conc{conc}"] = run_leg(stream, conc)
            log(f"interactive_lane [{stream}] conc={conc}: "
                f"p50={leg[f'conc{conc}']['heal_p50_s'] * 1e3:.1f}ms "
                f"p99={leg[f'conc{conc}']['heal_p99_s'] * 1e3:.1f}ms")
        out[stream] = leg
    out["lane"] = q.stats()["interactive_lane"]
    return {"interactive_lane": out}


def chaos_profile(rng) -> dict:
    """--chaos: the degraded-operation half of the north-star. A 16+4
    set of 1 MiB objects is measured clean, then with a 1-slow-disk
    (delay(200) on every shard read) + 1-dead-disk (typed DiskNotFound
    on every op) profile armed through the production fault registry
    (docs/fault.md) — the same rules an operator would arm via
    `mc admin`-style POST /minio/admin/v3/fault. Reported side by side:
    GET p50/p99 (hedged reads route around the straggler; the health
    tracker trips the dead disk to fast-fail), heal-shard p50/p99 wall
    time (each heal rebuilds toward the dead disk under a slow source),
    plus the fired/won hedge counters and final disk health states.
    Both passes pin MINIO_TPU_GET_PATH=dispatch so they measure the
    same (Python shard-read) code path — chaos runs always take it, and
    its shard reads feed the adaptive hedge threshold's p95 window."""
    import threading

    from minio_tpu import fault
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.obs.metrics import counters_snapshot
    from minio_tpu.storage import XLStorage
    K, M, OBJ = 16, 4, 1 << 20
    N_OBJ, GET_REPS, DELAY_MS = 8, 4, 200.0
    body = rng.integers(0, 256, OBJ, dtype=np.uint8).tobytes()
    root = tempfile.mkdtemp(prefix="benchchaos-", dir=bench_dir())
    ol = None  # the finally below must not NameError if setup raises
    prev_path = os.environ.get("MINIO_TPU_GET_PATH")
    os.environ["MINIO_TPU_GET_PATH"] = "dispatch"
    # probe cadence must undercut the cleanup join(timeout=2) below, or
    # a tripped disk's probe thread outlives the rmtree'd backing dir
    prev_cool = os.environ.get("MINIO_TPU_HEALTH_COOLDOWN_S")
    os.environ["MINIO_TPU_HEALTH_COOLDOWN_S"] = "0.5"
    out: dict = {"profile": f"slow=delay({DELAY_MS:.0f}ms) dead=DiskNotFound "
                            f"at {K}+{M}, {N_OBJ}x1MiB"}

    def pcts(samples: list[float]) -> dict:
        return {"p50_ms": round(float(np.percentile(samples, 50)) * 1e3, 1),
                "p99_ms": round(float(np.percentile(samples, 99)) * 1e3, 1)}

    def run_pass(ol) -> dict:
        gets: list[float] = []
        for _ in range(GET_REPS):
            for i in range(N_OBJ):
                t0 = time.perf_counter()
                if ol.get_object_bytes("b", f"o{i}") != body:
                    raise AssertionError(f"o{i} bytes mismatch")
                gets.append(time.perf_counter() - t0)
        heals: list[float] = []
        for i in range(N_OBJ):
            t0 = time.perf_counter()
            ol.heal_object("b", f"o{i}")
            heals.append(time.perf_counter() - t0)
        return {"get": pcts(gets), "heal": pcts(heals)}

    try:
        # zero-padded dirs: rule targets match by substring, and a bare
        # ".../d1" would also hit ".../d10"-".../d19"
        disks = [XLStorage(os.path.join(root, f"d{i:02d}"))
                 for i in range(K + M)]
        ol = ErasureObjects(disks, default_parity=M)
        ol.make_bucket("b")
        for i in range(N_OBJ):
            ol.put_object("b", f"o{i}", io.BytesIO(body), OBJ)
        out["clean"] = run_pass(ol)
        def fired_count() -> float:
            return sum(v for k, v in counters_snapshot().items()
                       if "minio_tpu_hedged_reads_total" in k
                       and 'outcome="fired"' in k)

        hedged_before = fired_count()
        slow, dead = ol.disks[0], ol.disks[1]
        fault.arm(f"disk:{slow.endpoint()}:read_at:delay({DELAY_MS:.0f})")
        fault.arm(f"disk:{dead.endpoint()}:*:error(DiskNotFound)")
        out["chaos"] = run_pass(ol)
        snap = counters_snapshot()
        out["chaos"]["hedged_reads"] = {
            k.split('outcome="')[1].rstrip('"}'): v
            for k, v in snap.items()
            if "minio_tpu_hedged_reads_total" in k} or {}
        out["chaos"]["hedged_fired_during"] = fired_count() - hedged_before
        out["chaos"]["disk_states"] = {
            d.endpoint(): d.health_state() for d in ol.disks
            if hasattr(d, "health_state")
            and d.health_state() != "ok"}
        log(f"chaos 16+4 1MiB: clean get p99 "
            f"{out['clean']['get']['p99_ms']}ms -> chaos get p99 "
            f"{out['chaos']['get']['p99_ms']}ms (hedges fired: "
            f"{out['chaos']['hedged_fired_during']}); heal p99 "
            f"{out['clean']['heal']['p99_ms']} -> "
            f"{out['chaos']['heal']['p99_ms']}ms")
    finally:
        fault.clear()
        if prev_path is None:
            os.environ.pop("MINIO_TPU_GET_PATH", None)
        else:
            os.environ["MINIO_TPU_GET_PATH"] = prev_path
        if prev_cool is None:
            os.environ.pop("MINIO_TPU_HEALTH_COOLDOWN_S", None)
        else:
            os.environ["MINIO_TPU_HEALTH_COOLDOWN_S"] = prev_cool
        # let tripped-disk probe threads notice the cleared faults and
        # exit before their backing dirs vanish
        for d in (ol.disks if ol is not None else []):
            t = getattr(d, "_probe_thread", None)
            if isinstance(t, threading.Thread):
                t.join(timeout=2)
        shutil.rmtree(root, ignore_errors=True)
    return out


class _NullWriter:
    def write(self, b):
        return len(b)


def select_scan_bench(rng) -> dict:
    """Device-workloads config A (ISSUE 8 / docs/select.md): batched
    Select scan GiB/s on a numeric-predicate CSV at 1 MiB blocks x 128
    batch, against the classic per-row interpreter on a sample of the
    SAME data (the row loop runs ~MB/s, so it gets a slice and the
    ratio extrapolates — both numbers are decoded-bytes/sec)."""
    from minio_tpu.s3select import S3SelectRequest, run_select
    mb = int(os.environ.get("MINIO_TPU_BENCH_SCAN_MB", "128"))
    # ~26 B/row numeric CSV: id,v,w
    n = mb * (1 << 20) // 26
    ids = np.arange(n) % 10_000_000
    v = rng.integers(0, 1_000_000, n)
    w = rng.integers(0, 100, n)
    body = ("\n".join(f"{a},{b},{c}" for a, b, c in
                      zip(ids, v, w)) + "\n").encode()
    sql = ("SELECT _1 FROM S3Object "
           "WHERE _2 BETWEEN 990000 AND 1000000 AND _3 < 8")
    req = S3SelectRequest()
    req.expression = sql
    req.csv_header = "NONE"

    def run_with(mode: str, data: bytes) -> float:
        prev = os.environ.get("MINIO_TPU_SCAN")
        os.environ["MINIO_TPU_SCAN"] = mode
        try:
            t0 = time.perf_counter()
            run_select(req, data, _NullWriter())
            return len(data) / (time.perf_counter() - t0) / (1 << 30)
        finally:
            if prev is None:
                os.environ.pop("MINIO_TPU_SCAN", None)
            else:
                os.environ["MINIO_TPU_SCAN"] = prev

    run_with("auto", body[: 4 << 20])    # warm (jit compile)
    scan_gibs = run_with("auto", body)
    sample = body[: body.rfind(b"\n", 0, 8 << 20) + 1]
    rowloop_gibs = run_with("off", sample)
    log(f"select_scan {mb}MiB: scan {scan_gibs:.3f} GiB/s vs rowloop "
        f"{rowloop_gibs:.4f} GiB/s ({scan_gibs / rowloop_gibs:.1f}x)")
    return {"select_scan_gibs": round(scan_gibs, 3),
            "select_scan_rowloop_gibs": round(rowloop_gibs, 4),
            "select_scan_speedup": round(scan_gibs / rowloop_gibs, 1)}


def sse_put_bench(rng) -> dict:
    """Device-workloads config B (ISSUE 8 / docs/sse.md): SSE PUT
    overhead %% vs plaintext at 16+4 par8 (1 MiB bodies), per package
    cipher. AES-GCM reports null without the cryptography wheel."""
    import threading

    from minio_tpu.crypto.sse import (CIPHER_AESGCM, CIPHER_CHACHA20,
                                      HAVE_CRYPTOGRAPHY, EncryptReader,
                                      enc_size)
    from minio_tpu.objectlayer import ErasureObjects
    from minio_tpu.storage import XLStorage
    K, M, OBJ = 16, 4, 1 << 20
    N_PER = int(os.environ.get("MINIO_TPU_BENCH_SSE_NPER", "8"))
    body = rng.integers(0, 256, OBJ, dtype=np.uint8).tobytes()
    oek, iv = b"\x42" * 32, b"\x07" * 12
    root = tempfile.mkdtemp(prefix="benchsse-", dir=bench_dir())
    out: dict = {}
    try:
        disks = [XLStorage(os.path.join(root, f"d{i}"))
                 for i in range(K + M)]
        ol = ErasureObjects(disks, default_parity=M)
        ol.make_bucket("b")

        def par8(tag: str, cipher: str | None) -> float:
            def worker(j):
                for r in range(N_PER):
                    name = f"{tag}-{j}-{r}"
                    if cipher is None:
                        ol.put_object("b", name, io.BytesIO(body), OBJ)
                    else:
                        ol.put_object(
                            "b", name,
                            EncryptReader(io.BytesIO(body), oek, iv,
                                          cipher=cipher),
                            enc_size(OBJ))
            threads = [threading.Thread(target=worker, args=(j,))
                       for j in range(8)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        par8("warm", None)
        # warm the chacha lane too (first full-package kernel compile
        # is ~20-40 s on the TPU host — must not land in the timed run)
        EncryptReader(io.BytesIO(body), oek, iv,
                      cipher=CIPHER_CHACHA20).read()
        t_plain = par8("plain", None)
        t_cha = par8("cha", CIPHER_CHACHA20)
        cha_pct = (t_cha - t_plain) / t_plain * 100
        out = {"sse_put_overhead_pct": {
            "chacha20": round(cha_pct, 1),
            "aes-gcm": None,
        }, "sse_put_plain_gibs": round(
            8 * N_PER * OBJ / t_plain / (1 << 30), 3)}
        if HAVE_CRYPTOGRAPHY:
            t_aes = par8("aes", CIPHER_AESGCM)
            out["sse_put_overhead_pct"]["aes-gcm"] = round(
                (t_aes - t_plain) / t_plain * 100, 1)
        log(f"sse_put par8 16+4: plain {t_plain:.2f}s "
            f"overhead {out['sse_put_overhead_pct']}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def timeline_extras() -> dict:
    """Flight-recorder artifacts for BENCH_r07+ (ISSUE 9): a truncated
    timeline of the run (newest 120 events, enough to see the last
    config's enqueue→plan→flush→complete chains per lane), the per-lane
    utilization snapshot, the standing PUT/GET/heal attribution report
    (stage p50/p99 + share of wall — the e2e configs above fed it), and
    the recorder's measured per-event cost with the derived overhead
    estimate against the encode bench.

    Overhead proof for the acceptance criterion: the encode config runs
    device-resident fori_loops that never touch the recorder, and the
    dispatch path pays <=4 recorded events per item — per-event cost ×
    4 over the ~ms-scale per-item wall is the recorder-ON tax, reported
    here so the <1% claim is a number, not an assertion."""
    from minio_tpu.obs import attribution, timeline

    # snapshot the run's timeline BEFORE the microbench floods the ring
    # with synthetic events
    artifact = {
        **timeline.status(),
        "utilization": timeline.utilization(),
        "events": timeline.snapshot(limit=120),
    }
    report = attribution.report()

    # per-event record() cost, recorder ON (default ring)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        timeline.record("enqueue", op="bench", bytes=1 << 20)
    on_ns = (time.perf_counter() - t0) / n * 1e9
    # and the disabled early-out
    prev = os.environ.get("MINIO_TPU_TIMELINE")
    os.environ["MINIO_TPU_TIMELINE"] = "0"
    timeline.configure()
    t0 = time.perf_counter()
    for i in range(n):
        timeline.record("enqueue", op="bench", bytes=1 << 20)
    off_ns = (time.perf_counter() - t0) / n * 1e9
    if prev is None:
        os.environ.pop("MINIO_TPU_TIMELINE", None)
    else:
        os.environ["MINIO_TPU_TIMELINE"] = prev
    timeline.configure()
    # <=4 recorded events per dispatched item; a 1 MiB item at even
    # 1 GiB/s spends ~1 ms — events per item / item wall = overhead
    per_item_s = (1 << 20) / (1 << 30)
    overhead_pct = 4 * on_ns / 1e9 / per_item_s * 100
    log(f"timeline record(): {on_ns:.0f} ns/event on, {off_ns:.0f} "
        f"ns/event off -> est. {overhead_pct:.3f}% at 1 GiB/s per-item")
    return {
        "timeline": artifact,
        "attribution": report,
        "timeline_overhead": {
            "record_ns_on": round(on_ns, 1),
            "record_ns_off": round(off_ns, 1),
            "est_dispatch_overhead_pct_at_1gibs": round(overhead_pct, 4),
        },
    }


def scale_slo_extra() -> dict:
    """ISSUE 10: the mixed-workload SLO scale harness (tools/loadgen)
    as a standing bench extra for BENCH_r07+. Runs the tier-1 profile
    (1k objects, 64 mixed closed-loop clients + an open-loop arrival
    ramp, one scanner cycle forced mid-run, an admission overload
    probe) against a fresh in-process server and ships the verdict
    report minus its bulky embedded sections — the SLO verdicts,
    per-class latency/availability and the scanner attribution are the
    numbers the trajectory tracks. Scale up via MINIO_TPU_SCALE_*."""
    import tempfile

    from tools.loadgen import Profile, run_tier1_profile
    profile = Profile(
        objects=int(os.environ.get("MINIO_TPU_SCALE_OBJECTS", "1000")),
        clients=int(os.environ.get("MINIO_TPU_SCALE_CLIENTS", "64")),
        duration_s=float(os.environ.get("MINIO_TPU_SCALE_DURATION",
                                        "6")),
        open_rps=float(os.environ.get("MINIO_TPU_SCALE_OPEN_RPS",
                                      "50")),
        # multi-tenant spread (ISSUE 18): 48 buckets against the
        # default top_n=32 registry forces real folding, so the
        # bucket_metrics_bounded_ok verdict gates on a scrape that
        # actually had to bound itself
        buckets=int(os.environ.get("MINIO_TPU_SCALE_BUCKETS", "48")),
    )
    with tempfile.TemporaryDirectory(prefix="bench-slo-") as root:
        rep = run_tier1_profile(root, profile)
    slim = {k: v for k, v in rep.items()
            if k not in ("health", "slo", "per_op")}
    slim["slo_interactive_5m"] = \
        rep["slo"]["classes"]["interactive"]["windows"]["5m"]
    slim["slo_breach"] = {
        cls: ent["breach"] for cls, ent in rep["slo"]["classes"].items()}
    log(f"scale_slo: {rep['requests_total']} reqs @ {rep['rps']}/s, "
        f"passed={rep['verdicts']['passed']}")
    # degraded-GET + heal interactive mix (ISSUE 13): a second, smaller
    # run with one disk's shard reads killed — GETs reconstruct on the
    # interactive device lane, a heal worker rebuilds concurrently, and
    # the interactive class's own burn rates judge the latency tier.
    # MINIO_TPU_SCALE_DEGRADED=0 skips.
    if os.environ.get("MINIO_TPU_SCALE_DEGRADED", "1") != "0":
        dprofile = Profile(
            objects=int(os.environ.get(
                "MINIO_TPU_SCALE_DEGRADED_OBJECTS", "128")),
            clients=int(os.environ.get(
                "MINIO_TPU_SCALE_DEGRADED_CLIENTS", "16")),
            duration_s=float(os.environ.get(
                "MINIO_TPU_SCALE_DEGRADED_DURATION", "4")),
            value_bytes=256 << 10,   # above the 128 KiB inline line
            open_rps=0.0,
            degraded=True,
        )
        with tempfile.TemporaryDirectory(prefix="bench-slo-deg-") as root:
            drep = run_tier1_profile(root, dprofile)
        slim["degraded"] = {
            "profile": drep["profile"],
            "degraded": drep["degraded"],
            "interactive": drep["per_class"].get("interactive", {}),
            "verdicts": {k: v for k, v in drep["verdicts"].items()
                         if k.startswith("degraded") or k == "passed"},
        }
        log(f"scale_slo degraded: reconstruct items="
            f"{drep['degraded'].get('interactive_lane_items')} heals="
            f"{drep['degraded'].get('heals')} passed="
            f"{drep['verdicts']['passed']}")
    # replication-chaos phase (ISSUE 19): a third run on a real 4-node
    # topology with a replication rule at node 3, the target killed
    # mid-stream and rejoined — the no_replica_obligation_lost /
    # replication_backlog_drained / replication_lag_slo_ok verdicts
    # gate it. MINIO_TPU_SCALE_REPLICATION=0 skips.
    if os.environ.get("MINIO_TPU_SCALE_REPLICATION", "1") != "0":
        from tools.loadgen import run_topology_profile
        rprofile = Profile(
            objects=int(os.environ.get(
                "MINIO_TPU_SCALE_REPLICATION_OBJECTS", "128")),
            clients=int(os.environ.get(
                "MINIO_TPU_SCALE_REPLICATION_CLIENTS", "8")),
            duration_s=float(os.environ.get(
                "MINIO_TPU_SCALE_REPLICATION_DURATION", "6")),
            open_rps=0.0,
            scanner_mid_run=False,
            overload_probe=False,
            notifier_probe=False,
            replication_target_node=3,
        )
        with tempfile.TemporaryDirectory(prefix="bench-slo-rep-") \
                as root:
            rrep = run_topology_profile(root, rprofile, nodes=4,
                                        disks_per_node=2)
        rsec = dict(rrep["replication"])
        rsec.pop("lost_replicas", None)
        slim["replication"] = {
            "profile": rrep["profile"],
            "replication": rsec,
            "verdicts": {k: v for k, v in rrep["verdicts"].items()
                         if "replica" in k or "replication" in k or
                         k == "passed"},
        }
        log(f"scale_slo replication: acked="
            f"{rsec.get('acked_writes')} lost="
            f"{rsec.get('lost_count')} drain="
            f"{rsec.get('drain_s')}s passed="
            f"{rrep['verdicts']['passed']}")
    return {"scale_slo": slim}


def node_chaos_extra() -> dict:
    """ISSUE 12: clean vs kill-1-of-4 on a real 4-node topology
    (dist.harness.LocalCluster — separate listeners, storage REST,
    dsync locks). Reports S3 PUT/GET p50/p99 with all nodes up, the
    same with one node killed mid-bench (write-quorum degraded writes +
    cross-peer reads), and the heal-drain seconds after the node
    rejoins — the BENCH_r07+ trajectory for the node fault-tolerance
    plane. MINIO_TPU_NODE_CHAOS_BENCH=0 skips."""
    if os.environ.get("MINIO_TPU_NODE_CHAOS_BENCH", "1") == "0":
        return {}
    import tempfile
    import time as _t

    from minio_tpu.dist.harness import LocalCluster
    from tools.loadgen import _SigClient

    ops = int(os.environ.get("MINIO_TPU_NODE_CHAOS_OPS", "12"))
    body = np.random.default_rng(5).integers(
        0, 256, 256 << 10, dtype=np.uint8).tobytes()

    def pcts(vals):
        vs = sorted(vals)
        return {"p50_ms": round(vs[len(vs) // 2] * 1e3, 1),
                "p99_ms": round(vs[min(len(vs) - 1,
                                       int(0.99 * len(vs)))] * 1e3, 1)}

    def measure(cl, tag):
        puts, gets = [], []
        for i in range(ops):
            t0 = _t.perf_counter()
            r = cl.request("PUT", f"/ncb/{tag}{i:03d}", body=body)
            assert r.status_code == 200, (tag, i, r.status_code)
            puts.append(_t.perf_counter() - t0)
            t0 = _t.perf_counter()
            r = cl.request("GET", f"/ncb/{tag}{i:03d}")
            assert r.status_code == 200 and len(r.content) == len(body)
            gets.append(_t.perf_counter() - t0)
        return {"put": pcts(puts), "get": pcts(gets)}

    def repl_leg(lc, cl, tag, target_idx, kill):
        """One replication leg (ISSUE 19 trajectory): rule at
        ``target_idx``, ``ops`` unique PUTs (with a mid-stream
        kill/restart of the target when ``kill``), then the backlog
        drained to zero and the per-leg lag quantiles read off a
        fresh lag window."""
        from minio_tpu.obs.latency import Window
        src, dstb = f"rsrc-{tag}", f"rdst-{tag}"
        cl.request("PUT", f"/{src}")
        xml = (
            "<ReplicationConfiguration><Rule><ID>bench</ID>"
            "<Status>Enabled</Status><Priority>1</Priority>"
            "<Destination>"
            f"<Bucket>{dstb}</Bucket><Endpoint>{lc.urls[target_idx]}"
            "</Endpoint></Destination></Rule>"
            "</ReplicationConfiguration>").encode()
        r = cl.request("PUT", f"/{src}", query={"replication": ""},
                       body=xml)
        assert r.status_code == 200, r.status_code
        rs = lc.nodes[0].server.replication_sys
        rs.lag = Window()        # per-leg quantiles, not cumulative
        for i in range(ops):
            if kill and i == ops // 3:
                lc.kill(target_idx)
            if kill and i == 2 * ops // 3:
                lc.restart(target_idx)
            r = cl.request("PUT", f"/{src}/o{i:03d}", body=body)
            assert r.status_code == 200, (tag, i, r.status_code)
        t0 = _t.monotonic()
        drained = False
        while _t.monotonic() - t0 < 120:
            st = rs.stats()
            if st["queued"] + st["retry_pending"] == 0:
                drained = True
                break
            _t.sleep(0.1)
        lagr = rs.lag_report()
        return src, {
            "lag_p50_ms": round(lagr["lag_p50_s"] * 1e3, 1),
            "lag_p99_ms": round(lagr["lag_p99_s"] * 1e3, 1),
            "drain_s": round(_t.monotonic() - t0, 2),
            "drained": drained,
            "backlog": lagr["backlog"],
        }

    with tempfile.TemporaryDirectory(prefix="bench-nc-") as root:
        lc = LocalCluster(root, nodes=4, disks_per_node=2, parity=2)
        try:
            cl = _SigClient(lc.endpoint(0), lc.access_key,
                            lc.secret_key)
            r = cl.request("PUT", "/ncb")
            assert r.status_code == 200, r.status_code
            clean = measure(cl, "c")
            lc.kill(3)
            degraded = measure(cl, "k")
            lc.restart(3)
            t0 = _t.monotonic()
            drained = False
            while _t.monotonic() - t0 < 120:
                mrf = getattr(lc.nodes[0].server, "mrf", None)
                if mrf is not None and mrf.stats()["queued"] == 0:
                    drained = True
                    break
                _t.sleep(0.25)
            drain_s = round(_t.monotonic() - t0, 2)
            # replication trajectory (ISSUE 19): lag quantiles + drain
            # seconds with the target healthy vs killed-and-rejoined
            # mid-stream, plus a forced full-bucket resync replay
            rs = getattr(lc.nodes[0].server, "replication_sys", None)
            replication: dict = {}
            if rs is not None:
                _, replication["clean"] = repl_leg(lc, cl, "cl", 1,
                                                   kill=False)
                ksrc, replication["kill_target"] = repl_leg(
                    lc, cl, "kt", 3, kill=True)
                t0 = _t.monotonic()
                n_resync = rs.resync(ksrc, force=True)
                while _t.monotonic() - t0 < 120:
                    st = rs.stats()
                    if st["queued"] + st["retry_pending"] == 0:
                        break
                    _t.sleep(0.1)
                replication["resync"] = {
                    "drain_s": round(_t.monotonic() - t0, 2),
                    "resynced": n_resync,
                }
        finally:
            lc.shutdown()
    out = {"clean": clean, "kill_1_of_4": degraded,
           "heal_drain_s": drain_s, "heal_drained": drained,
           "replication": replication}
    log(f"node_chaos: clean put p99 {clean['put']['p99_ms']}ms vs "
        f"kill-1-of-4 {degraded['put']['p99_ms']}ms, heal drain "
        f"{drain_s}s")
    if replication:
        log(f"node_chaos replication: clean lag p99 "
            f"{replication['clean']['lag_p99_ms']}ms vs kill-target "
            f"{replication['kill_target']['lag_p99_ms']}ms, resync "
            f"drain {replication['resync']['drain_s']}s")
    return {"node_chaos": out}


def finish(payload: dict) -> None:
    """Print the one-line result and quiesce framework threads; the
    process then leaves through the interpreter's normal teardown, so a
    late failure keeps its exit code."""
    print(json.dumps(payload))
    sys.stdout.flush()
    sys.stderr.flush()
    import minio_tpu
    minio_tpu.shutdown()


def require_chip() -> dict:
    """The device this run measures, as JAX reports it — or exit: device
    numbers from a non-TPU backend are not device numbers, and a host
    data plane that silently lost its native library (blake2b bitrot,
    no native PUT/GET) is not the system under test."""
    import jax
    from minio_tpu import native
    from minio_tpu.erasure.bitrot import (DEFAULT_BITROT_ALGO,
                                          BitrotAlgorithm)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: backend is {dev.platform!r}, not 'tpu'; "
                 "refusing to print device numbers (no CPU fallback)")
    if not native.available() or \
            DEFAULT_BITROT_ALGO is not BitrotAlgorithm.HIGHWAYHASH256S:
        sys.exit("bench.py: native library unavailable or default "
                 f"bitrot is {DEFAULT_BITROT_ALGO.value}, not "
                 "highwayhash256S (see the ERROR log above)")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def device_obs_extra() -> dict:
    """Device-plane observability snapshot (ISSUE 16): HBM ledger
    high-water marks, the compile table totals, and per-op roofline
    ratios accumulated across EVERY config above — the bench's own
    device traffic doubles as the evidence run. Slimmed to the leaves
    bench_compare knows how to judge (roofline up-better, compile
    seconds down-better, ledger counts non-headline)."""
    from minio_tpu.obs import device
    st = device.status(touch_backend=True)
    ledger = {lane: {"peak_bytes": row["peak_bytes"],
                     "peak_buffers": row["peak_buffers"],
                     "acquired_total": row["acquired_total"],
                     "donated_total": row["donated_total"]}
              for lane, row in st["ledger"].items()}
    comp = st["compile"]
    roofline = {op: {"roofline_ratio": row["roofline_ratio"],
                     "achieved_gibs": row["achieved_gibs"],
                     "device_seconds": round(row["device_seconds"], 4),
                     "flushes": row["flushes"]}
                for op, row in st["roofline"].items()}
    return {"device_obs": {
        "ledger": ledger,
        "ledger_balanced": st["ledger_balanced"],
        "compiles_total": comp["compiles_total"],
        "compile_seconds_total": round(comp["compile_seconds_total"], 3),
        "compile_storms_total": comp["storms_total"],
        "roofline": roofline,
    }}


def bucket_stats_extra() -> dict:
    """Per-bucket analytics scrape cost (ISSUE 18): the registry folds
    past ``top_n`` buckets, so a 4096-bucket storm must render in about
    the same wall time (and the same series count) as 16 buckets — the
    acceptance bound is scrape_4096 <= 2x scrape_16. Driven directly
    against the registry (the s3api charge path is one dict update on
    top of this), then reset so the synthetic storm leaves no trace in
    later extras."""
    import time as _t

    from minio_tpu.obs import bucketstats as bstats

    def drive(n: int) -> tuple[float, int, dict]:
        bstats.reset()
        for i in range(n):
            bstats.record_request(
                f"bench-{i:05d}", "getobject", 200, 0.002,
                ttfb_s=0.0005, bytes_in=128, bytes_out=4096)
        best = float("inf")
        for _ in range(5):
            t0 = _t.perf_counter()
            lines = bstats.metric_lines()
            best = min(best, (_t.perf_counter() - t0) * 1e3)
        labels = {ln.split('bucket="', 1)[1].split('"', 1)[0]
                  for ln in lines if 'bucket="' in ln}
        rep = bstats.report()
        return best, len(labels), rep

    ms16, labels16, _ = drive(16)
    ms4096, labels4096, rep = drive(4096)
    bstats.reset()
    out = {
        "scrape_16_ms": round(ms16, 3),
        "scrape_4096_ms": round(ms4096, 3),
        "scrape_scaling_overhead": round(ms4096 / max(ms16, 1e-9), 2),
        "series_labels": labels4096,
        "tracked": rep["tracked"],
        "fold_hits": rep["folds"],
    }
    log(f"bucket_stats: scrape 16={out['scrape_16_ms']}ms "
        f"4096={out['scrape_4096_ms']}ms "
        f"(x{out['scrape_scaling_overhead']}), "
        f"labels {labels16}->{labels4096}, folds {rep['folds']}")
    return {"bucket_stats": out}


def main() -> None:
    chaos = "--chaos" in sys.argv[1:]
    device = require_chip()
    rng = np.random.default_rng(0)
    cpu_gibs = cpu_baseline(rng)
    host = host_profile(rng)
    # e2e before the device configs: the device stages' multi-GiB host
    # staging churn measurably degrades kernel page allocation afterwards
    # (tmpfs writes -25%, syscall time ~2x on this host), which would tax
    # the e2e numbers with state the data plane didn't create
    put = e2e_put(rng)
    # durability tax rides the disk-bound slot too
    fsy = fsync_put(rng)
    # chaos rides the same disk-bound slot (before device staging churn)
    cha = chaos_profile(rng) if chaos else None
    dev = device_configs(rng)
    lat = heal_latency(rng)
    # interactive device lane (ISSUE 13): heal-shard p50/p99 on both
    # lane disciplines — rides the same global queue as heal_latency
    ia_lane = interactive_lane_extra(rng)
    # device workloads (ISSUE 8): Select scan + SSE package crypto
    scan = select_scan_bench(rng)
    sse = sse_put_bench(rng)
    # mixed-workload SLO scale harness (ISSUE 10) — after the kernel
    # configs, before the timeline snapshot so its traffic shows there
    scale = scale_slo_extra()
    # node fault tolerance on the 4-node topology (ISSUE 12)
    node_chaos = node_chaos_extra()
    # flight-recorder artifacts LAST so the truncated timeline +
    # attribution report cover every config above (ISSUE 9)
    tl = timeline_extras()
    # device-plane ledger/compile/roofline accumulated over the whole
    # run — snapshot after every config has dispatched (ISSUE 16)
    dev_obs = device_obs_extra()
    # per-bucket analytics scrape cost, AFTER the loadgen extras so the
    # synthetic 4096-bucket storm can reset the registry freely (ISSUE 18)
    bucket_stats = bucket_stats_extra()

    enc = dev["encode_16p4_1MiB_b128"]
    extra_chaos = {"chaos": cha} if cha is not None else {}
    # host-CPU attribution windows (ISSUE 14): one per bounded config,
    # assembled as the standing `host_profile` extra
    host_profile = {"put_par8_16p4": put.pop("host_profile", {}),
                    "heal": lat.pop("host_profile", {})}
    finish({
        "metric": "erasure_encode_gibs_16+4_1MiB_batch128",
        "value": round(enc, 2),
        "unit": "GiB/s",
        "vs_baseline": round(enc / cpu_gibs, 2),
        **device,
        "extra": {
            "cpu_avx2_encode_gibs": round(cpu_gibs, 2),
            "host": host,
            "host_profile": host_profile,   # ISSUE 14 evidence channel
            "e2e_put_gibs": put,                      # config 1
            "fsync_put_gibs": fsy,             # durability tax (PR 6)
            "encode_sweep_8p4_gibs": dev["encode_sweep_8p4"],  # config 2
            "reconstruct_2loss_gibs": round(
                dev["reconstruct_2loss_16p4_b128"], 2),        # config 3
            "fused_verify_reconstruct_gibs": round(
                dev["fused_verify_reconstruct_16p4_b128"], 2),  # config 4
            "fused_verify_reconstruct_hh_gibs": round(
                dev["fused_verify_reconstruct_16p4_b128_hh"], 2),
            "batched_heal_rebuild_gibs": round(
                dev["batched_heal_rebuild_b128"], 2),           # config 5
            "heal_shard_latency": lat,                # north-star p99 half
            **ia_lane,     # both-lanes heal p50/p99 (ISSUE 13)
            "reconstruct_vs_cpu": round(
                dev["reconstruct_2loss_16p4_b128"] / cpu_gibs, 2),
            **scan,                  # device workloads A (docs/select.md)
            **sse,                   # device workloads B (docs/sse.md)
            **scale,      # mixed-workload SLO scale harness (ISSUE 10)
            **node_chaos,      # 4-node kill/heal topology (ISSUE 12)
            **tl,     # flight-recorder timeline + attribution (ISSUE 9)
            **dev_obs,   # HBM ledger + compile + roofline (ISSUE 16)
            **bucket_stats,  # bounded per-bucket scrape cost (ISSUE 18)
            **extra_chaos,                        # --chaos degraded run
        },
    })


if __name__ == "__main__":
    main()
