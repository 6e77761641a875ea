"""Compile the served path's kernels for a DESCRIBED TPU v5e, at the shapes
the server really produces — what interpret mode cannot show: tiling the
chip's compiler refuses, too much fast memory, a program that does not fit
the 16 GB device, a kernel that cannot be partitioned over a mesh. A compile
that passes is not a chip run (chip_smoke.py is); this file only keeps every
later PR from breaking the lowering at no chip time.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), the persistent compile cache is off around these compiles
(a described-device entry can be written but not read back), and code that
asks ``on_tpu()`` is steered in the fixture so the real call chains lower
with ``interpret=False``.

Also here: chip_smoke.py's off-chip refusal, driven in-process.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

W_8P4_4MIB = 131072     # 8+4, default 4 MiB block: 512 KiB shards
W_8P4_1MIB = 32768      # a 1 MiB object's single short block at 8+4
W_16P4_1MIB = 16384     # BASELINE's 16+4 / 1 MiB (chip_smoke leg d)
W_12P4_4MIB = 87382     # 12+4 at 4 MiB: needs padding, the (8, 256) layout
CHUNK = 16384
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import signal

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    on_term = signal.getsignal(signal.SIGTERM)
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        # the TPU library installs its own SIGTERM handler, which dumps a
        # stack trace into the run's output when a time limit cuts the
        # run; this worker goes on to run other files, so leave the
        # signal as it was found
        signal.signal(signal.SIGTERM, on_term)
    from minio_tpu.ops import (chacha_pallas, mur3_pallas, rs_pallas,
                               scan_pallas)
    mods = (chacha_pallas, mur3_pallas, rs_pallas, scan_pallas)
    saved = [m.on_tpu for m in mods]
    for m in mods:
        m.on_tpu = lambda: True
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    for m, f in zip(mods, saved):
        m.on_tpu = f


@pytest.fixture(scope="module")
def S(topo):
    """ShapeDtypeStruct builder: uint32 on one described chip by default."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])

    def make(*shape, sharding=one):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)
    return make


def _codec(k, m):
    from minio_tpu.ops import rs_jax
    return rs_jax.ReedSolomon(k, m, backend="pallas")


def _total_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _static_encode(codec, bsz, w):
    from minio_tpu.ops import rs_pallas
    return rs_pallas._static_batch_call(
        np.ascontiguousarray(codec.parity_rows).tobytes(), codec.m, codec.k,
        bsz, w, False)


def _encode_hashed(codec, w):
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.ops import fused
    return fused.encode_hashed_fn_for(HIGHWAY_KEY, w * 4,
                                      codec.encode_words_batch, CHUNK, 0)


def _fused(codec, w):
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.ops import fused
    return fused.fused_fn_for(HIGHWAY_KEY, w * 4, codec._mm_batch_per,
                              CHUNK, 0)


def test_put_encode_hashed_8p4_largest_fits_hbm(S):
    """Leg b's PUT flush at the bulk lane's largest padded batch: input +
    parity + both hash layouts of 128 full 4 MiB blocks on one chip."""
    c = _encode_hashed(_codec(8, 4), W_8P4_4MIB).lower(
        S(128, 8, W_8P4_4MIB)).compile()
    assert _has_kernel(c)
    need = _total_bytes(c)
    # one program at a time is what the compiler counts; the bulk lane
    # allows DEVICE_PIPELINE of them in flight — 8 clients cannot fill
    # that with 128-block flushes, but a quarter of HBM per program is
    # where this should start failing, not at the device's edge
    assert need < HBM_BYTES // 4, f"{need / 2**30:.2f} GiB"


def test_degraded_get_fused_verify_rebuild_8p4(S):
    """Interactive lane, batch 8, two lost shards, HighwayHash verify."""
    nc = W_8P4_4MIB * 4 // CHUNK
    c = _fused(_codec(8, 4), W_8P4_4MIB).lower(
        S(8, 8, 2, 8), S(8, 8, W_8P4_4MIB), S(8, 8, nc * 8)).compile()
    assert _has_kernel(c)


def test_heal_rebuild_donated_and_bulk_8p4(S):
    """The donated interactive rebuild and the bulk lane's batch 128."""
    codec = _codec(8, 4)
    c = codec.batch_per_donated().lower(
        S(8, 8, 2, 8), S(8, 8, W_8P4_4MIB)).compile()
    assert _has_kernel(c)
    c = codec._mm_batch_per.lower(
        S(128, 8, 4, 8), S(128, 8, W_8P4_4MIB)).compile()
    assert _has_kernel(c) and _total_bytes(c) < HBM_BYTES // 4


def test_padded_layout_12p4(S):
    """87,382-word shards pad to the (8, 256) layout: static encode and
    dynamic rebuild."""
    import jax.numpy as jnp
    codec = _codec(12, 4)
    c = _static_encode(codec, 8, W_12P4_4MIB).lower(
        S(8, 12, W_12P4_4MIB), jnp.uint32(0)).compile()
    assert _has_kernel(c)
    c = codec._mm_batch_per.lower(
        S(8, 8, 2, 12), S(8, 12, W_12P4_4MIB)).compile()
    assert _has_kernel(c)


def test_north_star_16p4_encode_and_rebuild(S):
    """chip_smoke leg d: static encode and 2-loss rebuild, 128 x 1 MiB."""
    import jax.numpy as jnp
    codec = _codec(16, 4)
    c = _static_encode(codec, 128, W_16P4_1MIB).lower(
        S(128, 16, W_16P4_1MIB), jnp.uint32(0)).compile()
    assert _has_kernel(c)
    c = codec._mm_batch_per.lower(
        S(128, 8, 2, 16), S(128, 16, W_16P4_1MIB)).compile()
    assert _has_kernel(c)


def test_north_star_16p4_encode_hashed(S):
    """Leg d's HighwayHash flush (its fused verify+rebuild lowers the same
    program family as the 8+4 case above; one of the two is kept)."""
    c = _encode_hashed(_codec(16, 4), W_16P4_1MIB).lower(
        S(128, 16, W_16P4_1MIB)).compile()
    assert _has_kernel(c)


def test_mur3_pallas_hash_lane(S):
    """The Pallas MUR3 kernel inside the fused PUT flush (algo id 1) —
    written after the last chip record, interpret-only until now."""
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.ops import fused
    codec = _codec(16, 4)
    fn = fused.encode_hashed_fn_for(HIGHWAY_KEY, W_16P4_1MIB * 4,
                                    codec.encode_words_batch, CHUNK, 1)
    c = fn.lower(S(8, 16, W_16P4_1MIB)).compile()
    assert c.as_text().count("tpu_custom_call") >= 2  # encode + hash


def test_select_scan_server_block(S):
    """Leg c: the WHERE scan at the server's default 1 MiB scan block."""
    import jax
    from minio_tpu.ops import scan_pallas
    # interpret=False explicitly: the factories are lru-cached, and an
    # entry resolved under the steered on_tpu() must not be what a later
    # CPU test of this worker gets back
    fn = scan_pallas.scan_fn_for((("num", 1, "gt", 9990),), (0, 1, 2), 44,
                                 1 << 20, 4096, interpret=False)
    c = jax.jit(fn).lower(S(8, (1 << 20) // 4)).compile()
    assert _has_kernel(c)


def test_sse_xor_multi_package(S):
    """Leg c: the multi-key ChaCha launch, 16 x 64 KiB packages per item."""
    from minio_tpu.ops import chacha_pallas
    c = chacha_pallas.multi_jitted(16, 16384, interpret=False).lower(
        S(4, 8), S(4, 16, 3), S(4, 16, 16384)).compile()
    assert _has_kernel(c)


def test_mesh_routes_and_sharded_step_four_chips(topo, S):
    """--chips 4: the dispatch plane's shard_map routes and the
    ("objects","shards") step against a Mesh of the described devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from minio_tpu.runtime import mesh as mm
    codec = _codec(16, 4)
    mesh = Mesh(np.array(topo.devices), ("objects",))
    split = NamedSharding(mesh, P("objects"))
    whole = NamedSharding(mesh, P())
    x = S(128, 16, W_16P4_1MIB, sharding=split)
    c = mm.sharded_batched(codec._mm_batch, mesh, (False, True)).lower(
        S(8, 4, 16, sharding=whole), x).compile()
    assert _has_kernel(c)
    c = mm.sharded_batched(codec._mm_batch_per, mesh, (True, True)).lower(
        S(128, 8, 2, 16, sharding=split), x).compile()
    assert _has_kernel(c)
    step, m2 = mm.build_sharded_step(16, 4, 4, devices=topo.devices)
    shards = NamedSharding(m2, P(None, None, "shards"))
    c = step.lower(
        S(8, 4, 16, sharding=shards), S(8, 16, 16, sharding=shards),
        S(8, 16, 512, sharding=NamedSharding(
            m2, P("objects", "shards", None)))).compile()
    assert "all-gather" in c.as_text()


def test_compile_cache_is_placed_in_one_place(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no other directory is set in code.
    Unset: <checkout>/.jax_cache. And one file of the program does it."""
    import jax
    from minio_tpu import ops
    saved = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert ops.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs < 1.0
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert ops.configure_compile_cache() == "/x"
        assert jax.config.jax_compilation_cache_dir is None  # untouched
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
    # the program: the package plus the root-level entry scripts
    program = [os.path.join(base, f)
               for base, _, files in os.walk(os.path.join(ROOT, "minio_tpu"))
               for f in files if f.endswith(".py")]
    program += [os.path.join(ROOT, f) for f in os.listdir(ROOT)
                if f.endswith(".py")]
    setters = []
    for path in program:
        with open(path, encoding="utf-8") as fh:
            if "compilation_cache_dir" in fh.read():
                setters.append(os.path.relpath(path, ROOT))
    assert setters == [os.path.join("minio_tpu", "ops", "__init__.py")]


def test_chip_smoke_refuses_off_chip(capsys):
    """With JAX on the CPU the smoke exits non-zero at once, says why, and
    prints no ok line (in-process: a child would load the TPU library)."""
    import jax
    assert jax.devices()[0].platform == "cpu"
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    for argv in ([], ["--chips", "4"]):
        rc = chip_smoke.main(argv)
        out = capsys.readouterr().out
        assert rc != 0
        assert "refusing to run" in out and "'cpu'" in out
        for line in out.splitlines():
            try:
                assert not json.loads(line).get("ok")
            except ValueError:
                pass
