"""Dispatch/batching runtime tests: batched results must be bit-identical
to sync paths; concurrent submissions must coalesce into few launches."""
import io
import threading

import numpy as np
import pytest

from minio_tpu.erasure import Erasure
from minio_tpu.ops.rs_jax import get_codec, pack_shards, unpack_shards
from minio_tpu.runtime.dispatch import DispatchQueue


def rng_shards(k, s, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=(k, s), dtype=np.uint8)


def test_batched_encode_matches_sync():
    q = DispatchQueue(max_batch=8, max_delay=0.002)
    codec = get_codec(4, 2)
    futs = []
    datas = []
    for i in range(20):
        d = rng_shards(4, 1024, seed=i)
        datas.append(d)
        futs.append(q.encode(codec, pack_shards(d)))
    for i, f in enumerate(futs):
        got = unpack_shards(f.result(timeout=10))
        want = codec.encode(datas[i])
        np.testing.assert_array_equal(got, want)
    assert q.batches >= 3  # 20 items / max 8 per batch
    assert q.items == 20
    q.stop()


def test_batched_masked_rebuild_mixed_patterns():
    """One batch mixing different loss patterns (per-element masks)."""
    q = DispatchQueue(max_batch=64, max_delay=0.005)
    codec = get_codec(6, 3)
    futs = []
    wants = []
    for i in range(12):
        data = rng_shards(6, 512, seed=100 + i)
        parity = codec.encode(data)
        full = np.concatenate([data, parity])
        # vary the loss pattern per element
        lost = ((i % 6), ((i * 2 + 1) % 9))
        lost = tuple(sorted(set(lost)))[:3]
        present = tuple(j for j in range(9) if j not in lost)[:6]
        masks = codec.target_masks_np(present, lost)
        gathered = np.stack([full[j] for j in present])
        futs.append(q.masked(codec, pack_shards(gathered), masks))
        wants.append((lost, full))
    for f, (lost, full) in zip(futs, wants):
        out = unpack_shards(f.result(timeout=10))
        for row, t in enumerate(lost):
            np.testing.assert_array_equal(out[row], full[t])
    q.stop()


def test_concurrent_streams_coalesce():
    """Many threads encoding simultaneously produce correct results."""
    er = Erasure(4, 2, 64 << 10)
    results = {}
    datas = {i: np.random.default_rng(i).integers(
        0, 256, size=200 << 10, dtype=np.uint8).tobytes() for i in range(8)}

    def work(i):
        from minio_tpu.erasure.streaming import (BufferSink, erasure_decode,
                                                 erasure_encode)
        from minio_tpu.erasure import new_bitrot_writer, new_bitrot_reader
        from minio_tpu.erasure.bitrot import BitrotAlgorithm
        from minio_tpu.erasure.streaming import BufferSource
        algo = BitrotAlgorithm.BLAKE2B256S
        sinks = [BufferSink() for _ in range(6)]
        writers = [new_bitrot_writer(s, algo, er.shard_size())
                   for s in sinks]
        n = erasure_encode(er, io.BytesIO(datas[i]), writers, 4)
        for w in writers:
            w.close()
        size = len(datas[i])
        readers = [new_bitrot_reader(BufferSource(s.getvalue()), algo,
                                     er.shard_file_size(size),
                                     er.shard_size())
                   for s in sinks]
        out = BufferSink()
        erasure_decode(er, out, readers, 0, size, size)
        results[i] = out.getvalue()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(8):
        assert results[i] == datas[i]


def test_async_sync_equivalence_on_erasure():
    er = Erasure(8, 4, 1 << 20)
    data = np.random.default_rng(7).integers(
        0, 256, size=(1 << 20) + 333, dtype=np.uint8).tobytes()
    sync = er.encode_data(data)
    async_ = er.encode_data_async(data).result(timeout=30)
    for a, b in zip(sync, async_):
        np.testing.assert_array_equal(a, b)
    # rebuild_targets_async equivalence
    shards = [s.copy() for s in sync]
    shards[2] = None
    shards[9] = None
    rebuilt = er.rebuild_targets_async(shards, (2, 9)).result(timeout=30)
    np.testing.assert_array_equal(rebuilt[0], sync[2])
    np.testing.assert_array_equal(rebuilt[1], sync[9])
    with pytest.raises(ValueError):
        er.rebuild_targets_async(shards, (0, 1, 2, 3, 9)).result(timeout=30)


def test_cpu_route_matches_device(monkeypatch):
    """Forced-CPU dispatch produces bit-identical results to the device
    path for encode, masked rebuild, and fused verify+rebuild."""
    import numpy as np
    from minio_tpu.native import highwayhash as hhn
    from minio_tpu.ops import rs_jax
    from minio_tpu.runtime.dispatch import DispatchQueue
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY

    codec = rs_jax.get_codec(4, 2)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (4, 4096), dtype=np.uint8)
    words = rs_jax.pack_shards(data)
    present = (1, 2, 3, 4)
    masks = codec.target_masks_np(present, (0, 5))
    chunk = 1024
    digs = hhn.hash256_batch(
        HIGHWAY_KEY, data.reshape(-1, chunk)).reshape(4, -1)
    digs32 = np.ascontiguousarray(digs).view(np.uint32)

    results = {}
    for mode in ("device", "cpu"):
        monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", mode)
        q = DispatchQueue()
        try:
            enc = q.encode(codec, words).result()
            # masked rebuild consumes the chosen PRESENT shards
            gathered = rs_jax.pack_shards(np.stack(
                [data[i] if i < 4 else
                 np.asarray(enc[i - 4 + 0]).view(np.uint8)  # parity rows
                 for i in present]))
            reb = q.masked(codec, gathered, masks).result()
            fused = q.fused(codec, words, masks, digs32, HIGHWAY_KEY, chunk)
            # NOTE: fused uses the k=4 DATA shards as sources with their
            # real digests; masks map chosen->targets, shapes only matter
            out, valid = fused.result()
            results[mode] = (np.asarray(enc), np.asarray(reb),
                             np.asarray(out), np.asarray(valid))
        finally:
            q.stop()
    for a, b in zip(results["device"], results["cpu"]):
        assert np.array_equal(a, b)
    assert results["cpu"][3].all()  # digests valid


def test_cpu_route_fused_detects_corruption(monkeypatch):
    import numpy as np
    from minio_tpu.native import highwayhash as hhn
    from minio_tpu.ops import rs_jax
    from minio_tpu.runtime.dispatch import DispatchQueue
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY

    monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", "cpu")
    codec = rs_jax.get_codec(4, 2)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (4, 4096), dtype=np.uint8)
    chunk = 4096
    digs = hhn.hash256_batch(HIGHWAY_KEY, data.reshape(-1, chunk)).reshape(4, -1)
    digs32 = np.ascontiguousarray(digs).view(np.uint32)
    data[2, 100] ^= 0xFF  # corrupt after digesting
    masks = codec.target_masks_np((0, 1, 2, 3), (4,))
    q = DispatchQueue()
    try:
        out, valid = q.fused(codec, rs_jax.pack_shards(data), masks,
                             digs32, HIGHWAY_KEY, chunk).result()
        assert not valid[2] and valid[[0, 1, 3]].all()
    finally:
        q.stop()


def test_device_hold_coalesces_and_releases(monkeypatch):
    """With the device pipeline saturated, sub-batch buckets are held to
    coalesce; they must still flush (a) when the pipeline drains and (b)
    by the MAX_HOLD_S safety valve even if accounting wedges."""
    from minio_tpu.runtime import dispatch as dp
    monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", "device")
    monkeypatch.setattr(dp, "MAX_HOLD_S", 0.2)
    q = DispatchQueue(max_batch=64, max_delay=0.001)
    codec = get_codec(4, 2)
    # wedge the accounting: pipeline looks permanently saturated
    with q._profile_lock:
        q._dev_inflight = dp.DEVICE_PIPELINE + 1
    d = rng_shards(4, 1024, seed=7)
    futs = [q.encode(codec, pack_shards(d)) for _ in range(5)]
    # released by the safety valve despite "saturation"
    for f in futs:
        got = unpack_shards(f.result(timeout=10))
        np.testing.assert_array_equal(got, codec.encode(d))
    # all five coalesced into one flush while held
    assert q.batches == 1, q.batches
    q.stop()


def test_device_bound_mode_gates():
    from minio_tpu.runtime import dispatch as dp
    q = DispatchQueue(max_batch=8, max_delay=0.001)
    codec = get_codec(4, 2)
    b = dp._Bucket(codec, "encode")
    b.items.append(dp._Pending(words=pack_shards(rng_shards(4, 256)),
                               masks=None))
    import os
    os.environ["MINIO_TPU_DISPATCH_MODE"] = "cpu"
    try:
        assert q._device_bound(b) is False
        os.environ["MINIO_TPU_DISPATCH_MODE"] = "device"
        assert q._device_bound(b) is True
    finally:
        os.environ.pop("MINIO_TPU_DISPATCH_MODE", None)
    q.stop()


def test_stats_count_salvages_by_reason(monkeypatch, caplog):
    """stats() carries what the smoke holds to zero: CPU salvages of
    device work by reason (and the link probe's state, next test). A
    device flush that raises logs at ERROR once per (op, shape), then at
    WARNING."""
    import logging

    from minio_tpu import fault
    q = DispatchQueue(max_batch=8, max_delay=0.001)
    codec = get_codec(4, 2)
    d = rng_shards(4, 1024, seed=3)
    try:
        assert q.stats()["salvaged_items"] == {}
        rid = fault.arm("kernel:*:encode:error(FaultyDisk)@count=1")
        try:
            got = unpack_shards(q.encode(codec, pack_shards(d)).result(10))
        finally:
            fault.disarm(rid)
        np.testing.assert_array_equal(got, codec.encode(d))
        assert q.stats()["salvaged_items"] == {"injected": 1}

        # a flush the device refuses (a compiler error, say): salvaged,
        # counted under its own reason, ERROR first then WARNING
        monkeypatch.setenv("MINIO_TPU_DISPATCH_MODE", "device")

        def refuse(*a, **kw):
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        monkeypatch.setattr(q, "_flush_device", refuse)
        with caplog.at_level(logging.WARNING, logger="minio_tpu.dispatch"):
            for _ in range(2):
                got = unpack_shards(
                    q.encode(codec, pack_shards(d)).result(10))
                np.testing.assert_array_equal(got, codec.encode(d))
        st = q.stats()
        assert st["salvaged_items"] == {"injected": 1,
                                        "device_flush_failed": 2}
        recs = [r for r in caplog.records
                if "device flush failed" in r.getMessage()]
        assert [r.levelno for r in recs] == [logging.ERROR,
                                             logging.WARNING]
        assert "Mosaic failed to compile" in recs[0].getMessage()
        assert st["probe"]["state"] in ("ok", "failed", "pending")
    finally:
        q.stop()


def test_probe_failure_is_logged_and_counted(monkeypatch, caplog):
    import logging

    from minio_tpu.runtime import dispatch as dp

    def boom():
        raise RuntimeError("no device answered")
    monkeypatch.setattr(dp.LinkProfile, "probe", staticmethod(boom))
    q = DispatchQueue(max_batch=8, max_delay=0.001)
    try:
        with caplog.at_level(logging.ERROR, logger="minio_tpu.dispatch"):
            q._kick_probe()
            q._probe_thread.join(timeout=10)
        pr = q.stats()["probe"]
        # >= 1: the queue's own loop may have kicked a probe as well
        assert pr["state"] == "failed" and pr["failures"] >= 1
        assert any("link probe failed" in r.getMessage()
                   and "no device answered" in (r.exc_text or "")
                   + str(r.exc_info) for r in caplog.records)
    finally:
        q.stop()
