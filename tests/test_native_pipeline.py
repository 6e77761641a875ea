"""Native fused data-plane pipeline (mt_put_block / mt_get_block): the fast
path must be byte-identical on disk with the Python/dispatch path, and the
two must interoperate in both directions (a native-written object read by
the dispatch path and vice versa)."""
import io
import os
import tempfile

import numpy as np
import pytest

from minio_tpu import native
from minio_tpu.objectlayer import ErasureObjects
from minio_tpu.storage import XLStorage

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


def _mk(tmp, n=6, parity=2):
    disks = [XLStorage(os.path.join(tmp, f"d{i}")) for i in range(n)]
    ol = ErasureObjects(disks, default_parity=parity)
    ol.make_bucket("b")
    return ol


@pytest.fixture
def ol(tmp_path):
    return _mk(str(tmp_path))


SIZES = [0, 5, 1 << 16, (1 << 20) + 12345, 3 << 20]


@pytest.mark.parametrize("size", SIZES)
def test_native_put_dispatch_get(ol, size, monkeypatch):
    body = np.random.default_rng(size or 1).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    ol.put_object("b", "o", io.BytesIO(body), size)
    monkeypatch.setenv("MINIO_TPU_GET_PATH", "dispatch")
    assert ol.get_object_bytes("b", "o") == body


@pytest.mark.parametrize("size", SIZES)
def test_dispatch_put_native_get(ol, size, monkeypatch):
    body = np.random.default_rng(size or 2).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    monkeypatch.setenv("MINIO_TPU_PUT_PATH", "dispatch")
    ol.put_object("b", "o", io.BytesIO(body), size)
    monkeypatch.delenv("MINIO_TPU_PUT_PATH")
    assert ol.get_object_bytes("b", "o") == body


def test_shard_files_bit_identical(tmp_path, monkeypatch):
    """The exact framed shard bytes must match between paths (readers of
    either kind then interop for free)."""
    body = np.random.default_rng(3).integers(
        0, 256, (2 << 20) + 777, dtype=np.uint8).tobytes()
    roots = {}
    for mode in ("auto", "dispatch"):
        monkeypatch.setenv("MINIO_TPU_PUT_PATH", mode)
        root = tempfile.mkdtemp(dir=tmp_path)
        ol = _mk(root)
        ol.put_object("b", "o", io.BytesIO(body), len(body))
        roots[mode] = root
    monkeypatch.delenv("MINIO_TPU_PUT_PATH")
    for i in range(6):
        a_dir = os.path.join(roots["auto"], f"d{i}", "b", "o")
        b_dir = os.path.join(roots["dispatch"], f"d{i}", "b", "o")
        a_parts = sorted(p for _, _, fs in os.walk(a_dir) for p in fs
                         if p.startswith("part."))
        assert a_parts  # sanity: shards are on disk, not inlined
        for p in a_parts:
            pa = next(os.path.join(dp, p) for dp, _, fs in os.walk(a_dir)
                      if p in fs)
            pb = next(os.path.join(dp, p) for dp, _, fs in os.walk(b_dir)
                      if p in fs)
            with open(pa, "rb") as fa, open(pb, "rb") as fb:
                assert fa.read() == fb.read(), f"disk {i} {p} differs"


def test_native_get_detects_bitrot(ol):
    """Corrupt each disk's shard in turn: whichever erasure index that disk
    holds (data -> the native fused verify must catch it and reconstruct;
    parity -> the healthy read never touches it), the GET must return the
    exact body."""
    body = np.random.default_rng(4).integers(
        0, 256, 2 << 20, dtype=np.uint8).tobytes()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    for disk in ol.disks:
        part = next(os.path.join(dp, f)
                    for dp, _, fs in os.walk(os.path.join(disk.base, "b", "o"))
                    for f in fs if f.startswith("part."))
        with open(part, "r+b") as fh:
            fh.seek(40)  # inside the first chunk payload
            orig = fh.read(1)
            fh.seek(40)
            fh.write(bytes([orig[0] ^ 0xFF]))
        assert ol.get_object_bytes("b", "o") == body, disk.base
        with open(part, "r+b") as fh:  # restore for the next iteration
            fh.seek(40)
            fh.write(orig)


def test_degraded_get_is_native_and_still_heals_on_read(tmp_path):
    """A GET with a data shard's drive emptied: exact bytes through the
    one-call native degraded route, and on_partial (heal-on-read) still
    hears of the object."""
    import shutil
    from minio_tpu.objectlayer.metadata import hash_order
    from minio_tpu.obs import metrics as mx
    ol = _mk(str(tmp_path), n=12, parity=4)
    body = np.random.default_rng(5).integers(
        0, 256, (9 << 20) + 321, dtype=np.uint8).tobytes()
    ol.put_object("b", "o", io.BytesIO(body), len(body))
    # the drive holding data shard 1 loses the object
    drive = hash_order("b/o", 12).index(1)
    shutil.rmtree(os.path.join(str(tmp_path), f"d{drive}", "b", "o"))
    heard = []
    ol.on_partial = lambda *a, **kw: heard.append((a, kw))
    key = 'minio_tpu_pipeline_get_blocks_total{route="%s"}'
    before = mx.counters_snapshot()
    assert ol.get_object_bytes("b", "o") == body
    after = mx.counters_snapshot()
    assert after.get(key % "native_degraded", 0) - \
        before.get(key % "native_degraded", 0) == 3
    assert after.get(key % "fused", 0) == before.get(key % "fused", 0)
    assert [a[:2] for a, _ in heard] == [("b", "o")]


def test_put_block_fds_roundtrip(tmp_path):
    """put_block_fds writes the same framed bytes mt_put_block produces,
    honours fd=-1 skips, and reports per-fd errors without raising."""
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.ops import gf256
    k, m, chunk = 4, 2, 16384
    data = np.random.default_rng(7).integers(
        0, 256, 1 << 20, dtype=np.uint8).tobytes()
    shard_len = len(data) // k
    pmat = gf256.build_matrix(k, m)[k:]
    want = native.put_block(data, len(data), pmat, k, m, shard_len, chunk,
                            HIGHWAY_KEY)
    fl = native.framed_len(shard_len, chunk)
    paths = [os.path.join(tmp_path, f"s{i}") for i in range(k + m)]
    fds = [os.open(p, os.O_CREAT | os.O_WRONLY) for p in paths]
    use = list(fds)
    use[2] = -1          # offline disk: skipped
    errs = native.put_block_fds(data, len(data), pmat, k, m, shard_len,
                                chunk, HIGHWAY_KEY, use, 0)
    for fd in fds:
        os.close(fd)
    assert errs[2] == 0  # skipped, not an error
    assert all(e == 0 for e in errs)
    for i in range(k + m):
        if i == 2:
            assert os.path.getsize(paths[i]) == 0
            continue
        with open(paths[i], "rb") as f:
            assert f.read() == want[i * fl:(i + 1) * fl].tobytes(), i


def test_put_block_fds_reports_bad_fd(tmp_path):
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.ops import gf256
    k, m, chunk = 2, 1, 4096
    data = b"x" * 8192
    shard_len = 4096
    pmat = gf256.build_matrix(k, m)[k:]
    good = os.open(os.path.join(tmp_path, "g"), os.O_CREAT | os.O_WRONLY)
    ro = os.open(os.path.join(tmp_path, "r"), os.O_CREAT | os.O_RDONLY)
    errs = native.put_block_fds(data, len(data), pmat, k, m, shard_len,
                                chunk, HIGHWAY_KEY, [good, ro, -1], 0)
    os.close(good)
    os.close(ro)
    assert errs[0] == 0
    assert errs[1] != 0   # EBADF on the read-only fd
    assert errs[2] == 0   # skipped


def test_fd_path_survives_one_dead_writer_mid_stream(tmp_path):
    """A PUT over 6 disks where one sink's fd goes bad must still land
    with write quorum (the dead disk becomes a vote, not a failure)."""
    ol = _mk(str(tmp_path))
    body = np.random.default_rng(11).integers(
        0, 256, 3 << 20, dtype=np.uint8).tobytes()
    # sabotage disk 5's file writer factory to hand out read-only fds
    orig = ol.disks[5].create_file_writer

    class _RoWriter:
        def __init__(self, inner):
            self._inner = inner
            self._ro = os.open(inner._path, os.O_RDONLY)

        def write(self, b):
            raise OSError("read-only sink")

        def fileno(self):
            return self._ro

        def close(self):
            os.close(self._ro)
            self._inner.close()

        def abort(self):
            os.close(self._ro)
            self._inner.abort()

    ol.disks[5].create_file_writer = \
        lambda v, p: _RoWriter(orig(v, p))
    try:
        ol.put_object("b", "o", io.BytesIO(body), len(body))
    finally:
        ol.disks[5].create_file_writer = orig
    assert ol.get_object_bytes("b", "o") == body


def test_get_block_pread_roundtrip_and_errors(tmp_path):
    """mt_get_block_pread: reads+verifies+assembles from shard files;
    bad fds surface as -(10+i) codes, corruption as the shard index."""
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.ops import gf256
    k, m, chunk = 4, 2, 16384
    data = np.random.default_rng(9).integers(
        0, 256, 1 << 20, dtype=np.uint8).tobytes()
    shard_len = len(data) // k
    pmat = gf256.build_matrix(k, m)[k:]
    framed = native.put_block(data, len(data), pmat, k, m, shard_len,
                              chunk, HIGHWAY_KEY)
    fl = native.framed_len(shard_len, chunk)
    paths = []
    for i in range(k):
        p = os.path.join(tmp_path, f"s{i}")
        with open(p, "wb") as f:
            f.write(framed[i * fl:(i + 1) * fl].tobytes())
        paths.append(p)
    fds = [os.open(p, os.O_RDONLY) for p in paths]
    out, code = native.get_block_pread(fds, [0] * k, k, shard_len, chunk,
                                       HIGHWAY_KEY)
    assert code == -1
    assert out.tobytes() == data
    # corrupt shard 2's payload
    with open(paths[2], "r+b") as f:
        f.seek(40)
        f.write(b"\xff")
    _, code = native.get_block_pread(fds, [0] * k, k, shard_len, chunk,
                                     HIGHWAY_KEY)
    assert code == 2
    # bad fd on shard 1
    os.close(fds[1])
    bad = fds[1]
    _, code = native.get_block_pread([fds[0], bad, fds[2], fds[3]],
                                     [0] * k, k, shard_len, chunk,
                                     HIGHWAY_KEY)
    assert code == -(10 + 1)
    for i in (0, 2, 3):
        os.close(fds[i])


def test_build_key_follows_content_and_flags_not_mtimes(monkeypatch,
                                                        tmp_path):
    """The rebuild key is a hash of the five sources + the compiler
    flags: a changed byte or flag changes it, a touched mtime does not."""
    import shutil
    src = tmp_path / "native"
    src.mkdir()
    for name in native._SOURCES:
        shutil.copy(os.path.join(native._DIR, name), src / name)
    monkeypatch.setattr(native, "_DIR", str(src))
    key = native.build_key()
    assert len(key) == 64
    os.utime(src / "mur3.cpp", (1, 1))
    assert native.build_key() == key
    with open(src / "mur3.cpp", "ab") as f:
        f.write(b"\n")
    changed = native.build_key()
    assert changed != key
    monkeypatch.setattr(native, "BUILD_FLAGS",
                        native.BUILD_FLAGS + ("-DX",))
    assert native.build_key() not in (key, changed)


def test_failed_build_is_one_error_with_the_compilers_words(
        monkeypatch, tmp_path, caplog):
    """One fixed command line; a refusal is an ERROR carrying g++'s own
    output, available() stays False, and nothing half-built is left."""
    import logging
    src = tmp_path / "native"
    src.mkdir()
    for name in native._SOURCES:
        (src / name).write_text("this is not C++\n")
    monkeypatch.setattr(native, "_DIR", str(src))
    monkeypatch.setattr(native, "_BUILD", str(tmp_path / "_build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_error", None)
    with caplog.at_level(logging.ERROR, logger="minio_tpu.native"):
        assert native.available() is False
        assert native.available() is False  # cached: no second build
    errs = [r for r in caplog.records if r.levelno == logging.ERROR]
    assert len(errs) == 1
    msg = errs[0].getMessage()
    assert "g++ -O3 -mavx2 -shared -fPIC" in msg and "error" in msg
    assert os.listdir(tmp_path / "_build") == []


_DEGRADED_CHUNK = 1024
#: payload bytes a shard: whole chunks / a short last chunk / a length
#: that is no multiple of 32 (gf_accum's scalar tail)
_DEGRADED_PLEN = {"full": 4 * _DEGRADED_CHUNK,
                  "tail": 4 * _DEGRADED_CHUNK + 512,
                  "odd": 3 * _DEGRADED_CHUNK + 37}
_DEGRADED_CASES = [(k, m, lost, kind)
                   for k, m in ((4, 2), (8, 4), (16, 4))
                   for lost in range(1, m + 1)
                   for kind in _DEGRADED_PLEN]


@pytest.mark.parametrize("k,m,lost,kind", _DEGRADED_CASES)
def test_get_block_pread_degraded_matches_decode(tmp_path, k, m, lost, kind):
    """mt_get_block_pread_degraded against Erasure.decode_data_blocks over
    real shard files: exact bytes with data and parity shards lost; a
    flipped byte names its source's position; a short file is -(10+i)."""
    from minio_tpu.erasure import Erasure
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.ops import gf256
    chunk, plen = _DEGRADED_CHUNK, _DEGRADED_PLEN[kind]
    rng = np.random.default_rng(1000 * k + 10 * lost + len(kind))
    data = rng.integers(0, 256, k * plen, dtype=np.uint8).tobytes()
    framed = native.put_block(data, len(data), gf256.build_matrix(k, m)[k:],
                              k, m, plen, chunk, HIGHWAY_KEY)
    fl = native.framed_len(plen, chunk)
    paths = []
    for i in range(k + m):
        paths.append(os.path.join(tmp_path, f"s{i}"))
        with open(paths[i], "wb") as f:
            f.write(framed[i * fl:(i + 1) * fl].tobytes())
    # data and parity mixed: the larger half of the loss falls on data
    n_data = (lost + 1) // 2
    gone = set(rng.choice(k, n_data, replace=False).tolist()) | \
        set((k + rng.choice(m, lost - n_data, replace=False)).tolist())
    present = tuple(i for i in range(k + m) if i not in gone)[:k]
    missing = tuple(i for i in range(k) if i in gone)
    er = Erasure(k, m, k * plen)
    rows = er.codec.rebuild_rows(present, missing)
    fds = [os.open(paths[i], os.O_RDONLY) for i in present]
    try:
        def call():
            return native.get_block_pread_degraded(
                fds, [0] * k, present, k, plen, chunk, HIGHWAY_KEY, rows,
                missing)
        out, code = call()
        assert code == -1
        assert out.tobytes() == data
        # the plain reference: the codec's own reconstruct on the payloads
        shards = [None if i in gone else np.frombuffer(
            native.get_block([framed[i * fl:(i + 1) * fl]], 1, plen, chunk,
                             HIGHWAY_KEY)[0], dtype=np.uint8)
            for i in range(k + m)]
        ref = er.decode_data_blocks(shards)
        assert out.tobytes() == b"".join(
            np.asarray(s).tobytes() for s in ref[:k])
        # one flipped payload byte in the LAST chunk of one source
        pos = int(rng.integers(0, k))
        with open(paths[present[pos]], "r+b") as f:
            f.seek(fl - 1)
            last = f.read(1)
            f.seek(fl - 1)
            f.write(bytes([last[0] ^ 0x40]))
        assert call()[1] == pos
        with open(paths[present[pos]], "r+b") as f:
            f.seek(fl - 1)
            f.write(last)
        assert call()[1] == -1
        # a truncated source file
        short = int(rng.integers(0, k))
        os.truncate(paths[present[short]], fl - 7)
        assert call()[1] == -(10 + short)
    finally:
        for fd in fds:
            os.close(fd)
