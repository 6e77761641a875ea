"""The plain at-rest reference ``benchmark/lib/inline_ref.py`` (tier-1 copy
of the cases of ``benchmark/tests/test_rehearsal_small.py``): it holds
both layouts (a shard a drive inside ``xl.meta``; shard files), and it
sees a frame whose digest is wrong, a parity shard that is not the encode
of the data shards, two drives' shards swapped, a whole copy of the body
in every drive's ``Data``, a truncated journal. It imports nothing of the
program; here the program writes what it reads."""
import ast
import hashlib
import io
import json
import os
import struct
import sys
import zlib

import msgpack
import numpy as np
import pytest

from minio_tpu.objectlayer import ErasureObjects
from minio_tpu.ops import gf256
from minio_tpu.storage import XLStorage

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))
import inline_ref  # noqa: E402


def _geometry(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["geometry"]


GEOMS = {"8p4": (12, 4, _geometry("xl-8p4-12d-small")),
         "4p2": (6, 2, _geometry("xl-4p2-6d"))}
#: both layouts: at or under 128 KiB inline, over it shard files (the
#: second spans two erasure blocks and has a short last chunk)
SIZES = {"inline": 65536, "files": (4 << 20) + 70001}


def body_of(seed, size):
    return np.random.default_rng([391, seed]).bytes(size)


@pytest.fixture(params=[(g, lay) for g in sorted(GEOMS)
                        for lay in sorted(SIZES)],
                ids=lambda p: f"{p[0]}-{p[1]}")
def held(request, tmp_path):
    """One object of one layout on one fresh set: (object directories,
    geometry, body, layout)."""
    g, layout = request.param
    n, parity, geom = GEOMS[g]
    dirs = [str(tmp_path / f"d{i:02d}") for i in range(n)]
    ol = ErasureObjects([XLStorage(d) for d in dirs], default_parity=parity)
    ol.make_bucket("b")
    body = body_of(n, SIZES[layout])
    ol.put_object("b", "k/o", io.BytesIO(body), len(body))
    return [os.path.join(d, "b", "k", "o") for d in dirs], geom, body, layout


def check(held):
    ods, geom, body, _ = held
    return inline_ref.check_object(ods, geom, len(body),
                                   hashlib.sha256(body).hexdigest())


def rewrite(obj_dir, change):
    """Parse a journal the reference's way, let ``change(doc)`` edit it,
    and write it back with a good trailer."""
    path = os.path.join(obj_dir, "xl.meta")
    with open(path, "rb") as f:
        blob = f.read()
    doc = msgpack.unpackb(blob[8:-8], raw=False, strict_map_key=False)
    change(doc)
    out = blob[:8] + msgpack.packb(doc, use_bin_type=True)
    out += b"XLC1" + struct.pack("<I", zlib.crc32(out) & 0xFFFFFFFF)
    with open(path, "wb") as f:
        f.write(out)


def shard_path(obj_dir):
    ddir = [n for n in os.listdir(obj_dir) if n != "xl.meta"][0]
    return os.path.join(obj_dir, ddir, "part.1")


def flip(held, drive, at):
    """One byte of drive ``drive``'s shard, whichever way it is kept."""
    ods, _, _, layout = held

    def in_data(doc):
        (ddir, shard), = doc["Data"].items()
        b = bytearray(shard)
        b[at] ^= 0x21
        doc["Data"][ddir] = bytes(b)

    if layout == "inline":
        rewrite(ods[drive], in_data)
    else:
        with open(shard_path(ods[drive]), "r+b") as f:
            f.seek(at)
            c = f.read(1)
            f.seek(at)
            f.write(bytes([c[0] ^ 0x21]))


def index_of(obj_dir):
    return inline_ref.drive_copy(obj_dir)["index"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "lib", "inline_ref.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert "minio_tpu" not in names and "jax" not in names
    assert names <= {"__future__", "functools", "hashlib", "os", "struct",
                     "zlib", "msgpack", "numpy", "hh_ref"}


@pytest.mark.parametrize("k,m", [(8, 4), (4, 2), (2, 2), (16, 4)])
def test_its_matrix_and_its_encode_are_the_codecs(k, m):
    """Built from log/antilog tables alone, equal to the program's."""
    want = gf256.build_matrix(k, m)[k:]
    assert np.array_equal(np.array(inline_ref.parity_matrix(k, m)), want)
    data = np.random.default_rng(k).integers(0, 256, (k, 4099), np.uint8)
    data[0, :7] = 0
    assert np.array_equal(inline_ref.encode_parity(data, m),
                          gf256.gf_matmul_ref(want, data))


def test_holds_what_the_program_wrote_in_either_layout(held):
    ods, geom, body, layout = held
    got = check(held)
    assert (got["body_mismatch"], got["digest_bad"],
            got["parity_mismatch"]) == (0, 0, 0), got["why"]
    assert got["layout"] == layout and got["size"] == len(body)
    assert got["bytes"] <= inline_ref.at_rest_limit(len(body), geom)
    assert got["bytes"] > len(body)     # (k+m)/k of it, and the journals
    # a body that is not the one PUT
    other = inline_ref.check_object(ods, geom, len(body), "0" * 64)
    assert other["body_mismatch"] == 1


def test_sees_a_frame_whose_digest_is_wrong(held):
    ods, geom, body, layout = held
    k = geom["data"]
    data = next(i for i, od in enumerate(ods) if index_of(od) == 1)
    par = next(i for i, od in enumerate(ods) if index_of(od) == k + 1)
    flip(held, data, 5)            # inside a digest: the body is untouched
    got = check(held)
    assert (got["digest_bad"], got["body_mismatch"],
            got["parity_mismatch"]) == (1, 0, 0), got["why"]
    flip(held, par, 32 + 100)      # inside a parity chunk
    got = check(held)
    assert (got["digest_bad"], got["body_mismatch"],
            got["parity_mismatch"]) == (2, 0, 1), got["why"]


def test_sees_a_parity_shard_that_is_not_the_encode(held):
    """A parity shard replaced by another, framed RIGHT (its digests are
    its own): only the encode can tell."""
    ods, geom, body, layout = held
    k = geom["data"]
    a = next(i for i, od in enumerate(ods) if index_of(od) == k + 1)
    b = next(i for i, od in enumerate(ods) if index_of(od) == k + 2)
    swap(held, a, b)
    got = check(held)
    assert got["digest_bad"] == 0 and got["body_mismatch"] == 0
    assert got["parity_mismatch"] == 2, got["why"]


def test_sees_two_data_drives_shards_swapped(held):
    ods, geom, body, layout = held
    a = next(i for i, od in enumerate(ods) if index_of(od) == 1)
    b = next(i for i, od in enumerate(ods) if index_of(od) == 2)
    swap(held, a, b)
    got = check(held)
    assert got["digest_bad"] == 0 and got["body_mismatch"] == 1, got["why"]
    assert got["parity_mismatch"] >= 1


def swap(held, a, b):
    ods, _, _, layout = held
    if layout == "files":
        pa, pb = shard_path(ods[a]), shard_path(ods[b])
        with open(pa, "rb") as f:
            sa = f.read()
        with open(pb, "rb") as f:
            sb = f.read()
        with open(pa, "wb") as f:
            f.write(sb)
        with open(pb, "wb") as f:
            f.write(sa)
        return
    got = {}
    for i in (a, b):
        rewrite(ods[i], lambda doc, i=i: got.__setitem__(
            i, next(iter(doc["Data"].values()))))

    def put(shard):
        def change(doc):
            (ddir,) = doc["Data"]
            doc["Data"][ddir] = shard
        return change
    rewrite(ods[a], put(got[b]))
    rewrite(ods[b], put(got[a]))


@pytest.mark.parametrize("held", [("8p4", "inline"), ("4p2", "inline")],
                         indirect=True, ids=["8p4", "4p2"])
def test_a_whole_copy_in_every_drives_data_is_over_the_limit(held):
    """What FS mode keeps (the body itself in ``Data``) on every drive of
    an erasure set: 12x the bytes where EC:4 promises 1.5x."""
    ods, geom, body, layout = held

    def whole(doc):
        (ddir,) = doc["Data"]
        doc["Data"][ddir] = body
    for od in ods:
        rewrite(od, whole)
    got = check(held)
    assert got["bytes"] > inline_ref.at_rest_limit(len(body), geom)
    assert got["digest_bad"] == len(ods)    # and no copy frames right


def test_a_drive_without_the_object_and_a_torn_journal(held):
    ods, geom, body, layout = held
    k = geom["data"]
    import shutil
    par = next(i for i, od in enumerate(ods) if index_of(od) == k + 1)
    shutil.rmtree(ods[par])
    got = check(held)
    assert (got["body_mismatch"], got["digest_bad"],
            got["parity_mismatch"]) == (0, 0, 0), got["why"]
    data = next(i for i, od in enumerate(ods) if os.path.isdir(od)
                and index_of(od) == 1)
    path = os.path.join(ods[data], "xl.meta")
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[:-3])
    got = check(held)
    assert got["digest_bad"] == 1 and got["body_mismatch"] == 1
    with pytest.raises(inline_ref.Bad):
        inline_ref.parse_xl_meta(blob[:-3])
    with pytest.raises(inline_ref.Bad):
        inline_ref.parse_xl_meta(b"nonsense" + blob[8:])
