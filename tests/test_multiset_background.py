"""Background services over multi-set and multi-pool topologies (round-2
review flagged heal/scan as iterating only one set's assumptions): the
global healer and scanner must cover every set of every pool through the
streaming metacache iterators."""
import io
import os
import shutil

import numpy as np
import pytest

from minio_tpu.objectlayer.pools import ServerPools
from minio_tpu.objectlayer.sets import ErasureSets
from minio_tpu.scanner.autoheal import GlobalHealer
from minio_tpu.scanner.scanner import DataScanner
from minio_tpu.storage import XLStorage


def _sets(tmp_path, tag, set_count=2, drives=4):
    disks = [XLStorage(os.path.join(tmp_path, f"{tag}{i}"))
             for i in range(set_count * drives)]
    return ErasureSets(disks, set_count, drives, default_parity=2), disks


@pytest.mark.parametrize("size", [8 << 10, 160 << 10],
                         ids=["inline", "files"])
def test_global_heal_covers_all_sets(tmp_path, size):
    """At both layouts: shard files (over 128 KiB) and a shard a drive
    inside xl.meta (at or under it), which ``check_parts`` holds to its
    framed length wherever it lies."""
    sets, disks = _sets(str(tmp_path), "s")
    sets.make_bucket("mb")
    rng = np.random.default_rng(0)
    names = [f"obj-{i:02d}" for i in range(24)]
    for n in names:
        b = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        sets.put_object("mb", n, io.BytesIO(b), len(b))
    # confirm both sets actually own objects (hash placement)
    owners = {sets.get_hashed_set_index(n) for n in names}
    assert owners == {0, 1}
    # wipe one disk in EACH set
    for victim in (disks[1], disks[6]):
        shutil.rmtree(os.path.join(victim.base, "mb"))
        os.makedirs(os.path.join(victim.base, "mb"))
    res = GlobalHealer(sets, concurrency=8).heal_all()
    assert res["objects_healed"] == 24, res
    # shards are back on both wiped disks — metadata AND part data
    # (read_version alone would pass even if heal forgot the part files)
    set0_names = [n for n in names if sets.get_hashed_set_index(n) == 0]
    set1_names = [n for n in names if sets.get_hashed_set_index(n) == 1]
    for disk, name in ((disks[1], set0_names[0]),
                       (disks[6], set1_names[0])):
        fi = disk.read_version("mb", name, read_data=True)
        assert (fi.data is not None) == (size <= 128 << 10)
        assert os.path.isdir(os.path.join(
            disk.base, "mb", name, fi.data_dir)) == (fi.data is None)
        disk.check_parts("mb", name, fi)
        # a FileInfo read without its data is checked against the journal
        disk.check_parts("mb", name, disk.read_version("mb", name))
    # and the full objects decode end-to-end
    for n in names:
        sink = io.BytesIO()
        sets.get_object("mb", n, sink)
        assert len(sink.getvalue()) == size


def test_scanner_usage_covers_pools(tmp_path):
    sets_a, _ = _sets(str(tmp_path), "pa", set_count=1)
    sets_b, _ = _sets(str(tmp_path), "pb", set_count=1)
    pools = ServerPools([sets_a, sets_b])
    pools.make_bucket("pb1")
    rng = np.random.default_rng(1)
    # write through the pools layer: placement picks pools by free space /
    # existing versions; force objects into BOTH pools by writing directly
    for i in range(4):
        b = rng.integers(0, 256, 4 << 10, dtype=np.uint8).tobytes()
        sets_a.put_object("pb1", f"a{i}", io.BytesIO(b), len(b))
        sets_b.put_object("pb1", f"b{i}", io.BytesIO(b), len(b))
    sc = DataScanner(pools, sleep_per_object=0)
    snap = sc.scan_cycle()
    assert snap["buckets"]["pb1"]["objects"] == 8  # both pools counted
    # the pools-level iterator sees every object exactly once
    got = sorted(oi.name for oi in pools.iter_objects("pb1"))
    assert got == [f"a{i}" for i in range(4)] + [f"b{i}" for i in range(4)]
