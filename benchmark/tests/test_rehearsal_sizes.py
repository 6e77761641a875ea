"""CPU rehearsal of kind ``mixed_sizes`` (a whole run but for the look for a
chip) at a cut range of sizes, on a throw-away copy of the benchmark to
which the cell ``tiny-sizes.4p2`` was ADDED as files and entries: 1 KiB to
16 MiB in 14 doublings on 4+2, parts of 5 MiB, so all three routes are
there. Sound it is ``correct``, looks at two keys a doubling on the drives
and finds each layout where its size puts it; the five ``sizes.*`` readers
report from its records. An acknowledged write lost, two drives' shards of
every multipart object swapped and a whole copy of a small body in every
drive's ``Data`` are each not ``correct``, by the check that names them.
Numbers from these runs are the CPU's and are checked for presence or for
a count only. By hand, not tier-1 (~4 min)."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearse  # noqa: E402

CELL = "tiny-sizes.4p2"
LIKE = "put-sizes.8p4"
PART = 5 << 20
TINY_CFG = {**rehearse.TINY_CFG, "name": "tiny-4p2-sizes",
            "geometry": {**rehearse.TINY_CFG["geometry"],
                         "multipart_part_bytes": PART,
                         "multipart_min_bytes": PART + 1}}
TINY_MIX = {"kind": "mixed_sizes", "client_processes": 2,
            "threads_per_process": 2,
            "deck": {"GET": 9, "STAT": 6, "PUT": 3, "DELETE": 2},
            "size_min": 1024, "size_max": 16 << 20, "pool_objects": 56,
            "put_bodies": 14, "at_rest_sample": 28, "readback_sample": 14,
            "verify_env": {"MINIO_TPU_DISPATCH_MODE": "cpu"}}
SIZES = ("sizes.small_get_p95_ms", "sizes.put_mib_s",
         "sizes.put_route_mismatch_share", "sizes.small_wait_share",
         "sizes.get_off_native_block_share")
AT_REST = ("at_rest_body_mismatch", "at_rest_digest_bad",
           "at_rest_parity_mismatch", "at_rest_bytes_over",
           "at_rest_layout_wrong")
_HEAD = '''
import io, os, sys
sys.path.insert(0, os.path.join(sys.argv[1], "benchmark", "tests"))
import cpu_run
from minio_tpu.objectlayer import erasure_objects as eo
from minio_tpu.storage.xlmeta import XLMeta
'''
# after every Complete into the timed bucket the shard files of its first
# part change places on the first two drives
SWAPPED = _HEAD + '''
orig = eo.ErasureObjects.complete_multipart_upload


def complete(self, bucket, key, *a, **kw):
    oi = orig(self, bucket, key, *a, **kw)
    if bucket == "bench":
        paths = []
        for d in self.disks[:2]:
            base = os.path.join(d.endpoint(), bucket, key)
            ddir, = [e for e in os.listdir(base) if e != "xl.meta"]
            paths.append(os.path.join(base, ddir, "part.1"))
        os.rename(paths[0], paths[0] + ".x")
        os.rename(paths[1], paths[0])
        os.rename(paths[0] + ".x", paths[1])
    return oi


eo.ErasureObjects.complete_multipart_upload = complete
sys.exit(cpu_run.main())
'''
# what FS mode keeps, on every drive of an erasure set: after an inline PUT
# into the timed bucket each drive's ``Data`` entry is the whole body
WHOLE_COPY = _HEAD + '''
orig = eo.ErasureObjects._put_object_inner


def put(self, bucket, key, stream, size, opts=None):
    small = bucket == "bench" and 0 < size <= eo.SMALL_FILE_THRESHOLD
    body = stream.read(size) if small else None
    oi = orig(self, bucket, key,
              stream if body is None else io.BytesIO(body), size, opts)
    for d in self.disks if small else ():
        path = os.path.join(d.endpoint(), bucket, key, "xl.meta")
        with open(path, "rb") as f:
            meta = XLMeta.load(f.read())
        meta.data = {k: body for k in meta.data}
        with open(path, "wb") as f:
            f.write(meta.dump())
    return oi


eo.ErasureObjects._put_object_inner = put
sys.exit(cpu_run.main())
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = rehearse.make_copy(str(tmp_path_factory.mktemp("benchsizes")))
    b = os.path.join(dst, "benchmark")
    for path, obj in (("configs/tiny-4p2-sizes.json", TINY_CFG),
                      ("traffic/tiny-sizes.json", TINY_MIX)):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-4p2-sizes", "source": "rehearsal only",
        "file": "benchmark/configs/tiny-4p2-sizes.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-4p2-sizes", "traffic": "tiny-sizes",
        "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def run(copy, *extra, trace="0", seconds="4", script=None):
    argv = ("--workload", CELL, "--seed", "3000000044", "--seconds",
            seconds, "--trace", trace, *extra)
    if script is None:
        rc, last, out = rehearse.cpu_run(copy, *argv)
    else:
        p = subprocess.run(
            [sys.executable, script, copy, *argv],
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=rehearse.REPO, PYTHONDONTWRITEBYTECODE="1"),
            capture_output=True, text=True, timeout=600)
        rc, out = p.returncode, p.stdout + p.stderr
        last = json.loads(p.stdout.strip().splitlines()[-1])
    assert rc == 0 and last is not None, out[-3000:]
    return last, out


def scripted(tmp_path, text):
    script = tmp_path / "under_the_harness.py"
    script.write_text(text)
    return str(script)


def test_sound_run_is_correct_and_finds_each_layout_where_its_size_puts_it(
        copy):
    last, out = run(copy)
    assert last["correct"] is True and last["failed"] == 0, out[-4000:]
    assert set(last["metrics"]) == {"get_p95_ms", "get_mib_s", "setup_s"}
    for name in AT_REST:
        assert last["checks"][name] == {"value": 0, "limit": 0}
    at_rest, = [ln for ln in out.splitlines() if ln.startswith("ATREST ")]
    # 14 doublings from 1 KiB: 7 at or under 128 KiB, then 128 KiB-4 MiB in
    # one part (and the part of 4-8 MiB under 5 MiB + 1), the rest in parts;
    # two keys a doubling, one where the window removed the pool's four
    n = int(at_rest.split()[1])
    import re
    parts = {int(m) for m in re.findall(r"'filesx(\d)': ", at_rest)}
    assert 14 <= n <= 28 and "'inlinex1': " in at_rest \
        and 1 in parts and parts & {2, 3, 4} and max(parts) <= 4, at_rest
    puts = [ln for ln in out.splitlines() if ln.startswith("SAMPLES PUT")]
    assert not puts      # put_p95_ms is not this cell's


def test_traced_run_reads_the_five_sizes_metrics_and_the_table_by_class(
        copy):
    last, out = run(copy, trace="1")
    assert last["correct"] is True, out[-4000:]
    assert set(SIZES) <= set(last["metrics"]), last["metrics"]
    got = {n: last["metrics"][n]["value"] for n in SIZES}
    assert got["sizes.put_route_mismatch_share"] < 5.0, out[-3000:]
    assert got["sizes.small_get_p95_ms"] > 0 and got["sizes.put_mib_s"] > 0
    assert 0 < got["sizes.small_wait_share"] < 100
    assert got["sizes.get_off_native_block_share"] == 0.0, out[-3000:]
    assert last["metrics"]["storage.python_commit_share"]["value"] == 0.0
    assert last["metrics"]["server.http_503_share"]["value"] == 0
    counters, = [ln for ln in out.splitlines()
                 if ln.startswith("COUNTERS moved in the window")]
    for route in ("inline", "file", "multipart"):
        assert f'objectlayer_put_versions_total{{route="{route}"}}' \
            in counters, counters
    stages = [ln.split()[1:3] for ln in out.splitlines()
              if ln.startswith("STAGES ")]
    for cls in ("inline", "file", "multipart"):
        assert {api for c, api in stages if c == cls} >= {
            "getobject", "headobject", "deleteobject"}, stages
    assert ["multipart", "completemultipartupload"] in stages
    assert ["inline", "putobject"] in stages


def test_a_lost_write_is_not_correct_by_live_keys_missing(copy):
    last, out = run(copy, "--control", "lost-write")
    assert last["correct"] is False, out[-3000:]
    assert set(last["failed_checks"]) == {"live_keys_missing"}, \
        last["failed_checks"]


def test_two_drives_shards_of_a_multipart_object_swapped_are_seen_at_rest(
        copy, tmp_path):
    last, out = run(copy, script=scripted(tmp_path, SWAPPED))
    assert last["correct"] is False, out[-3000:]
    fell = last["failed_checks"]
    assert fell.get("at_rest_parity_mismatch", 0) \
        + fell.get("at_rest_body_mismatch", 0) > 0, fell
    assert not {"at_rest_bytes_over", "at_rest_layout_wrong",
                "at_rest_digest_bad"} & set(fell), fell


def test_a_whole_copy_of_a_small_body_in_every_drives_data_is_over_its_bound(
        copy, tmp_path):
    last, out = run(copy, script=scripted(tmp_path, WHOLE_COPY))
    assert last["correct"] is False, out[-3000:]
    assert last["failed_checks"].get("at_rest_bytes_over", 0) > 0, \
        last["failed_checks"]
