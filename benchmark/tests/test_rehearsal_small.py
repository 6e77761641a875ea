"""CPU rehearsal of kind ``mixed_small`` (a whole run but for the look for a
chip) at a cut pool, on a throw-away copy of the benchmark to which the cell
``tiny-small.4p2`` was ADDED as files and entries: sound it is ``correct``
and the three per-layer metrics read 100, 100 and 0; with the program's
inline threshold taken to 0 (the parent's case: shard files at every size)
it stays ``correct`` and the shares read 0; a whole copy of the body in
every drive's ``Data``, two drives' shards swapped and an acknowledged
write lost are each not ``correct``, by the check that names them. Numbers
from these runs are the CPU's and are checked for presence or for a count
only. By hand, not tier-1 (~3 min)."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearse  # noqa: E402

CELL = "tiny-small.4p2"
LIKE = "warp-mixed-small.8p4"
TINY_CFG = {**rehearse.TINY_CFG, "name": "tiny-4p2-small"}
TINY_MIX = {**rehearse.TINY_MIXED, "kind": "mixed_small",
            "pool_objects": 48, "object_bytes": 65536,
            "readback_sample": 4, "at_rest_sample": 8}
SHARES = ("inline.put_share", "inline.get_share",
          "storage.python_commit_share")
_HEAD = '''
import io, os, sys
sys.path.insert(0, os.path.join(sys.argv[1], "benchmark", "tests"))
import cpu_run
from minio_tpu.objectlayer import erasure_objects as eo
from minio_tpu.storage.xlmeta import XLMeta
'''
# the parent's case: this layer writes shard files at every size
NO_INLINE = _HEAD + '''
eo.SMALL_FILE_THRESHOLD = 0
sys.exit(cpu_run.main())
'''
# what FS mode keeps, on every drive of an erasure set: after a PUT into
# the timed bucket each drive's ``Data`` entry is the whole body
_AFTER_PUT = _HEAD + '''
orig = eo.ErasureObjects._put_object_inner


def edit(self, bucket, key, body):
    raise NotImplementedError


def put(self, bucket, key, stream, size, opts=None):
    body = stream.read(size) if bucket == "bench" else None
    oi = orig(self, bucket, key,
              stream if body is None else io.BytesIO(body), size, opts)
    if body is not None:
        edit(self, bucket, key, body)
    return oi


def journals(self, bucket, key):
    for d in self.disks:
        path = os.path.join(d.endpoint(), bucket, key, "xl.meta")
        with open(path, "rb") as f:
            yield path, XLMeta.load(f.read())


def store(path, meta):
    with open(path, "wb") as f:
        f.write(meta.dump())


eo.ErasureObjects._put_object_inner = put
'''
WHOLE_COPY = _AFTER_PUT + '''
def edit(self, bucket, key, body):
    for path, meta in journals(self, bucket, key):
        meta.data = {k: body for k in meta.data}
        store(path, meta)


sys.exit(cpu_run.main())
'''
SWAPPED = _AFTER_PUT + '''
def edit(self, bucket, key, body):
    (pa, a), (pb, b) = list(journals(self, bucket, key))[:2]
    a.data, b.data = ({k: v for k, v in zip(a.data, b.data.values())},
                      {k: v for k, v in zip(b.data, a.data.values())})
    store(pa, a)
    store(pb, b)


sys.exit(cpu_run.main())
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = rehearse.make_copy(str(tmp_path_factory.mktemp("benchsmall")))
    b = os.path.join(dst, "benchmark")
    for path, obj in (("configs/tiny-4p2-small.json", TINY_CFG),
                      ("traffic/tiny-small.json", TINY_MIX)):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-4p2-small", "source": "rehearsal only",
        "file": "benchmark/configs/tiny-4p2-small.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-4p2-small", "traffic": "tiny-small",
        "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def run(copy, *extra, trace="0", seconds="3", script=None):
    argv = ("--workload", CELL, "--seed", "3000000039", "--seconds",
            seconds, "--trace", trace, *extra)
    if script is None:
        rc, last, out = rehearse.cpu_run(copy, *argv)
    else:
        import subprocess
        p = subprocess.run(
            [sys.executable, script, copy, *argv],
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=rehearse.REPO,
                     PYTHONDONTWRITEBYTECODE="1"),
            capture_output=True, text=True, timeout=600)
        rc, out = p.returncode, p.stdout + p.stderr
        last = json.loads(p.stdout.strip().splitlines()[-1])
    assert rc == 0 and last is not None, out[-3000:]
    return last, out


def scripted(tmp_path, text):
    script = tmp_path / "under_the_harness.py"
    script.write_text(text)
    return str(script)


def test_sound_run_is_correct_and_looks_at_the_drives(copy):
    last, out = run(copy)
    assert last["correct"] is True and last["failed"] == 0, out[-4000:]
    # the PUT tail is the per-layer server.put_p95_ms in this cell (PERF.md
    # section 6, PR 39: it spreads too widely across leases to be bounded)
    assert set(last["metrics"]) == {"get_p95_ms", "get_mib_s", "setup_s"}
    for name in ("at_rest_body_mismatch", "at_rest_digest_bad",
                 "at_rest_parity_mismatch", "at_rest_bytes_over"):
        assert last["checks"][name] == {"value": 0, "limit": 0}
    at_rest = [ln for ln in out.splitlines() if ln.startswith("ATREST ")]
    assert len(at_rest) == 1 and "layouts {'inline': 8}" in at_rest[0] \
        and "(4 PUT in the window)" in at_rest[0], at_rest


def test_traced_run_reads_the_three_shares(copy):
    last, out = run(copy, trace="1")
    assert last["correct"] is True, out[-4000:]
    assert set(last["metrics"]) == {
        "server.http_503_share", "server.stat_p50_ms", "server.put_p95_ms",
        "device.compiles_in_window", "device.idle_share", *SHARES}
    got = {n: last["metrics"][n]["value"] for n in SHARES}
    assert got == {"inline.put_share": 100.0, "inline.get_share": 100.0,
                   "storage.python_commit_share": 0.0}, out[-3000:]
    assert last["metrics"]["server.http_503_share"]["value"] == 0
    assert any(ln.startswith("COUNTERS moved in the window")
               and 'objectlayer_inline_versions_total{op="put"}' in ln
               for ln in out.splitlines())


def test_shard_files_at_every_size_are_correct_and_the_shares_read_0(
        copy, tmp_path):
    """``correct`` holds the guarantees, not the mechanism: with the
    threshold at 0 (the parent's layout) the reference reads the shard
    files and finds the same; that the mechanism did no work is what the
    per-layer shares say."""
    last, out = run(copy, trace="1", script=scripted(tmp_path, NO_INLINE))
    assert last["correct"] is True, out[-4000:]
    assert "layouts {'files': 8}" in out
    got = {n: last["metrics"][n]["value"] for n in SHARES}
    assert got == {"inline.put_share": 0.0, "inline.get_share": 0.0,
                   "storage.python_commit_share": 0.0}, out[-3000:]


def test_a_whole_copy_in_every_drives_data_is_over_the_bytes_at_rest(
        copy, tmp_path):
    last, out = run(copy, script=scripted(tmp_path, WHOLE_COPY))
    assert last["correct"] is False, out[-3000:]
    assert last["failed_checks"].get("at_rest_bytes_over", 0) > 0, \
        last["failed_checks"]


def test_two_drives_shards_swapped_are_seen_at_rest(copy, tmp_path):
    last, out = run(copy, script=scripted(tmp_path, SWAPPED))
    assert last["correct"] is False, out[-3000:]
    fell = last["failed_checks"]
    assert fell.get("at_rest_parity_mismatch", 0) \
        + fell.get("at_rest_body_mismatch", 0) > 0, fell


def test_a_lost_write_is_not_correct(copy):
    last, out = run(copy, "--control", "lost-write")
    assert last["correct"] is False and last["failed"] > 0
    bad = [ln for ln in out.splitlines()
           if ln.startswith("CHECK live_keys_missing ")]
    assert bad and int(bad[0].split()[2]) > 0, out[-3000:]
