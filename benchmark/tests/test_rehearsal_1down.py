"""CPU rehearsal of kind ``mixed_1down`` (a whole run but for the look for
a chip) at a tiny size, on a throw-away copy of the benchmark to which the
cell ``tiny-1down.4p2`` was ADDED as files and entries: sound it is
``correct``, with an acknowledged write lost it is not, and with the
program's offline-drive rules taken back to the parent's the per-layer
metric ``mrf.heal_passes_per_op`` tells the two programs apart (under
0.05 against over 0.25). Numbers
from these runs are the CPU's and are checked for presence or for a count
only."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearse  # noqa: E402

CELL = "tiny-1down.4p2"
TINY_CFG = {**rehearse.TINY_CFG, "name": "tiny-4p2-1down",
            "offline_drives": 1}
TINY_MIX = {**rehearse.TINY_MIXED, "kind": "mixed_1down",
            "return_objects": 3, "trip_wait_s": 60.0,
            "reonline_wait_s": 30.0, "restore_wait_s": 60.0,
            "gone_wait_s": 30.0}
PASSES = "mrf.heal_passes_per_op"
# the parent's rules, put back underneath the harness: a read charges for
# an offline drive as for a missing shard, and a heal that found only
# offline drives goes up the backoff ladder
PARENT_RULES = '''
import os, sys
sys.path.insert(0, os.path.join(sys.argv[1], "benchmark", "tests"))
import cpu_run
from minio_tpu.objectlayer.erasure_objects import ErasureObjects
from minio_tpu.scanner.mrf import MRFHealer
from minio_tpu.utils import errors


def charge_offline_too(self, bucket, object, version_id, errs,
                       extra_degraded=False):
    if extra_degraded or any(e is not None for e in errs):
        self._notify_partial(bucket, object, version_id)


ErasureObjects._signal_read_faults = charge_offline_too
MRFHealer._wait_for = lambda self, item, endpoints: False
sys.exit(cpu_run.main())
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = rehearse.make_copy(str(tmp_path_factory.mktemp("bench1down")))
    b = os.path.join(dst, "benchmark")
    for path, obj in (("configs/tiny-4p2-1down.json", TINY_CFG),
                      ("traffic/tiny-1down.json", TINY_MIX)):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-4p2-1down", "source": "rehearsal only",
        "file": "benchmark/configs/tiny-4p2-1down.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-4p2-1down", "traffic": "tiny-1down",
        "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "warp-mixed-1down.8p4" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def run(copy, *extra, trace="0", seconds="3", script=None, env=None):
    argv = ("--workload", CELL, "--seed", "3000000007", "--seconds",
            seconds, "--trace", trace, *extra)
    if script is None:
        rc, last, out = rehearse.cpu_run(copy, *argv)
    else:
        import subprocess
        p = subprocess.run(
            [sys.executable, script, copy, *argv],
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=rehearse.REPO,
                     PYTHONDONTWRITEBYTECODE="1", **(env or {})),
            capture_output=True, text=True, timeout=600)
        rc, out = p.returncode, p.stdout + p.stderr
        last = json.loads(p.stdout.strip().splitlines()[-1])
    assert rc == 0 and last is not None, out[-3000:]
    return last, out


def test_sound_run_is_correct_and_says_what_it_saw(copy):
    last, out = run(copy)
    assert last["correct"] is True and last["failed"] == 0, out[-4000:]
    assert set(last["metrics"]) == {"get_p95_ms", "get_mib_s", "put_p95_ms",
                                    "setup_s"}
    lines = out.splitlines()
    assert any(ln.startswith("DRIVE ") and "is dead" in ln for ln in lines)
    assert any(ln.startswith("COUNTERS ") and "native_degraded" in ln
               for ln in lines)
    assert "NOTE other_drives_tripped []" in lines
    assert any(ln.startswith("RETURN online again") for ln in lines)


def test_traced_run_reads_the_new_metrics(copy):
    last, out = run(copy, trace="1")
    assert last["correct"] is True, out[-4000:]
    assert set(last["metrics"]) == {
        "server.http_503_share", "server.stat_p50_ms",
        "device.compiles_in_window", "device.idle_share", PASSES,
        "pipeline.off_native_block_share"}
    assert last["metrics"]["pipeline.off_native_block_share"]["value"] == 0
    assert last["metrics"][PASSES]["value"] < 0.05
    assert last["metrics"]["server.http_503_share"]["value"] == 0


def test_a_lost_write_is_not_correct(copy):
    last, out = run(copy, "--control", "lost-write")
    assert last["correct"] is False and last["failed"] > 0
    bad = [ln for ln in out.splitlines()
           if ln.startswith("CHECK live_keys_missing ")]
    assert bad and int(bad[0].split()[2]) > 0, out[-3000:]


def test_the_metric_tells_the_parents_rules_apart(copy, tmp_path):
    """Reads that charge for the offline drive and heals that retry
    against it (a fast ladder, so that its rungs fall inside the short
    window) keep the one healer thread busy for the whole window: it makes
    a heal pass for every second or third operation (it cannot make more:
    636 passes beside 1136 operations in 6 s here), where the sound program
    makes almost none."""
    script = tmp_path / "parent_rules.py"
    script.write_text(PARENT_RULES)
    last, out = run(copy, trace="1", seconds="6", script=str(script),
                    env={"MINIO_TPU_MRF_RETRY_BASE_S": "0.02"})
    assert last["metrics"][PASSES]["value"] > 0.25, out[-3000:]
    assert last["metrics"]["pipeline.off_native_block_share"]["value"] == 0
