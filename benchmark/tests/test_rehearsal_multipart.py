"""CPU rehearsal of the kind ``multipart_sse`` (through ``cpu_run_mp.py``)
at a tiny size, on a throw-away copy of the benchmark to which a tiny
configuration with a KMS master key and a tiny mix were ADDED. Numbers from
these runs are the CPU's and are checked for presence only."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearse  # noqa: E402

CELL = "tiny-mp.4p2"
TINY_CFG = dict(rehearse.TINY_CFG, name="tiny-4p2-sse",
                env={"MINIO_TPU_KMS_MASTER_KEY": "5a" * 32})
TINY_MP = {
    "kind": "multipart_sse", "client_processes": 2, "upload_threads": 1,
    "get_threads": 1, "parts": 2, "part_bytes": 5 * (1 << 20) + 4096,
    "pool_objects": 3, "ring": 2, "sse": "AES256", "readback_sample": 2,
    "range_gets": 8, "degraded_sample": 2, "at_rest_sample": 2,
    "big_parts": 16, "big_put_live_bound_bytes": 8 << 20,
    "big_get_live_bound_bytes": 48 << 20, "warm_repeats": 1,
    "verify_env": {"MINIO_TPU_DISPATCH_MODE": "cpu"}}
SSE_METRICS = {"sse.seal_ms_per_mib", "sse.open_ms_per_mib",
               "sse.device_package_share"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = rehearse.make_copy(str(tmp_path_factory.mktemp("benchmp")))
    b = os.path.join(dst, "benchmark")
    for path, obj in (("configs/tiny-4p2-sse.json", TINY_CFG),
                      ("traffic/tiny-mp.json", TINY_MP)):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-4p2-sse", "source": "rehearsal only",
        "file": "benchmark/configs/tiny-4p2-sse.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-4p2-sse",
                               "traffic": "tiny-mp", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "multipart-sse.8p4" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def run(copy, *extra, seed="3000000019", trace="0"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=rehearse.REPO,
               PYTHONDONTWRITEBYTECODE="1")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "tests",
                                      "cpu_run_mp.py"), copy,
         "--workload", CELL, "--seed", seed, "--seconds", "3", "--trace",
         trace, *extra],
        env=env, capture_output=True, text=True, timeout=600)
    out = p.stdout + p.stderr
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 0 and lines, out[-3000:]
    return json.loads(lines[-1]), out


def test_kind_end_to_end(copy):
    last, out = run(copy)
    assert last["correct"] is True and last["failed"] == 0, out[-4000:]
    assert set(last["metrics"]) == {"get_p95_ms", "get_mib_s", "put_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert "AT-REST 2 objects, 8 packages opened" in out
    assert "BIG 16 parts" in out


def test_per_layer_readers_report_from_the_counters(copy):
    last, out = run(copy, trace="1")
    assert last["correct"] is True, out[-4000:]
    assert set(last["metrics"]) == SSE_METRICS | {
        "server.http_503_share", "device.compiles_in_window",
        "device.idle_share"}
    assert last["metrics"]["sse.seal_ms_per_mib"]["value"] > 0
    assert last["metrics"]["sse.open_ms_per_mib"]["value"] > 0
    assert last["metrics"]["sse.device_package_share"]["value"] == 0


@pytest.mark.parametrize("extra,counter", [
    (("--break", "plain-part"), "plaintext_runs_at_rest"),
    (("--break", "plain-part"), "packages_unopened_at_rest"),
    (("--break", "wrong-part-range"), "range_bodies_wrong"),
    (("--break", "resident"), "memory_bound_exceeded"),
    (("--control", "lost-write"), "live_keys_missing"),
    (("--control", "wrong-master-key"), "packages_unopened_at_rest")])
def test_broken_path_or_guarantee_is_not_correct(copy, extra, counter):
    """A part stored in plaintext (every round trip still fits), a range
    answered from the wrong part, a server that keeps a part (and, on a
    GET, the object) in memory, a completed upload that is on no drive, an
    at-rest check under another master key: ``correct`` is false."""
    last, out = run(copy, *extra)
    assert last["correct"] is False and last["failed"] > 0
    bad = [ln for ln in out.splitlines()
           if ln.startswith(f"CHECK {counter} ")]
    assert bad and int(bad[0].split()[2]) > 0, out[-4000:]
    if counter == "memory_bound_exceeded":    # in the upload and in the read
        assert int(bad[0].split()[2]) == 2, out[-4000:]


def test_readers_find_nothing_without_the_counters():
    """On a program (or a kind) that leaves no SSE counters the readers
    return None and raise nothing."""
    lib = os.path.join(rehearse.REPO, "benchmark", "lib")
    code = (
        "import sys, importlib.util\n"
        f"sys.path.insert(0, {lib!r})\n"
        "run = {'window': {'threads': []}}\n"
        "for name in %r:\n"
        "    spec = importlib.util.spec_from_file_location('m', "
        f"{os.path.join(rehearse.REPO, 'benchmark', 'layer_metrics')!r}"
        " + '/' + name + '.py')\n"
        "    m = importlib.util.module_from_spec(spec)\n"
        "    spec.loader.exec_module(m)\n"
        "    assert m.read(run) is None, name\n"
        "    run2 = {'window': {'threads': [], 'sse_counters': ({}, {})}}\n"
        "    assert m.read(run2) is None, name\n") % (sorted(SSE_METRICS),)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert p.returncode == 0, p.stderr


def test_benchmark_reference_is_the_programs_copy():
    with open(os.path.join(rehearse.REPO, "benchmark", "lib",
                           "sse_ref.py")) as a, \
            open(os.path.join(rehearse.REPO, "minio_tpu", "crypto",
                              "sse_ref.py")) as b:
        assert a.read() == b.read()


def test_mp_client_imports_neither_jax_nor_the_program():
    lib = os.path.join(rehearse.REPO, "benchmark", "lib")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import mp_client, mp_model, sse_ref, sse_counters\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')"
        " or m.startswith('jaxlib') or m.startswith('minio_tpu')]\n"
        "assert not bad, bad\n") % lib
    p = subprocess.run([sys.executable, "-c", code], cwd="/",
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
