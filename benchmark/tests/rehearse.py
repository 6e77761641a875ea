"""Helpers of the rehearsal tests: a throw-away copy of the benchmark with
tiny cells ADDED to it as files and entries (no copied file is edited), and
one CPU run of a cell there."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TINY_CFG = {
    "name": "tiny-4p2", "source": "rehearsal only", "chips": 1,
    "drives": 6, "parity": 2, "env": {},
    "geometry": {"data": 4, "parity": 2, "block_bytes": 4194304,
                 "bitrot_algo": "highwayhash256S",
                 "bitrot_chunk_bytes": 16384,
                 "bitrot_key_hex": "4be734fa8e238acd263e83e6bb968552040f935d"
                                   "a39f441497e09d1322de36a0",
                 "etag_min_bytes": 1048576, "inline_max_bytes": 131072}}
TINY_MIXED = {
    "kind": "mixed", "client_processes": 2, "threads_per_process": 2,
    "pool_objects": 16, "object_bytes": 1310720,
    "deck": {"GET": 9, "STAT": 6, "PUT": 3, "DELETE": 2}, "put_bodies": 2,
    "readback_sample": 4,
    "verify_env": {"MINIO_TPU_DISPATCH_MODE": "cpu"}}
TINY_HEAL = {
    "kind": "heal_drive", "client_processes": 3,
    "get_threads_per_process": 2, "objects": 12, "object_bytes": 1310720,
    "warm_objects": 2, "warm_s": 0.5, "warm_repeats": 1,
    "warm_steps": [{"clients": 3, "env": {}},
                   {"clients": 2, "heal": True, "env": {}}], "readback_sample": 4,
    "heal_wait_s": 120.0}
# a per-layer metric a later PR might add: a file of its own
TINY_METRIC = '''"""GETs the window's clients finished (a count)."""


def read(run):
    return sum(r["op"] == "GET" for recs in run["window"]["threads"]
               for r in recs)
'''


def make_copy(dst: str) -> str:
    """Copy benchmark/ and BENCHMARK.json to ``dst`` and add the tiny
    configuration, two mixes, one per-layer metric and two cells: new files
    and new entries only."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(dst, "benchmark")
    before = _digest(b)
    for path, obj in (("configs/tiny-4p2.json", TINY_CFG),
                      ("traffic/tiny-mixed.json", TINY_MIXED),
                      ("traffic/tiny-heal.json", TINY_HEAL)):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(b, "layer_metrics", "tiny.gets.py"), "w") as f:
        f.write(TINY_METRIC)
    assert {k: v for k, v in _digest(b).items() if k in before} == before
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = ["tiny-mixed.4p2", "tiny-heal.4p2"]
    bench["configs"].append({
        "name": "tiny-4p2", "source": "rehearsal only",
        "file": "benchmark/configs/tiny-4p2.json", "reduced": [],
        "why": "rehearsal"})
    for name, mix in zip(cells, ("tiny-mixed", "tiny-heal")):
        bench["workloads"].append({"name": name, "config": "tiny-4p2",
                                   "traffic": mix, "chips": 1,
                                   "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for c, like in zip(
                cells, ("warp-mixed.4p2", "heal-drive.8p4"))
                if like in m["workloads"]]
    bench["per_layer"].append({
        "name": "tiny.gets", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "clients", "moves": "get_mib_s",
        "workloads": cells})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def _digest(root: str) -> dict:
    import hashlib
    out = {}
    for d, _, files in os.walk(root):
        for name in files:
            p = os.path.join(d, name)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def cpu_run(copy: str, *argv: str, timeout: int = 600):
    """(exit code, last stdout line as JSON or None, whole output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               PYTHONDONTWRITEBYTECODE="1")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "tests",
                                      "cpu_run.py"), copy, *argv],
        env=env, capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (IndexError, ValueError):
        last = None
    return p.returncode, last, p.stdout + p.stderr
