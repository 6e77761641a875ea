"""BENCHMARK.json against the files it names: every configuration, mix,
kind and metric is a file found by its name; names and units use the
permitted characters; the peaks table refuses a device it does not list."""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(BENCH, "lib"))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved), m["name"]
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_everything_named_is_a_file(bench):
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert cfg["chips"] == 1 and cfg["drives"] > cfg["parity"] > 0
        assert cfg["geometry"]["data"] == cfg["drives"] - cfg["parity"]
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "traffic_kinds",
                                           mix["kind"] + ".py"))
    for group, directory in (("end_to_end", "e2e_metrics"),
                             ("per_layer", "layer_metrics")):
        for m in bench[group]:
            path = os.path.join(BENCH, directory, m["name"] + ".py")
            assert os.path.exists(path), path
            with open(path) as f:
                assert "def read(run)" in f.read()


def test_unknown_device_has_no_peak():
    import window
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    run = {"peaks": peaks, "device": {"kind": "TPU v5 lite"}}
    assert window.peak(run, "hbm_bytes_per_s") == 819e9
    run["device"]["kind"] = "TPU v9 imaginary"
    with pytest.raises(RuntimeError):
        window.peak(run, "hbm_bytes_per_s")


def test_kernel_bytes_are_the_least():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "k", os.path.join(BENCH, "kernels", "rebuild_verify.py"))
    k = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(k)
    # 8 survivors of 512 KiB in, their 32 digests of 32 B each, 1 shard
    # out, 8 flags
    assert k.item_bytes(8, 524288, 16384, 1) == \
        8 * 524288 + 8 * 32 * 32 + 524288 + 8
    geom = {"data": 8, "block_bytes": 4 << 20, "bitrot_chunk_bytes": 16384}
    full, tail = k.item_bytes(8, 524288, 16384, 1), \
        k.item_bytes(8, 262144, 16384, 1)
    assert k.mean_item_bytes(geom, 10 << 20, 1) == (2 * full + tail) / 3
