"""The reduction from the profiler's trace to numbers, on a small trace
recorded on the v5e (PR 23): two markers 0.3 s apart around five launches of
a jitted function named ``fused`` (module ``jit_fused``)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "lib"))
import xplane  # noqa: E402

TRACE = os.path.join(HERE, "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    return xplane.reduce_file(TRACE)


def test_window_is_what_lies_between_the_markers(reduced):
    assert 0.30 < reduced["window_s"] < 0.31
    assert [p["name"] for p in reduced["planes"]] == ["/device:TPU:0"]


def test_busy_is_the_union_of_the_device_operations(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    assert set(ops) == {"%add_reduce_fusion fusion",
                        "%broadcast_add_fusion fusion"}
    # operations did not overlap here, so the union is their sum
    assert reduced["busy_s"] == pytest.approx(sum(ops.values()), rel=1e-9)
    assert 0 < reduced["busy_s"] < 1e-4


def test_seconds_per_program(reduced):
    assert set(reduced["program_s"]) == {"jit_bench_trace_marker",
                                         "jit_fused"}
    planes = xplane.device_planes(TRACE)
    fused = [e for e in planes[0]["modules"] if e[0].startswith("jit_fused")]
    assert len(fused) == 5
    assert reduced["program_s"]["jit_fused"] == pytest.approx(
        sum(b - a for _, a, b in fused), rel=1e-9)


def test_gaps_are_unattributed_and_add_up(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert all(name == "unattributed" for name, _ in gaps) and len(gaps) <= 10
    assert sum(g for _, g in gaps) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_union_and_short_names():
    assert xplane._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.short_op(
        "%while.7 = (s32[]{:T(128)}, u32[256]{0:T(256)S(1)}) while((s32[]) "
        "%tuple.438), condition=%c, body=%b") == "%while.7 while"


def test_a_trace_without_its_markers_is_an_error(tmp_path, monkeypatch):
    monkeypatch.setattr(xplane, "MARKER", "no_such_marker")
    with pytest.raises(RuntimeError, match="trace markers"):
        xplane.reduce_file(TRACE)
