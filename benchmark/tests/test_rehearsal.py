"""CPU rehearsals of a whole run (everything of run.py but its look for a
chip) at tiny sizes, on a throw-away copy of the benchmark to which the tiny
cells were ADDED as files and entries. Numbers from these runs are the CPU's
and are checked for presence only."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearse  # noqa: E402

MIXED, HEAL = "tiny-mixed.4p2", "tiny-heal.4p2"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    # make_copy itself asserts that no copied file was edited
    return rehearse.make_copy(str(tmp_path_factory.mktemp("bench")))


def run(copy, cell, *extra, seed="3000000007", trace="0"):
    rc, last, out = rehearse.cpu_run(
        copy, "--workload", cell, "--seed", seed, "--seconds", "2",
        "--trace", trace, *extra)
    assert rc == 0 and last is not None, out[-3000:]
    return last, out


@pytest.mark.parametrize("cell,metrics", [
    (MIXED, {"get_p95_ms", "get_mib_s", "put_p95_ms", "setup_s"}),
    (HEAL, {"get_p95_ms", "get_mib_s", "heal_mib_s", "setup_s"})])
def test_kind_end_to_end(copy, cell, metrics):
    last, out = run(copy, cell)
    assert last["correct"] is True and last["failed"] == 0, out[-3000:]
    assert last["attempted"] > 0
    assert set(last["metrics"]) == metrics
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(last["device"])
    # a sound run's line: the contract's keys, then every number compared
    # beside its limit, and no ``failed_checks``
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert all(c == {"value": 0, "limit": 0}
               for c in last["checks"].values())
    checks = [ln for ln in out.splitlines() if ln.startswith("CHECK ")]
    assert len(checks) == 2 * len(last["checks"])   # stdout, then stderr
    if cell == HEAL:
        assert sum(ln.startswith("NOTE heal_items_reported_failed ")
                   for ln in out.splitlines()) == 1


@pytest.mark.parametrize("cell,metrics", [
    (MIXED, {"server.http_503_share", "server.stat_p50_ms",
             "device.compiles_in_window", "device.idle_share", "tiny.gets"}),
    (HEAL, {"server.http_503_share", "dispatch.device_item_share",
            "dispatch.spilled_item_share", "device.compiles_in_window",
            "device.idle_share", "tiny.gets"})])
def test_added_cell_and_metric_need_only_files(copy, cell, metrics):
    """The cells, their configuration and mixes and the metric
    ``tiny.gets`` exist only as files and entries added to the copy. A
    reader with nothing to read (the kernel's, with no trace event) leaves
    its metric out."""
    last, out = run(copy, cell, trace="1")
    assert last["correct"] is True, out[-3000:]
    assert set(last["metrics"]) == metrics
    assert last["metrics"]["tiny.gets"]["value"] > 0
    assert "busy_s" in last["device"] and "breakdown" in last


@pytest.mark.parametrize("cell,extra,counter", [
    (MIXED, ("--break", "get-byte"), "get_bodies_wrong"),
    (HEAL, ("--break", "heal-noop"), "shards_missing_after_heal"),
    (MIXED, ("--control", "lost-write"), "live_keys_missing"),
    (HEAL, ("--control", "lost-write"), "live_keys_missing"),
    (HEAL, ("--control", "unhealed-shard"), "shards_missing_after_heal"),
    (HEAL, ("--control", "unhealed-part"), "shards_missing_after_heal")])
def test_broken_path_or_guarantee_is_not_correct(copy, cell, extra, counter):
    """The timed path broken underneath the harness (a byte altered where
    the body is produced; a heal that returns its state unchanged), or one
    stated guarantee broken (an acknowledged write that is on no drive; a
    healed shard that is not there; a healed shard whose ``xl.meta`` is
    there and whose part file is not): ``correct`` comes out false, and the
    result line says which check fell."""
    last, out = run(copy, cell, *extra)
    assert last["correct"] is False and last["failed"] > 0
    bad = [ln for ln in out.splitlines()
           if ln.startswith(f"CHECK {counter} ")]
    assert bad and int(bad[0].split()[2]) > 0, out[-3000:]
    assert last["failed_checks"][counter] > 0, last
    assert last["failed_checks"] == {
        n: c["value"] for n, c in last["checks"].items() if c["value"]}
    assert list(last)[-2:] == ["failed_checks", "checks"]
    if extra[0] == "--control" and "unhealed" in extra[1]:
        # the control is seen by the check it aims at and, where the
        # read-back sample holds its key, by that GET
        assert set(last["failed_checks"]) <= {counter, "ops_errored"}, last


def test_refuses_to_run_off_a_tpu():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "warp-mixed.4p2",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=rehearse.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2 and "refusing to run" in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_drives_fenced_in_the_window_are_waited_for(copy):
    """A host that stood still: two of the six drives fenced half-way
    through the window, so the window's last PUTs are on four drives. The
    read-back less ``parity`` drives' shards waits until the tracker has the
    drives online again and the program's own heal has made the sample
    whole; without that wait it asks for more than 4+2 promises and reads
    503 SlowDownRead (PERF.md section 6, PR 38)."""
    last, out = run(copy, MIXED, "--break", "fence")
    assert last["correct"] is True and last["failed"] == 0, out[-3000:]
    assert "NOTE whole: waited" in out
