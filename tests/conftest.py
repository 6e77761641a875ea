"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference strategy of simulating a multi-disk/multi-node cluster
with local resources (SURVEY.md §4: temp-dir disks, in-process multi-set
layouts) — here, multi-chip shardings run on virtual CPU devices.
"""
import faulthandler
import hashlib
import os
import sys

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the CPU backend: the env var is read when JAX first
# initializes a backend, which no import above has done. Set
# MINIO_TPU_TEST_ON_DEVICE=1 to run the suite against a real chip instead.
if os.environ.get("MINIO_TPU_TEST_ON_DEVICE") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

# No persistent compile cache under the tests: on the CPU backend it only
# costs (a fresh checkout has nothing to hit, every hit logs an XLA:CPU
# "machine features" error line, and six workers would write the
# checkout's .jax_cache). The program's own placement of the cache
# (minio_tpu/ops/__init__.py) still runs and is tested; this only keeps
# JAX from using it here.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

# Runtime lock-order race detection (obs/lockrank.py) is ON by default
# for the whole suite: every threading.Lock/RLock created by minio_tpu
# code after this point is tracked, building the global lock-order graph
# and reporting ABBA cycles / locks held across device flushes. Opt out
# with MINIO_TPU_LOCKRANK=0. Installing here — before minio_tpu modules
# import — is what lets module-level locks get wrapped too.
if os.environ.get("MINIO_TPU_LOCKRANK", "") == "":
    os.environ["MINIO_TPU_LOCKRANK"] = "1"
if os.environ["MINIO_TPU_LOCKRANK"] == "1":
    from minio_tpu.obs import lockrank

    lockrank.install()


#: Seconds one test may take, set-up and tear-down included, before every
#: thread's stack is written to stderr and the process running it is ended.
#: Five times the slowest single test of a whole `-n 6 --dist loadfile`
#: run on 8 cores and over (CHANGES.md, PR 24).
TEST_LIMIT_S = 300

_stderr_fd = pytest.StashKey[int]()


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`. Nothing carries the mark today (the four
    # that did end in under 10 s each alone and came back in PR 24); it is
    # for a test that takes over a minute alone and keeps a faster case of
    # itself in tier-1
    config.addinivalue_line(
        "markers", "slow: heavyweight sweep excluded from tier-1")
    # the real stderr, taken before a test's capture stands in front of it
    config.stash[_stderr_fd] = os.dup(sys.__stderr__.fileno())


def pytest_unconfigure(config):
    os.close(config.stash[_stderr_fd])


def _running_mark(item):
    """Under xdist, the path of a file that stands while ``item`` runs
    in one of this run's workers; None in a plain run. It lives in the
    run's own temporary directory (a worker's is `<run's>/popen-gwN`),
    which pytest clears away with it."""
    base = item.config.option.basetemp
    if not hasattr(item.config, "workerinput") or not base:
        return None
    d = os.path.join(os.path.dirname(base), "running")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, hashlib.sha1(item.nodeid.encode()).hexdigest())


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    """The per-test limit. faulthandler's watchdog is a thread of the C
    runtime, not a signal: it fires while the main thread sits inside XLA
    or waits for a lock, where Python never gets to run a SIGALRM handler.
    It ends the process, because nothing else gets that thread back.
    xdist reports the test as failed ("worker crashed while running") and
    replaces the worker, but `--dist loadfile` hands the new worker the
    rest of the file WITH the test that was running, again and again
    until `--max-worker-restart` ends the run. So a test that meets its
    own mark from a dead worker is skipped: its failure stands already."""
    mark = _running_mark(item)
    if mark is not None:
        if os.path.exists(mark):
            item.add_marker(pytest.mark.skip(
                reason="ended its worker earlier in this run and was "
                       "reported failed there; not run again"))
            return (yield)
        open(mark, "w").close()
    faulthandler.dump_traceback_later(TEST_LIMIT_S, exit=True,
                                      file=item.config.stash[_stderr_fd])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        if mark is not None:
            os.unlink(mark)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Surface accumulated lockrank reports at the end of the run so a
    newly-introduced lock-order inversion is visible even when no test
    asserted on it (tests/test_lockrank.py asserts the machinery)."""
    try:
        from minio_tpu.obs import lockrank
    except Exception:  # pragma: no cover — lockrank absent
        return
    reps = lockrank.reports()  # test_lockrank clears its seeded ones
    if not reps:
        return
    tw = terminalreporter
    tw.section("lockrank reports")
    for r in reps[:10]:
        locks = ", ".join(r.get("locks", []))
        tw.write_line(f"{r['kind']}: {locks} (thread {r['thread']})")
    if len(reps) > 10:
        tw.write_line(f"... {len(reps) - 10} more")
