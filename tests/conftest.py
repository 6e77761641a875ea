"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference strategy of simulating a multi-disk/multi-node cluster
with local resources (SURVEY.md §4: temp-dir disks, in-process multi-set
layouts) — here, multi-chip shardings run on virtual CPU devices.
"""
import os

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


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: heavyweight property/pin sweeps ride
    # this marker so they run in full passes without taxing the gate
    config.addinivalue_line(
        "markers", "slow: heavyweight sweep excluded from tier-1")


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
