"""The suite's own per-test limit (tests/conftest.py): a test that hangs
costs one failure and the limit, never the run. Driven as the driver
drives tier-1 (`-p xdist -n 2 --dist loadfile`) and plainly, in a pytest
of its own over a scratch directory whose conftest takes the hooks from
tests/conftest.py and shortens the one constant."""
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

CONFTEST = f"""
import importlib.util
spec = importlib.util.spec_from_file_location(
    "tier1_conftest", {os.path.join(HERE, "conftest.py")!r})
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
m.TEST_LIMIT_S = 2
globals().update((k, v) for k, v in vars(m).items() if k.startswith("pytest_"))
"""

# the hang is a sleep in C with the interpreter lock released, as a call
# into XLA is; SIGALRM is blocked to show that no signal is relied on
TEST_A = """
import signal, time
def test_before(): pass
def test_hang():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(120)
def test_after(): pass
"""


@pytest.mark.parametrize("xdist", [True, False], ids=["loadfile", "plain"])
def test_hung_test_costs_one_failure_and_the_limit(tmp_path, xdist):
    (tmp_path / "conftest.py").write_text(CONFTEST)
    (tmp_path / "test_a.py").write_text(TEST_A)
    (tmp_path / "test_b.py").write_text("def test_other(): pass\n")
    cmd = [sys.executable, "-m", "pytest", str(tmp_path), "-q",
           "--rootdir", str(tmp_path), "-p", "no:cacheprovider",
           "--basetemp", str(tmp_path / "bt")]
    if xdist:
        cmd += ["-p", "xdist", "-n", "2", "--dist", "loadfile"]
    t0 = time.monotonic()
    r = subprocess.run(cmd, cwd=os.path.dirname(HERE), capture_output=True,
                       text=True, timeout=110)
    took = time.monotonic() - t0
    assert r.returncode == 1, r.stdout + r.stderr
    assert took < 60, took
    # every thread's stack, the hung one's with the line it sat on
    assert "Timeout (0:00:02)!" in r.stderr
    assert "test_a.py\", line 6 in test_hang" in r.stderr
    if not xdist:
        return  # the one process was ended: no summary to read
    # one failure, named; the rest of its file and the other file ran;
    # the file's re-run on the new worker skipped the test, not hung again
    assert "crashed while running 'test_a.py::test_hang'" in r.stdout
    assert "1 failed, 3 passed, 1 skipped" in r.stdout, r.stdout
    assert r.stderr.count("Timeout (0:00:02)!") == 1
