"""Ids minted in the process (ISSUE 45, ``minio_tpu/utils/ids.py``): span
and trace ids, the names of staging entries and data directories come from
one private generator seeded once from the system's entropy, so an id costs
no system call and no turn at the interpreter lock.

* their forms are what ``uuid.uuid4()`` gave: 16 hex, 32 hex, the 36
  characters of a version-4 UUID;
* they do not repeat: not in a million draws from 16 threads at once, not
  between two fresh interpreters, not between a process and the child it
  forks, and not when somebody seeds the ``random`` module;
* their users read them as before: a staging id keeps its pid prefix for
  ``sweep_tmp``, a served request's ``x-amz-request-id`` is its root span's
  ``trace_id``."""
import os
import subprocess
import sys
import threading
import time
import uuid

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from s3client import S3Client  # noqa: E402

from minio_tpu.objectlayer import ErasureObjects  # noqa: E402
from minio_tpu.obs import spans as sp  # noqa: E402
from minio_tpu.server import S3Server  # noqa: E402
from minio_tpu.storage import XLStorage  # noqa: E402
from minio_tpu.storage.xlstorage import (_minted_by_live_peer,  # noqa: E402
                                         new_tmp_id)
from minio_tpu.utils import ids  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MINTERS = {"span_id": ids.span_id, "trace_id": ids.trace_id,
           "uuid4_str": ids.uuid4_str}


def _hex(s, n):
    return len(s) == n and s == s.lower() and int(s, 16) >= 0


@pytest.mark.parametrize("name", sorted(MINTERS))
def test_forms_are_what_uuid4_gave(name):
    got = [MINTERS[name]() for _ in range(2000)]
    assert len(set(got)) == len(got)
    if name == "span_id":
        assert all(_hex(g, 16) for g in got)
        # the whole width is drawn: some id starts with a zero, some do not
        assert {g[0] == "0" for g in got} == {True, False}
    elif name == "trace_id":
        assert all(_hex(g, 32) for g in got)
    else:
        for g in got:
            u = uuid.UUID(g)
            assert str(u) == g and len(g) == 36
            assert u.version == 4 and u.variant == uuid.RFC_4122
        # 122 bits are random: the fixed ones are the only ones that agree
        assert len({g[14] for g in got}) == 1
        assert {g[19] for g in got} == set("89ab")


def test_span_and_trace_ids_of_the_span_plane_come_from_here():
    assert _hex(sp.new_span_id(), 16) and _hex(sp.new_trace_id(), 32)
    ctx = sp.SpanContext(sp.new_trace_id(), sp.new_span_id(), sampled=True)
    back = sp.parse_traceparent(sp.to_traceparent(ctx))
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)


@pytest.mark.parametrize("name", sorted(MINTERS))
def test_no_repeat_in_a_million_draws_from_16_threads(name):
    mint, each, out = MINTERS[name], 1_000_000 // 16, [None] * 16
    go = threading.Barrier(16)

    def draw(i):
        go.wait()
        out[i] = [mint() for _ in range(each)]

    threads = [threading.Thread(target=draw, args=(i,)) for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)    # the lock changes hands mid-draw
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    seen = set()
    for got in out:
        seen.update(got)
    assert len(seen) == 16 * each


def _fresh(code):
    """Standard output of ``code`` in a fresh interpreter that can import
    the program."""
    env = {**os.environ, "PYTHONPATH": ROOT}
    return subprocess.run([sys.executable, "-c", code], env=env, check=True,
                          capture_output=True, text=True, timeout=60).stdout


FIRST_1000 = ("from minio_tpu.utils import ids\n"
              "for _ in range(1000):\n"
              "    print(ids.span_id(), ids.trace_id(), ids.uuid4_str())\n")


@pytest.mark.parametrize("seeded", [False, True],
                         ids=["fresh", "random_module_seeded"])
def test_two_interpreters_do_not_repeat_each_others_first_1000(seeded):
    """Seeding the ``random`` module's shared generator, before or after
    the import, is what a test or a library may do: the ids' generator is
    its own."""
    code = FIRST_1000
    if seeded:
        code = ("import random\nrandom.seed(45)\n" + code.replace(
            "for _", "random.seed(45)\nfor _", 1))
    a, b = _fresh(code).split(), _fresh(code).split()
    assert len(a) == len(b) == 3000
    assert len(set(a) | set(b)) == 6000


@pytest.mark.skipif(not hasattr(os, "fork"),
                    reason="the platform does not fork")
def test_a_forked_child_does_not_repeat_its_parent():
    """The child's copy of the generator is seeded again
    (``os.register_at_fork``): without that, parent and child mint the
    same ids from the fork on. Forked from a fresh interpreter: this one
    has threads."""
    out = _fresh(
        "import os, sys\n"
        "from minio_tpu.utils import ids\n"
        "ids.span_id()\n"
        "r, w = os.pipe()\n"
        "pid = os.fork()\n"
        "mine = [ids.uuid4_str() for _ in range(1000)]\n"
        "if pid == 0:\n"
        "    os.write(w, ' '.join(mine).encode())\n"
        "    os._exit(0)\n"
        "os.close(w)\n"
        "child = b''\n"
        "while True:\n"
        "    b = os.read(r, 1 << 16)\n"
        "    if not b:\n"
        "        break\n"
        "    child += b\n"
        "os.waitpid(pid, 0)\n"
        "print(' '.join(mine))\n"
        "print(child.decode())\n")
    parent, child = (line.split() for line in out.strip().split("\n"))
    assert len(parent) == len(child) == 1000
    assert len(set(parent) | set(child)) == 2000


def test_a_staging_id_keeps_its_pid_prefix_for_sweep_tmp():
    name = new_tmp_id()
    pid, _, rest = name.partition("-")
    assert int(pid) == os.getpid()
    assert uuid.UUID(rest).version == 4 and str(uuid.UUID(rest)) == rest
    # this process's own entries are swept; a live peer's are not; a dead
    # process's and a legacy, unprefixed name are
    assert _minted_by_live_peer(name) is False
    assert _minted_by_live_peer(f"{os.getppid()}-{rest}") is True
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    assert _minted_by_live_peer(f"{dead.pid}-{rest}") is False
    assert _minted_by_live_peer(ids.uuid4_str()) is False


def test_request_id_of_a_served_request_is_its_root_spans_trace_id(
        tmp_path, monkeypatch):
    # a sub-millisecond budget: every request breaches it and is kept
    monkeypatch.setenv("MINIO_TPU_QOS_INTERACTIVE_BUDGET_MS", "0.0001")
    obj = ErasureObjects([XLStorage(str(tmp_path / f"d{i}"))
                          for i in range(4)], default_parity=2)
    server = S3Server(obj, "127.0.0.1", 0, access_key="idak",
                      secret_key="idsecret123")
    server.start_background()
    try:
        c = S3Client(server.endpoint(), "idak", "idsecret123")
        assert c.put_bucket("idb").status_code == 200
        assert c.put_object("idb", "o", b"x" * 300_000).status_code == 200
        r = c.head_object("idb", "o")
        assert r.status_code == 200
        rid = r.headers["x-amz-request-id"]
        assert _hex(rid, 32)
        kept, deadline = None, time.monotonic() + 10
        while kept is None and time.monotonic() < deadline:
            kept = sp.store().get(rid)     # kept after the reply went out
            time.sleep(0.01)
    finally:
        server.shutdown()
    spans = kept["spans"]
    roots = [s for s in spans if not s["parent_span_id"]]
    assert [s["name"] for s in roots] == ["s3.headobject"]
    assert all(s["trace_id"] == rid for s in spans)
    assert sum(s["name"] == "storage.read_version" for s in spans) == 4
    span_ids = [s["span_id"] for s in spans]
    assert len(set(span_ids)) == len(span_ids)
    assert all(_hex(i, 16) for i in span_ids)
