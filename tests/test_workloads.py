"""Device data-plane workloads (ISSUE 8, docs/select.md + docs/sse.md):
the Select scan lane's semantic equivalence with the classic
interpreter, dispatch routing (device/CPU/chaos salvage), the SSE
ChaCha package lane through the dispatch plane, and the workloads
metric/config surface."""
import io
import os

import numpy as np
import pytest

from minio_tpu import fault
from minio_tpu.crypto import chacha20poly1305 as ccp
from minio_tpu.ops import scan_pallas as sp
from minio_tpu.s3select import S3SelectRequest, run_select
from minio_tpu.s3select import device as sdev
from minio_tpu.s3select.message import decode_messages
from minio_tpu.s3select.sql import parse_select

RNG = np.random.default_rng(31)

CSV = (b"name,age,city,score\n"
       b"alice,34,paris,10\n"
       b"bob,28,london,-3\n"
       b"carol,41,paris,7\n"
       b"dave,19,tokyo,2.5\n"
       b"erin,x,oslo,9\n")


def _run(sql: str, data: bytes, header="USE", mode="auto",
         progress=False, compression="NONE"):
    prev = os.environ.get("MINIO_TPU_SCAN")
    os.environ["MINIO_TPU_SCAN"] = mode
    try:
        req = S3SelectRequest()
        req.expression = sql
        req.csv_header = header
        req.compression = compression
        req.progress_enabled = progress
        out = io.BytesIO()
        st = run_select(req, data, out)
        msgs = decode_messages(out.getvalue())
        recs = b"".join(p for h, p in msgs
                        if h.get(":event-type") == "Records")
        return recs.decode(), st, msgs
    finally:
        if prev is None:
            os.environ.pop("MINIO_TPU_SCAN", None)
        else:
            os.environ["MINIO_TPU_SCAN"] = prev


# --------------------------------------------------------------------------
# predicate compiler


def test_compile_where_coverage():
    names = {"age": 1, "score": 3}
    sel = parse_select("SELECT name FROM S3Object "
                       "WHERE age > 30 AND score BETWEEN 0 AND 9")
    prog, cols = sdev.compile_where(sel.where, sel.alias, names)
    assert cols == (1, 3)
    assert prog == (("num", 0, "gt", 30), ("between", 1, 0, 9), ("and",))
    # fractional literals canonicalize into the exact int domain
    sel = parse_select("SELECT * FROM S3Object WHERE age > 25.5")
    prog, cols = sdev.compile_where(sel.where, sel.alias, names)
    assert prog == (("num", 0, "ge", 26),)
    sel = parse_select("SELECT * FROM S3Object WHERE age = 25.5")
    prog, _ = sdev.compile_where(sel.where, sel.alias, names)
    assert prog == (("const", False),)
    # numeric-string literal coerces; non-numeric folds for eq/ne
    sel = parse_select("SELECT * FROM S3Object WHERE age = '30'")
    prog, _ = sdev.compile_where(sel.where, sel.alias, names)
    assert prog == (("num", 0, "eq", 30),)
    sel = parse_select("SELECT * FROM S3Object WHERE age != 'zzz'")
    prog, _ = sdev.compile_where(sel.where, sel.alias, names)
    assert prog == (("const", True),)


def test_compile_where_rejections():
    names = {"age": 1, "city": 2}
    for sql in [
        "SELECT * FROM S3Object WHERE city LIKE 'p%'",
        "SELECT * FROM S3Object WHERE age + 1 > 30",
        "SELECT * FROM S3Object WHERE LOWER(city) = 'paris'",
        "SELECT * FROM S3Object WHERE age < 'abc'",   # lexicographic
        "SELECT * FROM S3Object WHERE nosuch > 3",
        "SELECT * FROM S3Object WHERE age > 9999999999",  # > int32
    ]:
        sel = parse_select(sql)
        assert sdev.compile_where(sel.where, sel.alias, names) is None, sql


# --------------------------------------------------------------------------
# semantic equivalence: device lane == classic interpreter


QUERY_MATRIX = [
    ("SELECT name FROM S3Object WHERE age > 30", "USE"),
    ("SELECT name, age FROM S3Object WHERE age BETWEEN 25 AND 40", "USE"),
    ("SELECT * FROM S3Object WHERE age IN (19, 41, 99)", "USE"),
    ("SELECT name FROM S3Object WHERE NOT (age = 34 OR age < 20)", "USE"),
    ("SELECT UPPER(name) FROM S3Object WHERE score >= 7 LIMIT 1", "USE"),
    ("SELECT name FROM S3Object WHERE age > 25.5", "USE"),
    ("SELECT s._1 FROM S3Object s WHERE s._2 >= 28", "NONE"),
    ("SELECT name FROM S3Object WHERE age IS NOT NULL", "USE"),
    # residual-heavy: score has a float and age a string in the data
    ("SELECT name FROM S3Object WHERE score < 8 AND age > 0", "USE"),
]


@pytest.mark.parametrize("sql,header", QUERY_MATRIX)
def test_device_equals_classic(sql, header):
    """cpu mode runs the full lane (compiler, structural split,
    residual handling, materialization) over the bit-identical pure
    reference — kernel-vs-reference is pinned in test_scan_pallas, and
    two representative queries run the auto (dispatch) mode below."""
    off, st_off, _ = _run(sql, CSV, header, mode="off")
    cpu, st_cpu, _ = _run(sql, CSV, header, mode="cpu")
    assert off == cpu, sql
    assert st_off == st_cpu


@pytest.mark.parametrize("sql,header", [QUERY_MATRIX[0], QUERY_MATRIX[8]])
def test_device_equals_classic_dispatch_mode(sql, header):
    off, st_off, _ = _run(sql, CSV, header, mode="off")
    disp, st_disp, _ = _run(sql, CSV, header, mode="dispatch")
    assert off == disp, sql
    assert st_off == st_disp


def test_scan_auto_resolves_by_backend():
    """auto = dispatch on a TPU backend, off elsewhere (interpret-mode
    Pallas is not an execution engine); explicit modes always win."""
    from minio_tpu.ops.scan_pallas import on_tpu
    prev = os.environ.get("MINIO_TPU_SCAN")
    try:
        os.environ["MINIO_TPU_SCAN"] = "auto"
        want = "dispatch" if on_tpu() else "off"
        assert sdev.scan_config()[0] == want
        os.environ["MINIO_TPU_SCAN"] = "dispatch"
        assert sdev.scan_config()[0] == "dispatch"
    finally:
        if prev is None:
            os.environ.pop("MINIO_TPU_SCAN", None)
        else:
            os.environ["MINIO_TPU_SCAN"] = prev


@pytest.mark.parametrize("mode", ["cpu", "dispatch"])
def test_unterminated_trailing_row(mode):
    """Review regression: a final CSV row WITHOUT a trailing newline
    whose row count hits a power of two used to overrun the codes
    array (max_rows was sized from newline counts only)."""
    for data in (b"1,1\n2,2", b"1,1", b"id,v\n1,5\n2,995",
                 b"1,1\n2,2\n3,3\n4,4\n5,5"):
        sql = "SELECT _1 FROM S3Object WHERE _2 > 0"
        off, st1, _ = _run(sql, data, header="NONE", mode="off")
        lane, st2, _ = _run(sql, data, header="NONE", mode=mode)
        assert off == lane, (mode, data)
        assert st1 == st2


def test_device_equals_classic_quoted_and_crlf_blocks():
    """Quote/CR/NUL anywhere in the data bails the WHOLE query to the
    classic path (review finding: byte-level row splitting cannot
    reproduce csv's quoted-embedded-newline record merging; bare CR
    and NUL make csv.reader error whole-stream). Every mode must
    behave IDENTICALLY — same output or same error."""
    import csv as _csv
    cases = [
        b"name,age\n\"quoted, name\",34\nplain,28\r\nlast,41\n",  # CRLF
        # the reviewer's repro: a quoted field with an EMBEDDED newline
        b"name,age\n\"multi\nline\",34\nplain,41\n",
        b"name,age\na,34\rb,41\n",       # bare CR: classic ERRORS
        b"name,age\n123\x00,5\n42,7\n",  # NUL: classic ERRORS
    ]
    for data in cases:
        for sql in ("SELECT name FROM S3Object WHERE age > 30",
                    "SELECT * FROM S3Object WHERE age > 0"):
            results = []
            for mode in ("off", "cpu", "dispatch"):
                try:
                    recs, st, _ = _run(sql, data, mode=mode)
                    results.append(("ok", recs, st["returned"]))
                except _csv.Error as e:
                    results.append(("err", type(e).__name__, str(e)))
            assert results[0] == results[1] == results[2], (sql, data,
                                                            results)


def test_device_equals_classic_property():
    rows = [b"id,v,w,s"]
    for i in range(4000):
        v = str(RNG.integers(-1000, 1000)).encode() \
            if RNG.random() < 0.9 else b"x%.2f" % RNG.random()
        rows.append(b"%d,%s,%d,str%d" % (i, v, RNG.integers(0, 50),
                                         RNG.integers(0, 3)))
    data = b"\n".join(rows) + b"\n"
    for sql in [
        "SELECT id FROM S3Object WHERE v > 500 OR w < 5",
        "SELECT id, v FROM S3Object WHERE v BETWEEN -100 AND 100 "
        "LIMIT 37",
        "SELECT COUNT(*) FROM S3Object WHERE w IN (1,2,3)",
        "SELECT id FROM S3Object WHERE NOT v <= 0 AND w != 7",
    ]:
        off, st1, _ = _run(sql, data, mode="off")
        disp, st2, _ = _run(sql, data, mode="dispatch")
        assert off == disp, sql
        assert st1 == st2


# --------------------------------------------------------------------------
# stats & progress events (s3select/message.py satellite)


def test_distinct_scanned_processed_returned_and_progress():
    import gzip
    gz = gzip.compress(CSV)
    sql = "SELECT name FROM S3Object WHERE age > 30"
    recs, st, msgs = _run(sql, gz, mode="cpu", compression="GZIP",
                          progress=True)
    assert st["scanned"] == len(gz)
    assert st["processed"] == len(CSV)
    assert st["returned"] == len(recs)
    assert len({st["scanned"], st["processed"], st["returned"]}) == 3
    kinds = [h.get(":event-type") for h, _ in msgs]
    assert kinds[-2:] == ["Stats", "End"] and "Progress" in kinds
    # frame bodies locked against the reference XML shape
    prog = [p for h, p in msgs
            if h.get(":event-type") == "Progress"][0].decode()
    stats = [p for h, p in msgs
             if h.get(":event-type") == "Stats"][0].decode()
    for body in (prog, stats):
        assert f"<BytesScanned>{len(gz)}</BytesScanned>" in body
        assert f"<BytesProcessed>{len(CSV)}</BytesProcessed>" in body
        assert f"<BytesReturned>{len(recs)}</BytesReturned>" in body
    hdrs = [h for h, _ in msgs if h.get(":event-type") == "Progress"][0]
    assert hdrs[":message-type"] == "event"
    assert hdrs[":content-type"] == "text/xml"


def test_request_progress_xml_parse():
    xml = (b"<SelectObjectContentRequest>"
           b"<Expression>SELECT * FROM S3Object</Expression>"
           b"<ExpressionType>SQL</ExpressionType>"
           b"<RequestProgress><Enabled>true</Enabled></RequestProgress>"
           b"<InputSerialization><CSV/></InputSerialization>"
           b"</SelectObjectContentRequest>")
    req = S3SelectRequest.parse(xml)
    assert req.progress_enabled


# --------------------------------------------------------------------------
# dispatch routing + chaos


def test_scan_chaos_kernel_fault_cpu_salvage():
    """A kernel-layer fault on a select_scan flush CPU-salvages with
    identical results (acceptance criterion)."""
    sql = "SELECT name FROM S3Object WHERE age >= 28"
    clean, st1, _ = _run(sql, CSV, mode="dispatch")
    fault.arm("kernel:device:select_scan:error(FaultyDisk)@count=8")
    try:
        chaos, st2, _ = _run(sql, CSV, mode="dispatch")
    finally:
        fault.clear()
    assert clean == chaos
    assert st1 == st2


def test_sse_chaos_kernel_fault_cpu_salvage(monkeypatch):
    """A kernel-layer fault on an sse_xor flush CPU-salvages; the
    sealed bytes are bit-identical (numpy lane pinned to the kernel).
    The clean pass uses the numpy lane directly (the 1025-lane
    interpret kernel would cost a ~60 s compile on CPU hosts); the
    chaos pass goes through dispatch, where the armed rule reroutes
    every flush to the same numpy reference."""
    from minio_tpu.crypto.sse import CIPHER_CHACHA20, EncryptReader
    body = RNG.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
    oek, iv = b"\x11" * 32, b"\x07" * 12
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "off")
    clean = EncryptReader(io.BytesIO(body), oek, iv,
                          cipher=CIPHER_CHACHA20).read()
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "1")  # force the lane
    fault.arm("kernel:device:sse_xor:error(FaultyDisk)@count=8")
    try:
        chaos = EncryptReader(io.BytesIO(body), oek, iv,
                              cipher=CIPHER_CHACHA20).read()
    finally:
        fault.clear()
    assert clean == chaos
    # the seal sites fed the workloads counter families
    from minio_tpu.obs import metrics as mx
    counters = mx.counters_snapshot()
    assert any(k.startswith("minio_tpu_workloads_sse_packages_total")
               for k in counters)
    assert any(k.startswith("minio_tpu_workloads_sse_bytes_total")
               for k in counters)


def test_dispatch_routes_and_metrics():
    from minio_tpu.runtime.dispatch import DispatchQueue
    rows = b"1,5\n2,15\n3,x\n4,25\n"
    block = rows + b"\n" * (64 - len(rows))
    program = (("num", 0, "gt", 10),)
    w = np.frombuffer(block, np.uint8).view("<u4").reshape(1, -1)
    ref = sp.scan_block_reference(block, program, (1,), 44, 8)
    prev = os.environ.get("MINIO_TPU_DISPATCH_MODE")
    q = DispatchQueue()
    try:
        for mode in ("device", "cpu"):
            os.environ["MINIO_TPU_DISPATCH_MODE"] = mode
            codes = q.select_scan(w, program, (1,), 44, 8).result(300)
            assert np.array_equal(codes, ref), mode
        key = RNG.integers(0, 256, 32, dtype=np.uint8).tobytes()
        nonces = np.stack([ccp.nonce_words(b"\x01" * 8 + b"\0\0\0\x05")])
        # 64 B packages: the same kernel shape test_chacha pins, so the
        # two share one (slow) interpret-mode jit compile per process
        data = RNG.integers(0, 256, (1, 64), dtype=np.uint8)
        ref_ct, ref_pk = ccp.keystream_xor(key, nonces, data)
        for mode in ("device", "cpu"):
            os.environ["MINIO_TPU_DISPATCH_MODE"] = mode
            ct, pk = q.sse_xor(np.ascontiguousarray(data).view("<u4"),
                               key, nonces).result(300)
            assert np.array_equal(
                np.asarray(ct).view(np.uint8).reshape(1, 64), ref_ct)
        st = q.stats()
        assert st["device_items"] >= 1 and st["cpu_items"] >= 1
    finally:
        if prev is None:
            os.environ.pop("MINIO_TPU_DISPATCH_MODE", None)
        else:
            os.environ["MINIO_TPU_DISPATCH_MODE"] = prev
        q.stop()


def test_workloads_metric_group_renders():
    from minio_tpu.obs.metrics import render_prometheus

    class _Srv:
        obj = None
    text = render_prometheus(_Srv(), scope="").decode()
    assert "minio_tpu_workloads_scan_lane" in text
    assert "minio_tpu_workloads_sse_cipher" in text


def test_scan_lane_config_modes():
    prev = os.environ.get("MINIO_TPU_SCAN")
    try:
        os.environ["MINIO_TPU_SCAN"] = "off"
        assert sdev.scan_config()[0] == "off"
        os.environ["MINIO_TPU_SCAN"] = "cpu"
        mode, blk = sdev.scan_config()
        assert mode == "cpu" and 4096 <= blk <= (8 << 20)
    finally:
        if prev is None:
            os.environ.pop("MINIO_TPU_SCAN", None)
        else:
            os.environ["MINIO_TPU_SCAN"] = prev
