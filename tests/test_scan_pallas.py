"""Batched Select scan kernel (ops/scan_pallas.py): the device path is
pinned bit-identical to the pure-Python reference — parse automaton edge
cases, program ops, structural-index corners — the same contract
mur3/rs_pallas carry (docs/select.md)."""
import numpy as np
import pytest

from minio_tpu.ops import scan_pallas as sp

RNG = np.random.default_rng(21)
DELIM = 44  # ','


def block_of(rows: list[bytes], L: int) -> bytes:
    txt = b"".join(rows)
    assert len(txt) <= L
    return txt + b"\n" * (L - len(txt))


def device_codes(block: bytes, program, cols, max_rows):
    fn = sp.scan_fn_for(program, cols, DELIM, len(block), max_rows)
    w = np.frombuffer(block, np.uint8).view("<u4").reshape(1, -1)
    return np.asarray(fn(w))[0]


def test_parse_edge_cases_pinned():
    rows = [
        b"a,34,x\n",            # plain int -> match depends on program
        b"b, 41 ,x\n",          # stripped spaces parse (int(' 41 '))
        b"c,-7,x\n",            # negative
        b"d,+19,x\n",           # explicit plus
        b"e,2.5,x\n",           # float -> residual
        b"f,,x\n",              # empty -> residual
        b"g,12_000,x\n",        # underscore literal -> residual
        b"h,1234567890,x\n",    # 10 digits -> residual
        b"i,007,x\n",           # leading zeros ok (int('007') == 7)
        b"j,999999999,x\n",     # 9 digits ok
        b"k\n",                 # missing field -> residual
        b"l,1 2,x\n",           # inner space -> residual
        b"m,                9,x\n",   # wider than the 16 B slot
        b"\n",                  # blank row -> residual (missing cell)
        b"n,5-3,x\n",           # trailing sign junk -> residual
        b"o,0,x\n",
        b"p,-0,x\n",            # int('-0') == 0
        b"q,123\x00,x\n",       # genuine NUL != slot padding -> residual
        b"r,\x0045,x\n",        # leading NUL -> residual
    ]
    program = (("num", 0, "ge", 0),)
    block = block_of(rows, 512)
    ref = sp.scan_block_reference(block, program, (1,), DELIM, 32)
    dev = device_codes(block, program, (1,), 32)
    assert np.array_equal(ref, dev)
    want = [1, 1, 0, 1, 2, 2, 2, 2, 1, 1, 2, 2, 2, 2, 2, 1, 1, 2, 2]
    assert ref[:len(rows)].tolist() == want


@pytest.mark.parametrize("program,cols", [
    (((("num", 0, "gt", 10)), ("num", 0, "lt", 40), ("and",)), (1,)),
    ((("between", 0, -5, 25),), (0,)),
    ((("in", 0, (7, 19, 34)),), (1,)),
    ((("num", 0, "eq", 0), ("const", True), ("or",), ("not",)), (2,)),
    ((("num", 0, "ne", 3), ("num", 1, "ge", 1), ("or",)), (0, 2)),
])
def test_program_ops_pinned(program, cols):
    rows = [b"%d,%d,%d\n" % (RNG.integers(-50, 50),
                             RNG.integers(-50, 50),
                             RNG.integers(-3, 3)) for _ in range(40)]
    rows[7] = b"x,y,z\n"  # residual row in the middle
    block = block_of(rows, 1 << 10)
    ref = sp.scan_block_reference(block, program, cols, DELIM, 64)
    dev = device_codes(block, program, cols, 64)
    assert np.array_equal(ref, dev), (program, cols)


def test_batched_blocks_pinned():
    blocks = []
    for _ in range(3):
        rows = [b"%d,%d\n" % (i, RNG.integers(0, 100))
                for i in range(RNG.integers(1, 30))]
        blocks.append(np.frombuffer(block_of(rows, 512), np.uint8))
    arr = np.stack(blocks)
    program = (("num", 0, "lt", 50),)
    ref = sp.scan_blocks_reference(arr, program, (1,), DELIM, 32)
    fn = sp.scan_fn_for(program, (1,), DELIM, 512, 32)
    dev = np.asarray(fn(np.ascontiguousarray(arr).view("<u4")))
    assert np.array_equal(ref, dev)


def test_random_property_pinned():
    """Wider randomized pin: mixed garbage/int cells, several programs."""
    def rand_cell(r):
        k = r.integers(0, 6)
        if k == 0:
            return str(r.integers(-10**9, 10**9)).encode()
        if k == 1:
            return str(r.integers(-50, 50)).encode()
        if k == 2:
            return (b" " * r.integers(0, 3) +
                    str(r.integers(0, 100)).encode() +
                    b" " * r.integers(0, 3))
        if k == 3:
            return str(r.uniform(-10, 10)).encode()[:12]
        if k == 4:
            return b"str%d" % r.integers(0, 5)
        return b""

    progs = [
        ((("num", 0, "ge", 0),), (1,)),
        ((("between", 0, -5, 25),), (2,)),
        ((("num", 0, "lt", 10), ("num", 1, "ne", 7), ("or",),
          ("not",)), (1, 3)),
    ]
    for _ in range(8):
        rows = []
        for _ in range(RNG.integers(1, 60)):
            ncell = RNG.integers(1, 6)
            rows.append(b",".join(rand_cell(RNG)
                                  for _ in range(ncell)) + b"\n")
        block = block_of(rows, 1 << 12)
        for program, cols in progs:
            ref = sp.scan_block_reference(block, program, cols, DELIM, 64)
            dev = device_codes(block, program, cols, 64)
            assert np.array_equal(ref, dev), (program, cols)


def test_reference_program_eval():
    assert sp.eval_program_reference(
        (("num", 0, "gt", 1), ("num", 1, "lt", 5), ("and",)), [3, 2])
    assert not sp.eval_program_reference(
        (("in", 0, (1, 2)), ("not",), ("const", False), ("or",)), [1])
    with pytest.raises(IndexError):   # operand underflow
        sp.eval_program_reference((("num", 0, "gt", 1), ("and",)), [3])
    with pytest.raises(ValueError):   # leftover operands
        sp.eval_program_reference(
            (("num", 0, "gt", 1), ("num", 1, "lt", 5)), [3, 2])
