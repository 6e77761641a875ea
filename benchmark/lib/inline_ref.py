"""Plain reference for what an erasure set holds AT REST of one object, read
from the drives as they lie: each drive's ``xl.meta`` (header, msgpack,
CRC trailer), that drive's shard of the newest version, taken from the
journal's ``Data[dataDir]`` (a small object kept inline) or, if it is not
there, from ``<dataDir>/part.N`` (shard files), every ``[32-byte
digest][chunk]`` frame checked with ``hh_ref``, the shards put in the order
of the erasure index each drive's journal states, the body rebuilt from the
k data shards, and the data shards encoded again with a plain GF(256)
Reed-Solomon encode (log/antilog tables; the matrix the geometry states) to
compare with what the parity drives hold. Same data, same answers, either
layout.

Imports nothing of the program: numpy, ``msgpack``, ``hh_ref``; the geometry
comes from the configuration file (``data``, ``parity``, ``block_bytes``,
``bitrot_chunk_bytes``, ``bitrot_key_hex`` and, where stated, ``rs_matrix``
and ``rs_field_poly``)."""
from __future__ import annotations

import functools
import hashlib
import os
import struct
import zlib

import msgpack
import numpy as np

import hh_ref

HEADER_V1, HEADER_V2 = b"XLT2 1  ", b"XLT2 2  "
TRAILER_MAGIC = b"XLC1"
DIGEST = 32


class Bad(Exception):
    """A drive's copy cannot be read as the layout says."""


# --- GF(256), the field of the geometry's ``rs_field_poly`` (0x11D) ---------

@functools.lru_cache(maxsize=None)
def _tables(poly: int) -> tuple[np.ndarray, np.ndarray]:
    """(antilog[510], log[256]) of GF(2^8) under ``poly``, generator 2."""
    exp = np.zeros(510, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i], log[x] = x, i
        x <<= 1
        if x & 0x100:
            x ^= poly
    exp[255:] = exp[:255]
    return exp, log


def _mul(a: int, b: int, poly: int) -> int:
    exp, log = _tables(poly)
    return 0 if not a or not b else int(exp[log[a] + log[b]])


def _invert(m: list[list[int]], poly: int) -> list[list[int]]:
    """Gauss-Jordan over GF(256) on a square matrix of ints."""
    exp, log = _tables(poly)
    n = len(m)
    a = [row[:] + [int(i == r) for i in range(n)] for r, row in enumerate(m)]
    for c in range(n):
        p = next(r for r in range(c, n) if a[r][c])
        a[c], a[p] = a[p], a[c]
        inv = int(exp[255 - log[a[c][c]]])
        a[c] = [_mul(inv, v, poly) for v in a[c]]
        for r in range(n):
            if r != c and a[r][c]:
                f = a[r][c]
                a[r] = [v ^ _mul(f, w, poly) for v, w in zip(a[r], a[c])]
    return [row[n:] for row in a]


@functools.lru_cache(maxsize=None)
def parity_matrix(k: int, m: int, kind: str = "vandermonde",
                  poly: int = 0x11D) -> tuple[tuple[int, ...], ...]:
    """The m parity rows of the systematic (k+m, k) encode matrix.
    ``vandermonde``: V[r][c] = r^c, times the inverse of its top square
    (klauspost/reedsolomon's default, which the reference wraps)."""
    if kind != "vandermonde":
        raise ValueError(f"inline_ref: no matrix {kind!r}")
    exp, log = _tables(poly)

    def power(r: int, c: int) -> int:
        return 1 if c == 0 else 0 if r == 0 else int(exp[log[r] * c % 255])

    v = [[power(r, c) for c in range(k)] for r in range(k + m)]
    top_inv = _invert(v[:k], poly)
    rows = []
    for r in range(k, k + m):
        rows.append(tuple(
            functools.reduce(lambda x, y: x ^ y,
                             (_mul(v[r][j], top_inv[j][c], poly)
                              for j in range(k)))
            for c in range(k)))
    return tuple(rows)


def encode_parity(data: np.ndarray, m: int, kind: str = "vandermonde",
                  poly: int = 0x11D) -> np.ndarray:
    """uint8 [k, n] data shards -> uint8 [m, n] parity shards."""
    k = data.shape[0]
    exp, log = _tables(poly)
    logs = log[data]                    # log of every byte (0 where byte 0)
    zero = data == 0
    out = np.zeros((m, data.shape[1]), np.uint8)
    for r, row in enumerate(parity_matrix(k, m, kind, poly)):
        for c, coef in enumerate(row):
            if coef:
                term = exp[logs[c] + log[coef]].astype(np.uint8)
                term[zero[c]] = 0
                out[r] ^= term
    return out


# --- one drive --------------------------------------------------------------

def parse_xl_meta(blob: bytes) -> dict:
    """``{"Versions": [...], "Data": {dataDir: bytes}}`` of one journal;
    ``Bad`` for a wrong header, a missing or wrong CRC trailer, or msgpack
    that does not parse."""
    if blob[:8] == HEADER_V2:
        if len(blob) < 16 or blob[-8:-4] != TRAILER_MAGIC:
            raise Bad("xl.meta: no trailer")
        if zlib.crc32(blob[:-8]) & 0xFFFFFFFF != \
                struct.unpack("<I", blob[-4:])[0]:
            raise Bad("xl.meta: trailer CRC differs")
        payload = blob[8:-8]
    elif blob[:8] == HEADER_V1:
        payload = blob[8:]
    else:
        raise Bad("xl.meta: header")
    try:
        doc = msgpack.unpackb(payload, raw=False, strict_map_key=False)
    except Exception as e:  # noqa: BLE001
        raise Bad(f"xl.meta: msgpack: {e}") from e
    return {"Versions": list(doc.get("Versions", [])),
            "Data": dict(doc.get("Data", {}))}


def drive_copy(obj_dir: str) -> dict:
    """What one drive holds of the object's newest version: ``version``
    (the journal's entry), ``index`` (its erasure index, 1-based),
    ``inline`` (the shard came from ``Data``), ``parts`` (the framed shard
    of each part, in part order) and ``bytes`` (the sizes of every file
    under the object's directory, summed)."""
    with open(os.path.join(obj_dir, "xl.meta"), "rb") as f:
        doc = parse_xl_meta(f.read())
    if not doc["Versions"] or doc["Versions"][0].get("Type") != 1:
        raise Bad("xl.meta: the newest version is no object")
    v = doc["Versions"][0]["V"]
    ddir = v.get("ddir", "")
    inline = ddir in doc["Data"]
    if inline:
        if len(v["parts"]) != 1:
            raise Bad("an inline version of more than one part")
        parts = [bytes(doc["Data"][ddir])]
    else:
        parts = []
        for p in v["parts"]:
            with open(os.path.join(obj_dir, ddir, f"part.{p['n']}"),
                      "rb") as f:
                parts.append(f.read())
    total = 0
    for root, _dirs, files in os.walk(obj_dir):
        total += sum(os.path.getsize(os.path.join(root, n)) for n in files)
    return {"version": v, "index": v["ec"]["i"], "inline": inline,
            "parts": parts, "bytes": total}


def _part_sizes(v: dict) -> list[int]:
    return [p["s"] for p in v["parts"]]


def shard_len(size: int, k: int, block: int) -> int:
    """Logical bytes of one shard of a part of ``size`` bytes: a full
    shard a full block, ceil(n / k) for the last, short one."""
    full, last = divmod(size, block)
    return full * -(-block // k) + -(-last // k)


def frame_chunk(geom: dict) -> int:
    full_shard = -(-geom["block_bytes"] // geom["data"])
    chunk = geom["bitrot_chunk_bytes"]
    return full_shard if full_shard % chunk else chunk


def unframe(framed: bytes, logical: int, chunk: int, key: bytes
            ) -> tuple[np.ndarray, int]:
    """``[digest][chunk]``... -> (the shard's logical bytes, how many
    frames carry a digest that is not their chunk's). ``Bad`` if the
    length is not that of ``logical`` bytes framed."""
    n_full, tail = divmod(logical, chunk)
    want = (n_full + bool(tail)) * DIGEST + logical
    if len(framed) != want:
        raise Bad(f"a framed shard of {len(framed)} bytes where {logical} "
                  f"logical bytes frame to {want}")
    arr = np.frombuffer(framed, np.uint8)
    out = np.empty(logical, np.uint8)
    bad = 0
    if n_full:
        frames = arr[: n_full * (DIGEST + chunk)].reshape(
            n_full, DIGEST + chunk)
        out[: n_full * chunk] = frames[:, DIGEST:].reshape(-1)
        digs = hh_ref.hh256_rows(key, frames[:, DIGEST:])
        bad += int((digs != frames[:, :DIGEST]).any(axis=1).sum())
    if tail:
        last = arr[n_full * (DIGEST + chunk):]
        out[n_full * chunk:] = last[DIGEST:]
        dig = hh_ref.hh256_rows(key, last[DIGEST:].reshape(1, tail))
        bad += int((dig[0] != last[:DIGEST]).any())
    return out, bad


# --- the object over its drives ---------------------------------------------

def check_object(obj_dirs: list[str], geom: dict, size: int | None = None,
                 sha256: str | None = None) -> dict:
    """One object as its drives hold it (``obj_dirs``: the object's
    directory on every drive). Returns counts, each of which has to be 0:

    ``body_mismatch``    1 when the body rebuilt from the k data shards is
                         not ``size`` bytes of SHA-256 ``sha256`` (or
                         cannot be rebuilt: a data shard's drive lacks the
                         object, two drives state one index)
    ``digest_bad``       drives with a frame whose digest is not its
                         chunk's, or whose copy does not parse
    ``parity_mismatch``  parity drives whose shard is not the plain encode
                         of the data shards
    ``bytes``            bytes at rest: every file under the object's
                         directories, summed over the drives

    and ``layout`` (``inline``, ``files``, ``mixed``), ``size`` (the
    journal's) and ``why`` (a line a fault)."""
    k, m, block = geom["data"], geom["parity"], geom["block_bytes"]
    key = bytes.fromhex(geom["bitrot_key_hex"])
    chunk = frame_chunk(geom)
    kind = geom.get("rs_matrix", "vandermonde").split()[0]
    poly = geom.get("rs_field_poly", 0x11D)
    out = {"body_mismatch": 0, "digest_bad": 0, "parity_mismatch": 0,
           "bytes": 0, "layout": "", "size": None, "why": []}
    copies: dict[int, dict] = {}
    layouts = set()
    for d in obj_dirs:
        try:
            c = drive_copy(d)
        except FileNotFoundError:
            out["why"].append(f"{d}: no copy")
            continue
        except (Bad, KeyError, TypeError) as e:
            out["digest_bad"] += 1
            out["why"].append(f"{d}: {e}")
            continue
        out["bytes"] += c["bytes"]
        layouts.add("inline" if c["inline"] else "files")
        if not 1 <= c["index"] <= k + m or c["index"] in copies:
            out["why"].append(f"{d}: erasure index {c['index']} is out "
                              "of range or stated twice")
            out["body_mismatch"] = 1
            continue
        copies[c["index"]] = c
    out["layout"] = layouts.pop() if len(layouts) == 1 else \
        "mixed" if layouts else "none"
    if not copies:
        out["body_mismatch"] = 1
        return out
    # the version most drives state (its data directory names it)
    votes: dict[str, int] = {}
    for c in copies.values():
        votes[c["version"]["ddir"]] = votes.get(c["version"]["ddir"], 0) + 1
    ddir = max(votes, key=votes.get)
    stale = [i for i, c in copies.items() if c["version"]["ddir"] != ddir]
    for i in stale:
        out["why"].append(f"shard {i}: another version "
                          f"({copies[i]['version']['ddir']})")
        del copies[i]
    v = next(iter(copies.values()))["version"]
    sizes = _part_sizes(v)
    out["size"] = v["size"]
    # unframe every drive's shard of every part
    shards: dict[int, list[np.ndarray]] = {}
    for i, c in sorted(copies.items()):
        try:
            got, bad = [], 0
            for framed, psize in zip(c["parts"], sizes):
                arr, b = unframe(framed, shard_len(psize, k, block), chunk,
                                 key)
                got.append(arr)
                bad += b
        except Bad as e:
            out["digest_bad"] += 1
            out["why"].append(f"shard {i}: {e}")
            continue
        if bad:
            out["digest_bad"] += 1
            out["why"].append(f"shard {i}: {bad} frame(s) whose digest is "
                              "not their chunk's")
        shards[i] = got
    # the body from the k data shards, part by part and block by block
    body = hashlib.sha256()
    n_body = 0
    whole = all(i in shards for i in range(1, k + 1))
    if not whole:
        out["body_mismatch"] = 1
        out["why"].append("data shards " + str(
            [i for i in range(1, k + 1) if i not in shards])
            + " are on no drive")
    full_shard = -(-block // k)
    for p, psize in enumerate(sizes):
        if not whole:
            break
        data = np.stack([shards[i][p] for i in range(1, k + 1)])
        for b, off in enumerate(range(0, psize, block)):
            n = min(block, psize - off)
            cols = slice(b * full_shard, b * full_shard + -(-n // k))
            body.update(data[:, cols].reshape(-1)[:n].tobytes())
            n_body += n
        parity = encode_parity(data, m, kind, poly)
        for j in range(m):
            held = shards.get(k + 1 + j)
            if held is not None and not np.array_equal(held[p], parity[j]):
                out["parity_mismatch"] += 1
                out["why"].append(f"shard {k + 1 + j}: not the encode of "
                                  "the data shards")
    if whole:
        if n_body != v["size"] or (size is not None and n_body != size) \
                or (sha256 is not None and body.hexdigest() != sha256):
            out["body_mismatch"] = 1
            out["why"].append(f"body of {n_body} bytes, SHA-256 "
                              f"{body.hexdigest()[:16]}..., is not what "
                              "was PUT")
    return out


def at_rest_limit(size: int, geom: dict, per_drive: int = 4096) -> int:
    """What an object of ``size`` bytes may take at rest: (k+m)/k of its
    size plus ``per_drive`` bytes a drive (the journal, the frames'
    digests)."""
    k, m = geom["data"], geom["parity"]
    return (k + m) * size // k + (k + m) * per_drive
