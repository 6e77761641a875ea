"""Plain reference for a bucket that holds every size: what an object of
``size`` bytes HAS to be at rest under the configuration's geometry, what
ETag it has to answer, and how much it may take on the drives.

Three layouts live side by side in such a bucket, and the size alone says
which one an object has:

``inline``     ``0 < size <= inline_max_bytes``: k+m erasure shards, one a
               drive, inside the drives' ``xl.meta``; no data directory.
``files``      larger, up to ``multipart_min_bytes - 1``: one part, k+m
               shard files ``<dataDir>/part.1``.
``multipart``  from ``multipart_min_bytes``: the CLIENT sends it as parts
               of ``multipart_part_bytes`` (the last one short), so it is
               ``ceil(size / multipart_part_bytes)`` parts of k+m shard
               files each.

ETags: a body or a part below ``etag_min_bytes`` answers the MD5 of its
bytes, one at or over it the MD5 of its data shards' bitrot digests
(``hh_ref``); a multipart object answers the fold of its parts' ETags,
``-N`` (``mp_model.fold``).

At rest: an inline version at most (k+m)/k of its size plus 4 KiB a drive
(``inline_ref.at_rest_limit``); a version of shard files at most (k+m)/k of
its size, plus 1/512 of that for the frames' digests (32 bytes a 16 KiB
chunk), plus 4 KiB a drive a part (the journal, the padding of each
block's last shard, the tail frames' digests).

``check`` reads one object from its drives as they lie through
``inline_ref.check_object`` (body, frame digests, parity by a plain encode)
and holds it to its size's layout and bound. ``Model`` is ``refmodel.Model``
taught the ``PART`` sub-records of ``lib/sizes_client.py``.

Imports nothing of the program: numpy, ``hashlib``, ``inline_ref``,
``hh_ref``, ``mp_model.fold``, ``refmodel``."""
from __future__ import annotations

import hashlib
import os

import numpy as np

import hh_ref
import inline_ref
import refmodel
from mp_model import fold

AT_REST = ("at_rest_body_mismatch", "at_rest_digest_bad",
           "at_rest_parity_mismatch", "at_rest_bytes_over",
           "at_rest_layout_wrong")
#: rows of 16 KiB hashed in one numpy pass (64 MiB of body: 153 MiB/s on
#: the sandbox's CPU, 114 at half and 73 at a quarter of that)
BATCH_ROWS = 4096


# --- what a size has to be ---------------------------------------------------

def part_sizes(size: int, geom: dict) -> list[int]:
    """The parts an object of ``size`` bytes is sent as: itself, or from
    ``multipart_min_bytes`` parts of ``multipart_part_bytes``, the last
    one short."""
    if size < geom["multipart_min_bytes"]:
        return [size]
    part = geom["multipart_part_bytes"]
    return [min(part, size - lo) for lo in range(0, size, part)]


def route_of(size: int, geom: dict) -> str:
    """``inline``, ``file`` or ``multipart``: the way such an object goes
    in (the program's ``route`` label says the same words)."""
    if 0 < size <= geom["inline_max_bytes"]:
        return "inline"
    return "multipart" if size >= geom["multipart_min_bytes"] else "file"


def layout_of(size: int, geom: dict) -> tuple[str, list[int]]:
    """(``inline`` | ``files``, the sizes of its parts) an object of
    ``size`` bytes has to have at rest."""
    return ("inline" if route_of(size, geom) == "inline" else "files",
            part_sizes(size, geom))


def at_rest_limit(size: int, geom: dict) -> int:
    """Bytes an object of ``size`` bytes may take on its drives, all
    files under its directories summed."""
    layout, parts = layout_of(size, geom)
    if layout == "inline":
        return inline_ref.at_rest_limit(size, geom)
    k, m = geom["data"], geom["parity"]
    shards = (k + m) * size // k
    return shards + shards // 512 + (k + m) * 4096 * len(parts)


# --- the ETag of each class -----------------------------------------------------

def reference_etags(bodies: list, geom: dict) -> list[str]:
    """``hh_ref.reference_etags`` for bodies of any lengths: the same
    rule (MD5 of the body below ``etag_min_bytes``, else MD5 over the data
    shards' frame digests, block by block, shard by shard), with the full
    blocks of ALL bodies hashed together, ``BATCH_ROWS`` chunks a numpy
    pass (a pass costs by its steps, not by its rows), and only each
    body's short last block on its own."""
    k, block = geom["data"], geom["block_bytes"]
    full_shard = -(-block // k)
    chunk = inline_ref.frame_chunk(geom)
    key = bytes.fromhex(geom["bitrot_key_hex"])
    per_block = full_shard // chunk * k     # rows a full block makes
    out: list[str | None] = [None] * len(bodies)
    md5s: dict[int, "hashlib._Hash"] = {}
    todo: list[tuple[int, int]] = []        # (body, offset) of full blocks
    for i, b in enumerate(bodies):
        if len(b) < geom["etag_min_bytes"]:
            out[i] = hashlib.md5(b).hexdigest()
            continue
        md5s[i] = hashlib.md5()
        todo += [(i, off) for off in range(0, len(b) - block + 1, block)]
    step = max(1, BATCH_ROWS // per_block)
    for g in range(0, len(todo), step):
        group = todo[g: g + step]
        rows = np.empty((len(group) * per_block, chunk), np.uint8)
        for j, (i, off) in enumerate(group):
            # a full block splits into k shards of full_shard bytes, each
            # into its chunks: rows in the order the digests are folded
            rows[j * per_block: (j + 1) * per_block] = np.frombuffer(
                bodies[i], np.uint8, block, off).reshape(per_block, chunk)
        digs = hh_ref.hh256_rows(key, rows).reshape(len(group), -1)
        for (i, _off), d in zip(group, digs):
            md5s[i].update(d.tobytes())
    for i, md5 in md5s.items():
        n = len(bodies[i]) % block
        if n:
            shard_len = -(-n // k)
            arr = np.zeros(k * shard_len, np.uint8)
            arr[:n] = np.frombuffer(bodies[i], np.uint8, n,
                                    len(bodies[i]) - n)
            md5.update(hh_ref.shard_digests(
                key, arr.reshape(k, shard_len), chunk))
        out[i] = md5.hexdigest()
    return out


def etag_of(body, geom: dict) -> str:
    """The ETag an object of these bytes has to answer, by its class."""
    sizes = part_sizes(len(body), geom)
    if len(sizes) == 1:
        return reference_etags([body], geom)[0]
    view, parts, lo = memoryview(body), [], 0
    for n in sizes:
        parts.append(view[lo: lo + n])
        lo += n
    return fold(reference_etags(parts, geom))


# --- one object on its drives -----------------------------------------------------

def stated_parts(obj_dirs: list[str]) -> list[int] | None:
    """The part sizes the first drive that has a journal states for the
    newest version (None where no drive has one that parses)."""
    for d in obj_dirs:
        try:
            with open(os.path.join(d, "xl.meta"), "rb") as f:
                doc = inline_ref.parse_xl_meta(f.read())
            return [p["s"] for p in doc["Versions"][0]["V"]["parts"]]
        except (OSError, inline_ref.Bad, KeyError, IndexError, TypeError):
            continue
    return None


def check(obj_dirs: list[str], geom: dict, size: int, sha256: str) -> dict:
    """``inline_ref.check_object`` of one object, and two counts more,
    each of which has to be 0: ``layout_wrong`` (1 when the layout on the
    drives or the parts the journal states are not what ``size`` names)
    and ``bytes_over`` (1 when it takes more at rest than
    ``at_rest_limit``); ``limit`` is that bound, ``want`` the layout."""
    got = inline_ref.check_object(obj_dirs, geom, size, sha256)
    layout, parts = layout_of(size, geom)
    stated = stated_parts(obj_dirs)
    got["want"] = (layout, parts)
    got["layout_wrong"] = int(got["layout"] != layout or stated != parts)
    if got["layout_wrong"]:
        got["why"].append(f"layout {got['layout']!r} of parts {stated}, "
                          f"where {size} bytes are {layout!r} of {parts}")
    got["limit"] = at_rest_limit(size, geom)
    got["bytes_over"] = int(got["bytes"] > got["limit"])
    if got["bytes_over"]:
        got["why"].append(f"{got['bytes']} bytes at rest, over "
                          f"{got['limit']}")
    return got


# --- the model ------------------------------------------------------------------

class Model(refmodel.Model):
    """``refmodel.Model`` (key -> size, SHA-256, ETag; every limit 0) with
    the five ``at_rest_*`` counts, and taught ``PART``: one part of a
    multipart PUT, a sub-record of the ``PUT`` record that carries the
    whole upload (Create sent to Complete's 200; its ``etag_ref`` is the
    fold of the part ETags the server returned, so the base model's rule
    for a PUT holds the Complete to it). A part has to be acknowledged
    with the host reference's ETag for its bytes; it is no operation of
    the deck and is not counted as one."""

    def __init__(self):
        super().__init__()
        for name in AT_REST:    # reported by every run, sound or not
            self.counts[name] = 0

    def replay(self, records: list[dict]) -> None:
        for r in records:
            if r["op"] != "PART":
                super().replay([r])
            elif r["status"] == 503:
                self.fault("ops_refused_503", r, r.get("err", ""))
            elif r["status"] != 200:
                self.fault("ops_errored", r, r.get("err", ""))
            elif r["etag"] != r["etag_ref"]:
                self.fault("put_etags_wrong", r, f"part {r['part']}: "
                           f"{r['etag']} != host reference {r['etag_ref']}")

    def at_rest(self, key: str, got: dict) -> None:
        """Charge what ``check`` found wrong of ``key``."""
        rec = {"op": "ATREST", "key": key, "status": 0}
        for name in AT_REST:
            for _ in range(got[name.removeprefix("at_rest_")]):
                self.fault(name, rec, "; ".join(got["why"][:3]))
