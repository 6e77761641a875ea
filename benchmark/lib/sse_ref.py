"""Plain reference of the SSE formats of docs/sse.md, multipart included.

Straightforward Python for the tests and the benchmark to hold the server
to: unseal an object key under a given master key or customer key, derive
part keys, open packages, map a plaintext range to parts and packages, and
read an object's stored bytes back from the shard files of the drives. It
shares no code with what it checks: nothing of ``crypto/sse.py``'s stream
classes, the numpy lanes, ``runtime/`` or ``ops/`` is imported. The AEAD
primitives are the ``cryptography`` wheel's where it is present and a
pure-Python ChaCha20-Poly1305 (RFC 8439) otherwise (AES-GCM then raises:
the server cannot write it either). ``benchmark/lib/sse_ref.py`` is the
benchmark's own copy of this file.
"""
from __future__ import annotations

import base64
import hashlib
import hmac
import os
import struct
from typing import NamedTuple

try:
    from cryptography.exceptions import InvalidTag
    from cryptography.hazmat.primitives.ciphers.aead import (
        AESGCM, ChaCha20Poly1305)
    HAVE_CRYPTOGRAPHY = True
except ImportError:
    HAVE_CRYPTOGRAPHY = False

    class InvalidTag(Exception):  # type: ignore[no-redef]
        pass

PKG = 64 << 10
TAG = 16
UNIT = PKG + TAG
AAD = b"minio-tpu-sse-v1"
AESGCM_NAME = "AES256-GCM"
CHACHA_NAME = "CHACHA20-POLY1305"
KMS_DEFAULT_KEY_ID = "minio-tpu-default"
M = "x-minio-internal-sse-"     # prefix of the metadata keys


class BadTag(Exception):
    """A package, a sealed key or a KMS blob did not authenticate."""


# --- ChaCha20-Poly1305, RFC 8439, for hosts without the wheel ---------------

def _rotl(v: int, n: int) -> int:
    return ((v << n) & 0xFFFFFFFF) | (v >> (32 - n))


def _quarter(s: list[int], a: int, b: int, c: int, d: int) -> None:
    s[a] = (s[a] + s[b]) & 0xFFFFFFFF
    s[d] = _rotl(s[d] ^ s[a], 16)
    s[c] = (s[c] + s[d]) & 0xFFFFFFFF
    s[b] = _rotl(s[b] ^ s[c], 12)
    s[a] = (s[a] + s[b]) & 0xFFFFFFFF
    s[d] = _rotl(s[d] ^ s[a], 8)
    s[c] = (s[c] + s[d]) & 0xFFFFFFFF
    s[b] = _rotl(s[b] ^ s[c], 7)


def _chacha_block(key: bytes, counter: int, nonce: bytes) -> bytes:
    init = [0x61707865, 0x3320646E, 0x79622D32, 0x6B206574,
            *struct.unpack("<8I", key), counter,
            *struct.unpack("<3I", nonce)]
    s = list(init)
    for _ in range(10):
        _quarter(s, 0, 4, 8, 12)
        _quarter(s, 1, 5, 9, 13)
        _quarter(s, 2, 6, 10, 14)
        _quarter(s, 3, 7, 11, 15)
        _quarter(s, 0, 5, 10, 15)
        _quarter(s, 1, 6, 11, 12)
        _quarter(s, 2, 7, 8, 13)
        _quarter(s, 3, 4, 9, 14)
    return struct.pack("<16I", *((a + b) & 0xFFFFFFFF
                                 for a, b in zip(s, init)))


def _chacha_xor(key: bytes, nonce: bytes, data: bytes) -> bytes:
    out = bytearray()
    for i in range(0, len(data), 64):
        ks = _chacha_block(key, 1 + i // 64, nonce)
        out += bytes(x ^ y for x, y in zip(data[i:i + 64], ks))
    return bytes(out)


def _poly1305(key: bytes, msg: bytes) -> bytes:
    r = int.from_bytes(key[:16], "little") & \
        0x0FFFFFFC0FFFFFFC0FFFFFFC0FFFFFFF
    s = int.from_bytes(key[16:], "little")
    acc, p = 0, (1 << 130) - 5
    for i in range(0, len(msg), 16):
        n = int.from_bytes(msg[i:i + 16] + b"\x01", "little")
        acc = (acc + n) * r % p
    return ((acc + s) & ((1 << 128) - 1)).to_bytes(16, "little")


def _pad16(b: bytes) -> bytes:
    return b + b"\x00" * (-len(b) % 16)


def _chacha_tag(key: bytes, nonce: bytes, aad: bytes, ct: bytes) -> bytes:
    otk = _chacha_block(key, 0, nonce)[:32]
    return _poly1305(otk, _pad16(aad) + _pad16(ct)
                     + struct.pack("<QQ", len(aad), len(ct)))


def aead_seal(cipher: str, key: bytes, nonce: bytes, data: bytes,
              aad: bytes) -> bytes:
    if cipher == AESGCM_NAME:
        return AESGCM(key).encrypt(nonce, data, aad)
    if HAVE_CRYPTOGRAPHY:
        return ChaCha20Poly1305(key).encrypt(nonce, data, aad)
    ct = _chacha_xor(key, nonce, data)
    return ct + _chacha_tag(key, nonce, aad, ct)


def aead_open(cipher: str, key: bytes, nonce: bytes, sealed: bytes,
              aad: bytes) -> bytes:
    """The plaintext, or BadTag. ``cipher`` is the metadata's name; absent
    or unknown names read as AES-256-GCM, as legacy objects do."""
    if cipher != CHACHA_NAME:
        if not HAVE_CRYPTOGRAPHY:
            raise RuntimeError("AES-256-GCM needs the 'cryptography' wheel")
        try:
            return AESGCM(key).decrypt(nonce, sealed, aad)
        except InvalidTag:
            raise BadTag from None
    if HAVE_CRYPTOGRAPHY:
        try:
            return ChaCha20Poly1305(key).decrypt(nonce, sealed, aad)
        except InvalidTag:
            raise BadTag from None
    ct, tag = sealed[:-TAG], sealed[-TAG:]
    if len(sealed) < TAG or not hmac.compare_digest(
            _chacha_tag(key, nonce, aad, ct), tag):
        raise BadTag
    return _chacha_xor(key, nonce, ct)


# --- keys --------------------------------------------------------------------

def unseal_oek(meta: dict, bucket: str, key: str, *,
               master_key: bytes | None = None,
               client_key: bytes | None = None) -> bytes:
    """The object key of ``bucket/key`` from its metadata: under the
    deployment's KMS master key (SSE-S3, SSE-KMS with the local KMS) or the
    customer's key (SSE-C)."""
    scheme = meta[M + "scheme"]
    cipher = meta.get(M + "cipher", "") or AESGCM_NAME
    sealed = base64.b64decode(meta[M + "sealed-key"])
    if scheme == "C":
        scheme_key = client_key
    else:
        blob = base64.b64decode(meta[M + "kms-blob"])
        if scheme == "KMS":
            key_id = meta.get(M + "kms-key-id", "")
            user_ctx = base64.b64decode(
                meta.get(M + "kms-context", "")).decode()
            ctx = f"{bucket}/{key}|{user_ctx}"
        else:
            key_id, ctx = "", f"{bucket}/{key}"
        sub = master_key
        if key_id and key_id != KMS_DEFAULT_KEY_ID:
            sub = hmac.new(master_key, b"minio-tpu-kms-sub:"
                           + key_id.encode(), hashlib.sha256).digest()
        # the local KMS seals its data keys with AES-256-GCM whatever the
        # package cipher is
        scheme_key = aead_open(AESGCM_NAME, sub, blob[:12], blob[12:],
                               ctx.encode())
    kek = hashlib.sha256(b"minio-tpu-sse-kek:" + scheme_key
                         + f":{bucket}/{key}".encode()).digest()
    return aead_open(cipher, kek, sealed[:12], sealed[12:], AAD)


def part_key(oek: bytes, number: int) -> bytes:
    return hmac.new(oek, struct.pack("<I", number), hashlib.sha256).digest()


class Stream(NamedTuple):
    key: bytes
    iv: bytes
    plain: int


def enc_size(plain: int) -> int:
    return plain + TAG * -(-plain // PKG) if plain > 0 else 0


def streams_of(meta: dict, parts: list[dict], oek: bytes) -> list[Stream]:
    """The package streams of an object from its ``xl.meta``: ``parts`` are
    the version's part records ({n, s, as, m}). One stream per part for a
    multipart upload, one under (OEK, base IV) for a single PUT."""
    if not meta.get(M + "multipart"):
        return [Stream(oek, base64.b64decode(meta[M + "iv"]),
                       int(meta[M + "plain-size"]))]
    return [Stream(part_key(oek, int(p["m"]["sse-part"])),
                   base64.b64decode(p["m"]["sse-iv"]), p["as"])
            for p in parts]


# --- packages ----------------------------------------------------------------

def open_package(cipher: str, key: bytes, iv: bytes, seq: int,
                 sealed: bytes) -> bytes:
    return aead_open(cipher, key, iv[:8] + struct.pack(">I", seq), sealed,
                     AAD + struct.pack(">I", seq))


def open_stream(cipher: str, s: Stream, stored: bytes) -> bytes:
    if len(stored) != enc_size(s.plain):
        raise BadTag
    out = b"".join(open_package(cipher, s.key, s.iv, i // UNIT,
                                stored[i:i + UNIT])
                   for i in range(0, len(stored), UNIT))
    if len(out) != s.plain:
        raise BadTag
    return out


def open_object(cipher: str, streams: list[Stream], stored: bytes) -> bytes:
    out, at = [], 0
    for s in streams:
        out.append(open_stream(cipher, s, stored[at:at + enc_size(s.plain)]))
        at += enc_size(s.plain)
    if at != len(stored):
        raise BadTag
    return b"".join(out)


# --- ranges ------------------------------------------------------------------

class Piece(NamedTuple):
    """The share of one part in a plaintext range: packages pkg0..pkg1 of
    part ``part`` (0-based), which lie at ``stored_off`` of the object's
    stored bytes and are ``stored_len`` long; of their plaintext ``skip``
    bytes are dropped and ``take`` kept."""
    part: int
    pkg0: int
    pkg1: int
    stored_off: int
    stored_len: int
    skip: int
    take: int


def map_range(plains: list[int], offset: int, length: int) -> list[Piece]:
    out, p0, e0 = [], 0, 0
    end = min(offset + length, sum(plains))
    for i, plain in enumerate(plains):
        lo, hi = max(offset, p0), min(end, p0 + plain)
        if lo < hi:
            pkg0, pkg1 = (lo - p0) // PKG, (hi - 1 - p0) // PKG
            stop = min((pkg1 + 1) * UNIT, enc_size(plain))
            out.append(Piece(i, pkg0, pkg1, e0 + pkg0 * UNIT,
                             stop - pkg0 * UNIT, lo - p0 - pkg0 * PKG,
                             hi - lo))
        p0 += plain
        e0 += enc_size(plain)
    return out


def read_range(cipher: str, streams: list[Stream], stored: bytes,
               offset: int, length: int) -> bytes:
    out = []
    for pc in map_range([s.plain for s in streams], offset, length):
        s = streams[pc.part]
        span = stored[pc.stored_off:pc.stored_off + pc.stored_len]
        plain = b"".join(
            open_package(cipher, s.key, s.iv, pc.pkg0 + i // UNIT,
                         span[i:i + UNIT])
            for i in range(0, len(span), UNIT))
        out.append(plain[pc.skip:pc.skip + pc.take])
    return b"".join(out)


# --- at rest: the drives' own files ------------------------------------------

def read_xl_meta(path: str) -> dict:
    """The newest version's record of an ``xl.meta`` file: {ddir, size,
    meta, parts, ec}."""
    import msgpack
    with open(path, "rb") as f:
        blob = f.read()
    if blob[:8] == b"XLT2 2  ":
        payload = blob[8:-8]        # "XLC1" + CRC32 trailer
    elif blob[:8] == b"XLT2 1  ":
        payload = blob[8:]
    else:
        raise ValueError(f"{path}: not an xl.meta")
    doc = msgpack.unpackb(payload, raw=False, strict_map_key=False)
    newest = max(doc["Versions"], key=lambda v: v.get("ModTime", 0.0))
    return newest["V"]


def unframe(framed: bytes, chunk: int, digest_len: int = 32) -> bytes:
    """A bitrot-framed shard file ([digest][chunk]...) without its
    digests."""
    step = digest_len + chunk
    return b"".join(framed[i + digest_len:i + step]
                    for i in range(0, len(framed), step))


def stored_object(drive_dirs: list[str], bucket: str, key: str
                  ) -> tuple[dict, list[bytes], dict[str, bytes]]:
    """What the drives hold of ``bucket/key``: the version record, each
    part's stored bytes reassembled from the DATA shards (needs all k of
    them), and every shard file's raw bytes by path."""
    version, shards, files = None, {}, {}
    for d in drive_dirs:
        meta_path = os.path.join(d, bucket, key, "xl.meta")
        if not os.path.exists(meta_path):
            continue
        v = read_xl_meta(meta_path)
        version = version or v
        shards[v["ec"]["i"]] = os.path.join(d, bucket, key, v["ddir"])
    k, block = version["ec"]["m"], version["ec"]["bs"]
    chunk = int(version["meta"]["x-minio-internal-bitrot-chunk"])
    parts = []
    for p in version["parts"]:
        cols = []
        for idx in range(1, k + 1):
            path = os.path.join(shards[idx], f"part.{p['n']}")
            with open(path, "rb") as f:
                files[path] = f.read()
            cols.append(unframe(files[path], chunk))
        out, at = [], 0     # ``at``: offset inside every shard file
        for b0 in range(0, p["s"], block):
            blen = min(block, p["s"] - b0)
            piece = -(-blen // k)
            row = b"".join(c[at:at + piece] for c in cols)
            out.append(row[:blen])
            at += piece
        parts.append(b"".join(out))
    for idx, ddir in shards.items():    # parity shards: raw bytes only
        if idx > k:
            for p in version["parts"]:
                path = os.path.join(ddir, f"part.{p['n']}")
                with open(path, "rb") as f:
                    files[path] = f.read()
    return version, parts, files
