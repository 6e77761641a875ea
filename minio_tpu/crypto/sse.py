"""SSE core: header parsing, envelope key sealing, and the package cipher
stream (reference cmd/crypto/sse-c.go, sse-s3.go, metadata.go and the DARE
stream the reference gets from sio; re-designed here as explicit AEAD
packages so ranged reads stay simple and auditable).

Stream format: plaintext split into PKG_SIZE packages; package i is
``AEAD(OEK).seal(nonce_i, pkg, aad_i)`` = ciphertext||16-byte tag with
``nonce_i = base_iv[0:8] || BE32(seq0+i)`` and ``aad_i = "minio-tpu-sse-v1"
|| BE32(seq0+i)``. Encrypted length = plain + 16*ceil(plain/PKG_SIZE).
Binding the sequence number into nonce AND AAD rejects package reordering
or truncation-with-splice.

Two package ciphers share that framing (ISSUE 8 / ROADMAP item 4):

- **AES-256-GCM** — the CPU-native scheme (AES-NI via the optional
  ``cryptography`` wheel; raises at use when absent, as since PR 1).
- **ChaCha20-Poly1305** — 32-bit add/xor/rotl, the VPU-native scheme: a
  whole PUT/GET block's packages are sealed/opened in ONE coalesced
  flush through the dispatch plane (runtime/dispatch.py op ``sse_xor``,
  kernel ops/chacha_pallas.py) with QoS class + byte accounting, the
  kernel-layer fault hook and CPU salvage; the numpy host lane
  (crypto/chacha20poly1305.py) is bit-identical and needs no native
  crypto dependency at all.

The object's cipher is recorded in internal metadata (META_CIPHER);
absent = AES-256-GCM (legacy objects). The OEK envelope seal follows the
package cipher, so an SSE-C ChaCha object is readable with zero optional
dependencies. docs/sse.md has the wire formats and routing rules."""
from __future__ import annotations

import base64
import hashlib
import hmac
import secrets
import struct
import time
from dataclasses import dataclass, field

import numpy as np

try:
    from cryptography.exceptions import InvalidTag
    from cryptography.hazmat.primitives.ciphers.aead import AESGCM
    HAVE_CRYPTOGRAPHY = True
except ImportError:  # gated optional dep: SSE raises at use, not import
    HAVE_CRYPTOGRAPHY = False

    class InvalidTag(Exception):  # type: ignore[no-redef]
        pass

    class AESGCM:  # type: ignore[no-redef]
        def __init__(self, *a, **k):
            raise RuntimeError(
                "the 'cryptography' package is not installed: "
                "SSE/KMS is unavailable on this build")

from ..objectlayer import datatypes as dt
from ..obs import stages as _stages

PKG_SIZE = 64 << 10
TAG = 16
_AAD = b"minio-tpu-sse-v1"

#: package cipher wire names (META_CIPHER values)
CIPHER_AESGCM = "AES256-GCM"
CIPHER_CHACHA20 = "CHACHA20-POLY1305"
#: packages per coalesced seal/open flush (1 MiB of 64 KiB packages —
#: the PUT/GET block quantum the dispatch lane batches on)
FLUSH_PKGS = 16
#: how long the open side stays off the interpreter lock between two lane
#: calls of one block: long enough for a waiting thread to wake and take it
_GIVE_WAY_S = 5e-5

# internal metadata keys (reference: X-Minio-Internal-Server-Side-Encryption-*)
META_SCHEME = "x-minio-internal-sse-scheme"          # "C" | "S3" | "KMS"
META_SEALED = "x-minio-internal-sse-sealed-key"      # b64 sealed OEK
META_IV = "x-minio-internal-sse-iv"                  # b64 12-byte base IV
META_KEY_MD5 = "x-minio-internal-sse-c-key-md5"      # SSE-C key fingerprint
META_KMS_BLOB = "x-minio-internal-sse-kms-blob"      # S3/KMS sealed data key
META_KMS_KEY_ID = "x-minio-internal-sse-kms-key-id"  # SSE-KMS master key id
META_KMS_CONTEXT = "x-minio-internal-sse-kms-context"  # b64 JSON context
META_PLAIN_SIZE = "x-minio-internal-sse-plain-size"
META_CIPHER = "x-minio-internal-sse-cipher"  # package cipher; absent = GCM
META_MULTIPART = "x-minio-internal-sse-multipart"  # "1": a stream per part

SSE_META_KEYS = (META_SCHEME, META_SEALED, META_IV, META_KEY_MD5,
                 META_KMS_BLOB, META_KMS_KEY_ID, META_KMS_CONTEXT,
                 META_PLAIN_SIZE, META_CIPHER, META_MULTIPART)

# a part's own metadata (part sidecar, then ``parts[i].m`` of xl.meta)
PART_IV = "sse-iv"          # b64 12-byte IV of this part's package stream
PART_NUMBER = "sse-part"    # the UploadPart number its key was derived from


def default_cipher() -> str:
    """The package cipher for NEW objects: ``workloads.sse_cipher``
    (docs/sse.md). ``auto`` picks AES-GCM when the ``cryptography``
    wheel (AES-NI) is present, else the self-contained ChaCha20 lane."""
    v = "auto"
    try:
        from ..config import get_config_sys
        v = (get_config_sys().get("workloads", "sse_cipher") or
             "auto").lower()
    except Exception:  # noqa: BLE001 — registry unavailable: auto
        pass
    if v in ("aes-gcm", "aes", "aes256-gcm"):
        return CIPHER_AESGCM
    if v in ("chacha20", "chacha", "chacha20-poly1305"):
        return CIPHER_CHACHA20
    return CIPHER_AESGCM if HAVE_CRYPTOGRAPHY else CIPHER_CHACHA20


def cipher_of(meta: dict) -> str:
    """The package cipher an existing object was written with."""
    return meta.get(META_CIPHER, "") or CIPHER_AESGCM


@dataclass
class SSEInfo:
    scheme: str                    # "C", "S3" or "KMS"
    key: bytes = b""               # SSE-C: client key (never persisted)
    key_md5: str = ""
    kms_key_id: str = ""           # SSE-KMS: requested master key id
    kms_context: str = ""          # SSE-KMS: canonical JSON context


def parse_sse_headers(hdr, bucket: str, object: str) -> SSEInfo | None:
    """Validate the request's SSE headers (cmd/crypto/sse-c.go ParseHTTP).
    Returns None when the request asks for no encryption."""
    algo_c = hdr.get("x-amz-server-side-encryption-customer-algorithm", "")
    sse = hdr.get("x-amz-server-side-encryption", "")
    if algo_c:
        if algo_c != "AES256":
            raise dt.InvalidEncryptionAlgo(bucket, object)
        key_b64 = hdr.get("x-amz-server-side-encryption-customer-key", "")
        md5_b64 = hdr.get("x-amz-server-side-encryption-customer-key-md5", "")
        try:
            key = base64.b64decode(key_b64, validate=True)
        except Exception:  # noqa: BLE001
            raise dt.InvalidSSEKey(bucket, object) from None
        if len(key) != 32:
            raise dt.InvalidSSEKey(bucket, object)
        want = base64.b64encode(hashlib.md5(key).digest()).decode()
        if md5_b64 != want:
            raise dt.SSEKeyMD5Mismatch(bucket, object)
        return SSEInfo(scheme="C", key=key, key_md5=md5_b64)
    if sse:
        if sse == "AES256":
            return SSEInfo(scheme="S3")
        if sse == "aws:kms":
            key_id = hdr.get(
                "x-amz-server-side-encryption-aws-kms-key-id", "")
            ctx_b64 = hdr.get("x-amz-server-side-encryption-context", "")
            ctx = ""
            if ctx_b64:
                # cmd/crypto/sse-kms.go ParseHTTP: context is b64 JSON;
                # re-serialize with sorted keys so the stored form is
                # canonical and unseal can't fail on key-order drift.
                import json as _json
                try:
                    parsed = _json.loads(base64.b64decode(
                        ctx_b64, validate=True))
                    if not isinstance(parsed, dict):
                        raise ValueError
                    ctx = _json.dumps(parsed, sort_keys=True,
                                      separators=(",", ":"))
                except Exception:  # noqa: BLE001
                    raise dt.InvalidSSEContext(bucket, object) from None
            return SSEInfo(scheme="KMS", kms_key_id=key_id,
                           kms_context=ctx)
        raise dt.InvalidEncryptionAlgo(bucket, object)
    return None


def sse_kms_context(bucket: str, object: str, user_ctx: str) -> str:
    """The KMS context string for an SSE-KMS object: the object path plus
    the caller's canonical JSON context (cmd/crypto/sse-kms.go binds both
    into the sealed blob so a blob replayed on another object — or with a
    different context — fails to unseal)."""
    return f"{bucket}/{object}|{user_ctx}"


def _kek(scheme_key: bytes, bucket: str, object: str) -> bytes:
    """Key-encryption key bound to the object path (unseal of a blob copied
    to another path fails)."""
    return hashlib.sha256(
        b"minio-tpu-sse-kek:" + scheme_key +
        f":{bucket}/{object}".encode()).digest()


def seal_object_key(oek: bytes, scheme_key: bytes, bucket: str,
                    object: str, cipher: str = CIPHER_AESGCM) -> bytes:
    """Seal the OEK under the path-bound KEK. The envelope AEAD follows
    the object's package cipher, so a ChaCha object needs no optional
    crypto dependency anywhere on its read path."""
    nonce = secrets.token_bytes(12)
    kek = _kek(scheme_key, bucket, object)
    if cipher == CIPHER_CHACHA20:
        from . import chacha20poly1305 as ccp
        return nonce + ccp.seal_one(kek, nonce, _AAD, oek)
    return nonce + AESGCM(kek).encrypt(nonce, oek, _AAD)


def unseal_object_key(sealed: bytes, scheme_key: bytes, bucket: str,
                      object: str, cipher: str = CIPHER_AESGCM) -> bytes:
    kek = _kek(scheme_key, bucket, object)
    if cipher == CIPHER_CHACHA20:
        from . import chacha20poly1305 as ccp
        try:
            return ccp.open_one(kek, sealed[:12], _AAD, sealed[12:])
        except ccp.BadTag:
            raise dt.SSEKeyMismatch(bucket, object) from None
    try:
        return AESGCM(kek).decrypt(sealed[:12], sealed[12:], _AAD)
    except InvalidTag:
        raise dt.SSEKeyMismatch(bucket, object) from None


def enc_size(plain: int) -> int:
    if plain <= 0:
        return max(plain, 0)
    return plain + TAG * (-(-plain // PKG_SIZE))


def plain_size_of(meta: dict, fallback: int) -> int:
    try:
        return int(meta.get(META_PLAIN_SIZE, ""))
    except ValueError:
        return fallback


def _nonce(base_iv: bytes, seq: int) -> bytes:
    return base_iv[:8] + struct.pack(">I", seq)


def _aad(seq: int) -> bytes:
    return _AAD + struct.pack(">I", seq)


def _short(cipher: str) -> str:
    return "chacha20" if cipher == CIPHER_CHACHA20 else "aes-gcm"


def _timed_block(lane, op: str, seq0: int, pkgs: list) -> list:
    """``lane.seal_block`` / ``lane.open_block`` with its wall time charged
    to the armed stage collector (``sse_seal`` / ``sse_open``) and to
    minio_tpu_workloads_sse_seconds_total{cipher,op}."""
    t0 = time.monotonic()
    try:
        with _stages.stage("sse_" + op):
            return getattr(lane, op + "_block")(seq0, pkgs)
    finally:
        dt_s = time.monotonic() - t0
        try:
            from ..obs import metrics as _mx
            _mx.inc("minio_tpu_workloads_sse_seconds_total", dt_s,
                    cipher=_short(lane.name), op=op)
        except Exception:  # noqa: BLE001 — obs never breaks the path
            pass


def _workload(op: str, cipher: str, route: str, pkgs: int, nbytes: int):
    """workloads metric group feed (docs/observability.md)."""
    try:
        from ..obs import metrics as _mx
        short = _short(cipher)
        _mx.inc("minio_tpu_workloads_sse_packages_total", pkgs,
                cipher=short, route=route)
        _mx.inc("minio_tpu_workloads_sse_bytes_total", nbytes,
                cipher=short, op=op)
    except Exception:  # noqa: BLE001 — obs never breaks the path
        pass


class _GCMPackages:
    """AES-256-GCM package lane — the CPU-native scheme (AES-NI via the
    ``cryptography`` wheel); seal/open loop per package on the host."""

    name = CIPHER_AESGCM

    def __init__(self, oek: bytes, base_iv: bytes):
        self._aead = AESGCM(oek)
        self.base_iv = base_iv

    def seal_block(self, seq0: int, pkgs: list) -> list:
        out = []
        total = 0
        for i, pkg in enumerate(pkgs):
            total += len(pkg)
            out.append(self._aead.encrypt(
                _nonce(self.base_iv, seq0 + i), pkg, _aad(seq0 + i)))
        _workload("seal", self.name, "cpu", len(pkgs), total)
        return out

    def open_block(self, seq0: int, cts: list) -> list:
        out = []
        total = 0
        for i, ct in enumerate(cts):
            total += len(ct)
            try:
                out.append(self._aead.decrypt(
                    _nonce(self.base_iv, seq0 + i), ct, _aad(seq0 + i)))
            except InvalidTag:
                raise _TagError from None
        _workload("open", self.name, "cpu", len(cts), total)
        return out


class _TagError(Exception):
    """Internal: package AEAD verification failed (mapped to
    dt.SSEDecryptError by the stream wrappers, which know bucket/key)."""


def _sse_device_route() -> bool:
    """Whether ChaCha package crypto rides the dispatch plane
    (``workloads.sse_device``, docs/sse.md): QoS-routed device flushes
    with CPU salvage; off = the numpy host lane, same bytes. ``auto``
    engages only on a real TPU backend — interpret-mode Pallas on a CPU
    host is minutes per 1 MiB flush while the numpy lane is
    bit-identical; ``1``/``dispatch`` forces the lane (tests, bench)."""
    v = "auto"
    try:
        from ..config import get_config_sys
        v = (get_config_sys().get("workloads", "sse_device") or
             "auto").lower()
    except Exception:  # noqa: BLE001
        pass
    if v in ("0", "off", "false"):
        return False
    from ..runtime import dispatch as _dsp
    if not _dsp.dispatch_enabled():
        return False
    if v in ("1", "on", "dispatch", "force"):
        return True
    from ..ops.chacha_pallas import on_tpu
    return on_tpu()


class _ChaChaPackages:
    """ChaCha20-Poly1305 package lane. Full packages of a block are
    keystream-XORed in ONE coalesced flush (dispatch op ``sse_xor`` —
    device kernel or bit-identical numpy salvage), Poly1305 tags ride
    the batched numpy limb path; the short tail package (and the
    envelope) use the scalar reference."""

    name = CIPHER_CHACHA20

    def __init__(self, oek: bytes, base_iv: bytes):
        self._oek = oek
        self.base_iv = base_iv

    def _nonces(self, seq0: int, n: int) -> np.ndarray:
        from .chacha20poly1305 import nonce_words
        return np.stack([nonce_words(_nonce(self.base_iv, seq0 + i))
                         for i in range(n)])

    def _xor_full(self, seq0: int, data: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray, str]:
        """(xored u8 [P, L], poly_keys u8 [P, 32], route) for full
        64-multiple packages."""
        nonces = self._nonces(seq0, data.shape[0])
        if _sse_device_route():
            from ..runtime import dispatch as _dsp
            ct_w, pk_w = _dsp.global_queue().sse_xor(
                np.ascontiguousarray(data).view("<u4"), self._oek,
                nonces).result()
            return (np.ascontiguousarray(ct_w).view(np.uint8),
                    np.ascontiguousarray(pk_w).view(np.uint8), "dispatch")
        from .chacha20poly1305 import keystream_xor
        out, pk = keystream_xor(self._oek, nonces,
                                np.ascontiguousarray(data))
        return out, pk, "host"

    def seal_block(self, seq0: int, pkgs: list) -> list:
        from . import chacha20poly1305 as ccp
        nfull = 0
        while nfull < len(pkgs) and len(pkgs[nfull]) == PKG_SIZE:
            nfull += 1
        out: list = []
        if nfull:
            data = np.stack([np.frombuffer(p, np.uint8) for p in
                             pkgs[:nfull]])
            ct, pk, route = self._xor_full(seq0, data)
            aads = [_aad(seq0 + i) for i in range(nfull)]
            tags = ccp.poly1305_tags(pk, ccp.mac_datas(aads, ct))
            sealed = np.empty((nfull, PKG_SIZE + TAG), np.uint8)
            sealed[:, :PKG_SIZE] = ct
            sealed[:, PKG_SIZE:] = tags
            out.extend(memoryview(sealed[i]) for i in range(nfull))
            _workload("seal", self.name, route, nfull, nfull * PKG_SIZE)
        for i in range(nfull, len(pkgs)):
            out.append(ccp.seal_one(self._oek,
                                    _nonce(self.base_iv, seq0 + i),
                                    _aad(seq0 + i), bytes(pkgs[i])))
            _workload("seal", self.name, "scalar", 1, len(pkgs[i]))
        return out

    def open_block(self, seq0: int, cts: list) -> list:
        from . import chacha20poly1305 as ccp
        nfull = 0
        while nfull < len(cts) and len(cts[nfull]) == PKG_SIZE + TAG:
            nfull += 1
        out: list = []
        if nfull:
            sealed = np.stack([np.frombuffer(c, np.uint8)
                               for c in cts[:nfull]])
            ct = np.ascontiguousarray(sealed[:, :PKG_SIZE])
            plain, pk, route = self._xor_full(seq0, ct)
            aads = [_aad(seq0 + i) for i in range(nfull)]
            tags = ccp.poly1305_tags(pk, ccp.mac_datas(aads, ct))
            # verify-before-release: nothing is emitted unless EVERY
            # package of the flush authenticates. Constant-time compare
            # over the whole tag block — same rule the scalar path's
            # _ct_eq applies (no early-exit timing oracle on tag bytes)
            import hmac
            want = np.ascontiguousarray(sealed[:, PKG_SIZE:])
            if not hmac.compare_digest(tags.tobytes(), want.tobytes()):
                raise _TagError
            out.extend(memoryview(plain[i]) for i in range(nfull))
            _workload("open", self.name, route, nfull,
                      nfull * (PKG_SIZE + TAG))
        for i in range(nfull, len(cts)):
            try:
                out.append(ccp.open_one(
                    self._oek, _nonce(self.base_iv, seq0 + i),
                    _aad(seq0 + i), bytes(cts[i])))
            except ccp.BadTag:
                raise _TagError from None
            _workload("open", self.name, "scalar", 1, len(cts[i]))
        return out


def package_cipher(cipher: str, oek: bytes, base_iv: bytes):
    """The package AEAD lane for a cipher wire name (META_CIPHER)."""
    if cipher == CIPHER_CHACHA20:
        return _ChaChaPackages(oek, base_iv)
    if cipher == CIPHER_AESGCM:
        return _GCMPackages(oek, base_iv)
    raise ValueError(f"unknown SSE package cipher {cipher!r}")


class EncryptReader:
    """Wraps a plaintext stream (typically the HashReader that enforces
    Content-MD5) and yields the encrypted package stream. Collects up to
    FLUSH_PKGS packages of plaintext and seals them through the package
    cipher's ONE coalesced flush (the ChaCha lane rides the dispatch
    plane); supports ``readinto`` so SSE PUT bodies land in pooled block
    buffers like plaintext ones (zero-copy ingest, GL010-registered)."""

    def __init__(self, stream, oek: bytes, base_iv: bytes,
                 cipher: str = CIPHER_AESGCM):
        self.stream = stream
        self.base_iv = base_iv
        self.cipher = package_cipher(cipher, oek, base_iv)
        self._seq = 0
        self._chunks: list = []   # sealed buffers, consume-from-front
        self._pos = 0             # read offset into _chunks[0]
        self._avail = 0
        self._eof = False

    def _fill(self):
        while not self._eof and self._avail < (1 << 20):
            # a flush's worth in ONE read, cut into packages where it
            # lies: a read a package made a 16 MiB part 256 turns at the
            # interpreter lock for its socket reads alone, and a request
            # that works in many short turns waits its turn behind every
            # reader that opens a block in one hold (PERF.md section 6,
            # PR 27: part PUT p95 1.1 -> 2.3 s)
            flush = memoryview(_read_full(self.stream,
                                          FLUSH_PKGS * PKG_SIZE))
            if len(flush) < FLUSH_PKGS * PKG_SIZE:
                self._eof = True
            if not len(flush):
                break
            pkgs = [flush[at:at + PKG_SIZE]
                    for at in range(0, len(flush), PKG_SIZE)]
            for sealed in _timed_block(self.cipher, "seal", self._seq,
                                       pkgs):
                self._chunks.append(memoryview(sealed))
                self._avail += len(sealed)
            self._seq += len(pkgs)

    def readinto(self, buf) -> int:
        mv = memoryview(buf).cast("B")
        done = 0
        while done < len(mv):
            if not self._chunks:
                self._fill()
                if not self._chunks:
                    break
            head = self._chunks[0]
            take = min(len(mv) - done, len(head) - self._pos)
            mv[done:done + take] = head[self._pos:self._pos + take]
            done += take
            self._pos += take
            self._avail -= take
            if self._pos == len(head):
                self._chunks.pop(0)
                self._pos = 0
        return done

    def read(self, n: int = -1) -> bytes:
        if n < 0:
            out = bytearray()
            while True:
                b = self.read(1 << 20)
                if not b:
                    return bytes(out)
                out += b
        self._fill()
        n = min(n, self._avail)
        out = bytearray(n)
        got = self.readinto(out)
        return bytes(out[:got])


class _Staging:
    """The plaintext of one ``write`` call on its way to the sink: ONE
    buffer a request, as large as the largest block it was handed, sent as
    one view of itself. The sink must be done with that view when its
    ``write`` returns (sockets, BytesIO and the zip/chunked writers are):
    the next block's plaintext overwrites it."""

    def __init__(self, writer):
        self.writer = writer
        self._mv = memoryview(bytearray())
        self._n = 0

    def room(self, n: int):
        """Make room for ``n`` more bytes: a larger buffer REPLACES the
        old one (never a resize: a view of it may still be alive in a
        frame the profiler pinned)."""
        if self._n + n > len(self._mv):
            grown = memoryview(bytearray(self._n + n))
            grown[:self._n] = self._mv[:self._n]
            self._mv = grown

    def put(self, plain):
        n = self._n + len(plain)
        self._mv[self._n:n] = plain
        self._n = n

    def drop(self):
        self._n = 0

    def release(self):
        """Everything staged goes out in one sink write."""
        if not self._n:
            return
        n, self._n = self._n, 0
        self.writer.write(self._mv[:n])
        try:
            from ..obs import metrics as _mx
            _mx.inc("minio_tpu_workloads_sse_sink_writes_total", 1,
                    op="open")
        except Exception:  # noqa: BLE001 — obs never breaks the path
            pass


class DecryptWriter:
    """Writer wrapper decrypting a package-aligned ciphertext stream and
    emitting the plaintext sub-range [skip, skip+limit) of it (ranged GETs
    read whole covering packages; the trim happens here).

    It works on the block a ``write`` hands it, where the block lies: the
    full packages are opened as slices of the incoming view, FLUSH_PKGS to
    a lane call, and only the package that straddles two calls (under
    PKG_SIZE + TAG bytes) is copied, into ``_carry``. No view of the
    block outlives the call: ``erasure_decode`` recycles its pooled buffer
    right after. The plaintexts are gathered in the staging buffer and go
    to the sink in ONE write, and only once EVERY package of the block has
    verified; a bad tag raises before a byte of the block is sent."""

    def __init__(self, writer, oek: bytes, base_iv: bytes, seq0: int,
                 skip: int, limit: int, bucket: str = "", object: str = "",
                 cipher: str = CIPHER_AESGCM,
                 staging: _Staging | None = None):
        self.writer = writer
        self.base_iv = base_iv
        self.cipher = package_cipher(cipher, oek, base_iv)
        self._seq = seq0
        self._skip = skip
        self._left = limit
        self._carry = bytearray()
        self._bo = (bucket, object)
        self._staging = staging or _Staging(writer)

    def feed(self, b):
        """Open the packages ``b`` completes into the staging buffer;
        nothing is sent (``write`` = ``feed`` + release)."""
        mv = memoryview(b).cast("B")
        unit = PKG_SIZE + TAG
        # the most a write of this size can complete, so that writes of
        # one size find the buffer they need in place
        self._staging.room((len(mv) // unit + 1) * PKG_SIZE)
        cts = []
        if self._carry:
            take = min(unit - len(self._carry), len(mv))
            self._carry += mv[:take]
            mv = mv[take:]
            if len(self._carry) < unit:
                return
            # whole now: opened from where it is, and REPLACED, never
            # cleared (an exported bytearray cannot be resized)
            cts.append(self._carry)
            self._carry = bytearray()
        whole = len(mv) - len(mv) % unit
        cts.extend(mv[at:at + unit] for at in range(0, whole, unit))
        if whole < len(mv):
            self._carry = bytearray(mv[whole:])
        self._open(cts)

    def _open(self, cts: list):
        for at in range(0, len(cts), FLUSH_PKGS):
            group = cts[at:at + FLUSH_PKGS]
            try:
                plains = _timed_block(self.cipher, "open", self._seq, group)
            except _TagError:
                self._staging.drop()
                raise dt.SSEDecryptError(*self._bo) from None
            self._seq += len(group)
            for plain in plains:
                plain = memoryview(plain).cast("B")
                if self._skip:
                    drop = min(self._skip, len(plain))
                    plain = plain[drop:]
                    self._skip -= drop
                if self._left >= 0:
                    plain = plain[:self._left]
                    self._left -= len(plain)
                if len(plain):
                    self._staging.put(plain)
            if at + FLUSH_PKGS < len(cts):
                # between two lane calls the interpreter lock is given
                # away: AES-GCM holds it through a call, and a block
                # opened in one hold makes every other request thread's
                # turn that much longer (a sleep of 0 does not hand it
                # over: the sleeper has it back before a waiter wakes)
                time.sleep(_GIVE_WAY_S)

    def drain(self):
        """Open the stream's last, short package (nothing is sent)."""
        if self._carry:
            last, self._carry = self._carry, bytearray()
            self._staging.room(len(last))
            self._open([last])

    def write(self, b):
        self.feed(b)
        self._staging.release()

    def close(self):
        self.finish()
        if hasattr(self.writer, "close"):
            self.writer.close()

    def finish(self):
        """Flush the trailing package without closing the sink."""
        self.drain()
        self._staging.release()


def decrypt_range_bounds(offset: int, length: int, plain_size: int
                         ) -> tuple[int, int, int, int]:
    """For a plaintext range [offset, offset+length): the ciphertext span
    to read (enc_off, enc_len), the first package seq, and the in-package
    skip. length < 0 means to-end."""
    if length < 0:
        length = plain_size - offset
    end = min(offset + length, plain_size)
    if offset >= plain_size or end <= offset:
        return 0, 0, 0, 0
    pkg0 = offset // PKG_SIZE
    pkg1 = (end - 1) // PKG_SIZE
    enc_off = pkg0 * (PKG_SIZE + TAG)
    enc_end = min((pkg1 + 1) * (PKG_SIZE + TAG), enc_size(plain_size))
    return enc_off, enc_end - enc_off, pkg0, offset - pkg0 * PKG_SIZE


def derive_part_key(oek: bytes, part_number: int) -> bytes:
    """The key of part ``part_number`` of a multipart object (reference
    cmd/crypto/key.go ObjectKey.DerivePartKey: HMAC-SHA256 of the
    little-endian part number under the object key)."""
    return hmac.new(oek, struct.pack("<I", part_number),
                    hashlib.sha256).digest()


@dataclass(frozen=True)
class PartStream:
    """One package stream of an object: a single-PUT object is one such
    stream under (OEK, base IV), a multipart object one per part."""
    key: bytes
    iv: bytes
    plain: int


@dataclass(frozen=True)
class Segment:
    """What one ``DecryptWriter`` of a ranged read does: ``stored`` bytes
    of ciphertext under (key, iv) from package ``seq0``, of whose plaintext
    ``skip`` bytes are dropped and ``limit`` released."""
    key: bytes
    iv: bytes
    seq0: int
    skip: int
    limit: int
    stored: int


@dataclass
class SSERead:
    """An encrypted object as one request may read it (s3api
    ``_sse_read_ctx``): the unsealed OEK never leaves this record."""
    streams: tuple
    plain_size: int
    resp: dict
    cipher: str


def part_streams(oek: bytes, parts, bucket: str = "", object: str = ""
                 ) -> tuple:
    """The package streams of a multipart-encrypted object from its
    ``xl.meta`` parts (``actual_size`` = plaintext size, ``meta`` = the IV
    and the part number the key derives from)."""
    out = []
    for p in parts:
        try:
            iv = base64.b64decode(p.meta[PART_IV], validate=True)
            number = int(p.meta[PART_NUMBER])
        except (KeyError, ValueError):
            raise dt.SSEDecryptError(bucket, object) from None
        if len(iv) != 12:
            raise dt.SSEDecryptError(bucket, object)
        out.append(PartStream(derive_part_key(oek, number), iv,
                              p.actual_size))
    return tuple(out)


def plan_range(streams, offset: int, length: int
               ) -> tuple[int, int, list[Segment]]:
    """For the plaintext range [offset, offset+length) of an object made
    of ``streams``: the ONE stored span to read (enc_off, enc_len) and the
    segments it is cut into, one per stream touched. A stream that is not
    the range's last is read to its end and the next from its package 0,
    so the span is contiguous. length < 0 means to-end."""
    total = sum(s.plain for s in streams)
    end = total if length < 0 else min(offset + length, total)
    segs: list[Segment] = []
    enc_off = enc_end = 0
    p0 = e0 = 0     # where this stream starts: plaintext, stored
    for s in streams:
        lo, hi = max(offset, p0), min(end, p0 + s.plain)
        if lo < hi:
            off, ln, seq0, skip = decrypt_range_bounds(lo - p0, hi - lo,
                                                       s.plain)
            if not segs:
                enc_off = e0 + off
            enc_end = e0 + off + ln
            segs.append(Segment(s.key, s.iv, seq0, skip, hi - lo, ln))
        p0 += s.plain
        e0 += enc_size(s.plain)
    return enc_off, enc_end - enc_off, segs


class RangeDecryptWriter:
    """Writer over the stored span ``plan_range`` names: cuts it at the
    segment boundaries and opens each piece with a ``DecryptWriter`` under
    that segment's key, IV and first sequence number. The staging buffer
    is this writer's, one for all its segments: what a ``write`` call
    opens, on either side of a part's edge, goes to the sink in one write
    once all of it has verified."""

    def __init__(self, writer, segments, cipher: str, bucket: str = "",
                 object: str = ""):
        self.writer = writer
        self._segs = iter(segments)
        self._cipher = cipher
        self._bo = (bucket, object)
        self._dw: DecryptWriter | None = None
        self._left = 0
        self._staging = _Staging(writer)

    def write(self, b):
        mv = memoryview(b).cast("B")
        while len(mv):
            if self._dw is None:
                seg = next(self._segs, None)
                if seg is None:     # more stored bytes than were planned
                    self._staging.drop()
                    raise dt.SSEDecryptError(*self._bo)
                self._dw = DecryptWriter(
                    self.writer, seg.key, seg.iv, seg.seq0, seg.skip,
                    seg.limit, *self._bo, cipher=self._cipher,
                    staging=self._staging)
                self._left = seg.stored
            take = min(len(mv), self._left)
            self._dw.feed(mv[:take])
            mv = mv[take:]
            self._left -= take
            if self._left == 0:
                self._dw.drain()
                self._dw = None
        self._staging.release()

    def finish(self):
        """Flush the trailing package without closing the sink."""
        if self._dw is not None:
            self._dw.drain()
            self._dw = None
        self._staging.release()


def _read_full(stream, n: int) -> bytes:
    chunks = []
    got = 0
    while got < n:
        b = stream.read(n - got)
        if not b:
            break
        chunks.append(b)
        got += len(b)
    return b"".join(chunks)
