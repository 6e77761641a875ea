"""AWS Signature V4 for the benchmark's clients: header signing only, with
an unsigned payload for S3 calls and a hashed one for admin calls. Plain
standard library; imports nothing of the program."""
from __future__ import annotations

import datetime
import hashlib
import hmac
import urllib.parse

UNSIGNED_PAYLOAD = "UNSIGNED-PAYLOAD"
ALGO = "AWS4-HMAC-SHA256"


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def _enc(s: str, slash: bool = True) -> str:
    return urllib.parse.quote(s, safe="-_.~" if slash else "-_.~/")


def sign(method: str, host: str, path: str, query: dict[str, str],
         ak: str, sk: str, payload_hash: str = UNSIGNED_PAYLOAD,
         region: str = "us-east-1") -> tuple[str, dict[str, str]]:
    """(request target, headers) for one signed request."""
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    headers = {"host": host, "x-amz-date": ts,
               "x-amz-content-sha256": payload_hash}
    signed = sorted(headers)
    cq = "&".join(f"{_enc(k)}={_enc(v)}" for k, v in sorted(query.items()))
    creq = "\n".join([
        method, _enc(path, slash=False) or "/", cq,
        "".join(f"{h}:{headers[h]}\n" for h in signed),
        ";".join(signed), payload_hash])
    scope = f"{ts[:8]}/{region}/s3/aws4_request"
    sts = "\n".join([ALGO, ts, scope,
                     hashlib.sha256(creq.encode()).hexdigest()])
    key = _hmac(f"AWS4{sk}".encode(), ts[:8])
    for part in (region, "s3", "aws4_request"):
        key = _hmac(key, part)
    sig = hmac.new(key, sts.encode(), hashlib.sha256).hexdigest()
    headers["authorization"] = (
        f"{ALGO} Credential={ak}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}")
    target = _enc(path, slash=False) + (
        "?" + urllib.parse.urlencode(query) if query else "")
    return target, headers
