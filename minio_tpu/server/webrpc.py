"""Web console plane: JSON-RPC 2.0 endpoint + upload/download routes
(reference cmd/web-handlers.go, 2,445 LoC, and cmd/web-router.go: the
browser UI's backend — Login issues a JWT, the webrpc methods mirror a
subset of the S3 surface for the console, and /minio/upload|download
move object data with the JWT as credential).

Methods (reference web.* names): Login, ServerInfo, StorageInfo,
MakeBucket, DeleteBucket, ListBuckets, ListObjects, RemoveObject,
SetAuth, CreateURLToken, PresignedGet. The JWT is HMAC-SHA256 over
header.payload (the reference signs HS512 with the credential secret;
same construction, one algorithm)."""
from __future__ import annotations

import base64
import hashlib
import hmac
import json
import time

from ..objectlayer import datatypes as dt

TOKEN_TTL_S = 24 * 3600
URL_TOKEN_TTL_S = 60


def _b64url(b: bytes) -> str:
    return base64.urlsafe_b64encode(b).rstrip(b"=").decode()


def _b64url_dec(s: str) -> bytes:
    return base64.urlsafe_b64decode(s + "=" * (-len(s) % 4))


def make_jwt(access_key: str, secret: str, ttl_s: int = TOKEN_TTL_S) -> str:
    header = _b64url(json.dumps({"alg": "HS256", "typ": "JWT"}).encode())
    claims = _b64url(json.dumps({
        "sub": access_key, "iss": "web",
        "exp": int(time.time()) + ttl_s}).encode())
    msg = f"{header}.{claims}".encode()
    sig = _b64url(hmac.new(secret.encode(), msg, hashlib.sha256).digest())
    return f"{header}.{claims}.{sig}"


def check_jwt(token: str, lookup_secret) -> str:
    """Validate signature + expiry; returns the access key or ''."""
    try:
        header, claims, sig = token.split(".")
        payload = json.loads(_b64url_dec(claims))
        ak = payload.get("sub", "")
        secret = lookup_secret(ak)
        if not secret:
            return ""
        msg = f"{header}.{claims}".encode()
        want = _b64url(hmac.new(secret.encode(), msg,
                                hashlib.sha256).digest())
        if not hmac.compare_digest(want, sig):
            return ""
        if payload.get("exp", 0) < time.time():
            return ""
        return ak
    except (ValueError, AttributeError):
        return ""


def _auth(h, params: dict) -> str:
    """JWT from the Authorization header or rpc params; returns access
    key or '' (reference isAuthTokenValid)."""
    token = ""
    auth = h.hdr.get("authorization", "")
    if auth.startswith("Bearer "):
        token = auth[7:]
    token = params.get("token", token)
    return check_jwt(token, h.s3.lookup_secret)


def _check(h, ak: str, action: str, bucket: str = "", obj: str = ""):
    """Run the same policy gate the S3 path uses: a scoped IAM user's
    JWT must not grant more through the console than through S3
    (reference web-handlers.go checks each action the same way)."""
    gate = getattr(h.s3, "authorize", None)
    if gate is None:
        return  # single-credential server: any valid JWT is root
    if not gate(ak, action, bucket, obj):
        raise dt.AccessDenied(bucket, obj, extra=f"not allowed {action}")


def handle_webrpc(h) -> None:
    """POST /minio/webrpc — JSON-RPC 2.0 (one call per request, like the
    reference's gorilla/rpc v2 JSON codec)."""
    if h.command != "POST":
        return h._error("MethodNotAllowed", "webrpc is POST-only", 405)
    try:
        req = json.loads(h._read_body() or b"{}")
        if not isinstance(req, dict):
            raise ValueError("request must be a JSON object")
        method = req.get("method", "")
        params = req.get("params") or {}
        if isinstance(params, list):
            params = params[0] if params else {}
        if not isinstance(params, dict):
            raise ValueError("params must be an object")
        rpc_id = req.get("id", 1)
    except ValueError as e:
        return _reply(h, 1, error=f"parse error: {e}")
    name = method.split(".", 1)[-1].lower()
    fn = _METHODS.get(name)
    if fn is None:
        return _reply(h, rpc_id, error=f"unknown method {method}")
    ak = ""
    if name not in _NO_AUTH:
        ak = _auth(h, params)
        if not ak:
            return _reply(h, rpc_id, error="authentication failed",
                          code=401)
    try:
        return _reply(h, rpc_id, result=fn(h, params, ak))
    except dt.ObjectAPIError as e:
        return _reply(h, rpc_id, error=str(e))
    except Exception as e:  # noqa: BLE001
        return _reply(h, rpc_id, error=f"internal error: {e}")


def _reply(h, rpc_id, result=None, error=None, code: int = 200):
    body: dict = {"jsonrpc": "2.0", "id": rpc_id}
    if error is not None:
        body["error"] = {"message": error}
    else:
        body["result"] = result
    h._send(code, json.dumps(body).encode(), "application/json")


# -- methods ------------------------------------------------------------------


def _m_login(h, p: dict, ak: str):
    user = p.get("username", "")
    sk = h.s3.lookup_secret(user)
    if not sk or not hmac.compare_digest(
            sk.encode(), str(p.get("password", "")).encode()):
        raise dt.AccessDenied(extra="invalid credentials")
    return {"token": make_jwt(user, sk), "uiVersion": "minio-tpu"}


def _m_server_info(h, p: dict, ak: str):
    import platform
    return {"MinioVersion": "minio-tpu/0.1",
            "MinioPlatform": platform.platform(),
            "MinioRuntime": platform.python_version(),
            "MinioRegion": h.s3.region}


def _m_storage_info(h, p: dict, ak: str):
    return h.s3.obj.storage_info()


def _m_make_bucket(h, p: dict, ak: str):
    bucket = p.get("bucketName", "")
    _check(h, ak, "s3:CreateBucket", bucket)
    # same core as the S3 path: metadata record, federation namespace
    # check + DNS registration
    h.s3.create_bucket(bucket)
    return True


def _m_delete_bucket(h, p: dict, ak: str):
    bucket = p.get("bucketName", "")
    _check(h, ak, "s3:DeleteBucket", bucket)
    h.s3.remove_bucket(bucket)
    return True


def _m_list_buckets(h, p: dict, ak: str):
    _check(h, ak, "s3:ListAllMyBuckets")
    return {"buckets": [{"name": b.name, "creationDate": b.created}
                        for b in h.s3.obj.list_buckets()]}


def _m_list_objects(h, p: dict, ak: str):
    bucket = p.get("bucketName", "")
    prefix = p.get("prefix", "")
    _check(h, ak, "s3:ListBucket", bucket)
    res = h.s3.obj.list_objects(bucket, prefix=prefix, delimiter="/",
                                max_keys=1000,
                                marker=p.get("marker", ""))
    return {"objects": [
        {"name": oi.name, "size": oi.size, "lastModified": oi.mod_time,
         "contentType": oi.content_type, "etag": oi.etag}
        for oi in res.objects],
        "prefixes": list(res.prefixes),
        "istruncated": res.is_truncated,
        "nextmarker": res.next_marker}


def _m_remove_object(h, p: dict, ak: str):
    bucket = p.get("bucketName", "")
    for obj in p.get("objects", []) or [p.get("objectName", "")]:
        if obj:
            _check(h, ak, "s3:DeleteObject", bucket, obj)
            h.s3.obj.delete_object(bucket, obj)
    return True


def _m_set_auth(h, p: dict, ak: str):
    # the reference rotates root credentials; here credentials live in
    # IAM/env, so guide the operator there instead of silently no-oping
    raise dt.NotImplemented(
        extra="use the admin IAM API to manage credentials")


def _m_create_url_token(h, p: dict, ak: str):
    """Short-lived token for download links (reference CreateURLToken)."""
    return {"token": make_jwt(ak, h.s3.lookup_secret(ak),
                              ttl_s=URL_TOKEN_TTL_S)}


_BUCKET_ARN = "arn:aws:s3:::{b}"
_OBJ_ARN = "arn:aws:s3:::{b}/{p}*"
_WRITE_OBJ_ACTIONS = ["s3:AbortMultipartUpload", "s3:DeleteObject",
                      "s3:ListMultipartUploadParts", "s3:PutObject"]


def _policy_doc(h, bucket: str) -> dict:
    meta = h.s3.bucket_meta.get(bucket)
    if meta.policy_json:
        try:
            return json.loads(meta.policy_json)
        except ValueError:
            pass
    return {"Version": "2012-10-17", "Statement": []}


def _stmt_objects(stmt) -> list[str]:
    res = stmt.get("Resource", [])
    return [res] if isinstance(res, str) else list(res)


def _is_anon(stmt) -> bool:
    pr = stmt.get("Principal")
    aws = pr.get("AWS") if isinstance(pr, dict) else pr
    vals = [aws] if isinstance(aws, str) else (aws or [])
    return stmt.get("Effect") == "Allow" and "*" in vals


def _prefix_level(doc: dict, bucket: str, prefix: str) -> str:
    obj_arn = _OBJ_ARN.format(b=bucket, p=prefix)
    read = write = False
    for stmt in doc.get("Statement", []):
        if not _is_anon(stmt) or obj_arn not in _stmt_objects(stmt):
            continue
        acts = stmt.get("Action", [])
        acts = [acts] if isinstance(acts, str) else acts
        if "s3:GetObject" in acts:
            read = True
        if "s3:PutObject" in acts:
            write = True
    return {(False, False): "none", (True, False): "readonly",
            (False, True): "writeonly", (True, True): "readwrite"}[
        (read, write)]


def _m_get_bucket_policy(h, p: dict, ak: str):
    """The canned anonymous-access level at a prefix (reference
    web-handlers.go:1786 via minio-go policy.GetPolicy)."""
    bucket = p.get("bucketName", "")
    _check(h, ak, "s3:GetBucketPolicy", bucket)
    h.s3.obj.get_bucket_info(bucket)
    doc = _policy_doc(h, bucket)
    return {"policy": _prefix_level(doc, bucket, p.get("prefix", ""))}


def _m_list_all_bucket_policies(h, p: dict, ak: str):
    """Every prefix with a canned anonymous policy (reference
    web-handlers.go:1884)."""
    bucket = p.get("bucketName", "")
    _check(h, ak, "s3:GetBucketPolicy", bucket)
    h.s3.obj.get_bucket_info(bucket)
    doc = _policy_doc(h, bucket)
    head = f"arn:aws:s3:::{bucket}/"
    prefixes = set()
    for stmt in doc.get("Statement", []):
        if not _is_anon(stmt):
            continue
        for arn in _stmt_objects(stmt):
            if arn.startswith(head) and arn.endswith("*"):
                prefixes.add(arn[len(head):-1])
    return {"policies": [
        {"prefix": pre + "*",
         "policy": _prefix_level(doc, bucket, pre)}
        for pre in sorted(prefixes)]}


def _m_set_bucket_policy(h, p: dict, ak: str):
    """Set/replace the canned anonymous policy at a prefix (reference
    web-handlers.go:1973): none|readonly|writeonly|readwrite become the
    standard AWS statement shapes, which the S3 anonymous-access gate
    then enforces."""
    bucket = p.get("bucketName", "")
    prefix = p.get("prefix", "")
    level = p.get("policy", "none")
    if level not in ("none", "readonly", "writeonly", "readwrite"):
        raise dt.InvalidRequest(bucket, "", f"bad policy {level!r}")
    _check(h, ak, "s3:PutBucketPolicy", bucket)
    h.s3.obj.get_bucket_info(bucket)
    doc = _policy_doc(h, bucket)
    bucket_arn = _BUCKET_ARN.format(b=bucket)
    obj_arn = _OBJ_ARN.format(b=bucket, p=prefix)
    # strip this prefix's statements (object-level, and bucket-level
    # ListBucket entries conditioned on the prefix)
    kept = []
    for stmt in doc.get("Statement", []):
        if _is_anon(stmt):
            if _stmt_objects(stmt) == [obj_arn]:
                continue
            cond = stmt.get("Condition", {}).get(
                "StringEquals", {}).get("s3:prefix", [])
            if cond == [prefix]:
                continue
        kept.append(stmt)
    if level in ("readonly", "readwrite"):
        kept.append({"Effect": "Allow", "Principal": {"AWS": ["*"]},
                     "Action": ["s3:ListBucket"],
                     "Condition": {"StringEquals": {"s3:prefix": [prefix]}},
                     "Resource": [bucket_arn]})
        kept.append({"Effect": "Allow", "Principal": {"AWS": ["*"]},
                     "Action": ["s3:GetObject"], "Resource": [obj_arn]})
    if level in ("writeonly", "readwrite"):
        kept.append({"Effect": "Allow", "Principal": {"AWS": ["*"]},
                     "Action": list(_WRITE_OBJ_ACTIONS),
                     "Resource": [obj_arn]})
    doc["Statement"] = kept
    h.s3.bucket_meta.update(
        bucket, policy_json=json.dumps(doc).encode() if kept else b"")
    return True


def _m_get_discovery_doc(h, p: dict, ak: str):
    """OpenID discovery for console SSO (reference GetDiscoveryDoc,
    web-handlers.go:2223): the configured provider's document, or null
    when SSO is not configured. Unauthenticated by design — the login
    page needs it before any credential exists."""
    iam = h.s3.iam
    prov = iam._openid_provider() if iam is not None else None
    if prov is None or not prov.configured():
        return {"DiscoveryDoc": None}
    doc = {}
    try:
        doc = prov.discovery_doc()
    except Exception:  # noqa: BLE001 — IDP down: login page degrades
        pass
    return {"DiscoveryDoc": doc or None}


def _m_login_sts(h, p: dict, ak: str):
    """Console SSO login (reference LoginSTS, web-handlers.go:2240):
    exchange an OpenID id_token for STS temporary credentials, return a
    web JWT bound to them."""
    if h.s3.iam is None:
        raise dt.NotImplemented(extra="STS login needs IAM enabled")
    try:
        cred = h.s3.iam.assume_role_with_web_identity(
            p.get("token", ""), 3600, b"")
    except ValueError as e:
        raise dt.AccessDenied(extra=f"STS login failed: {e}") from None
    return {"token": make_jwt(cred.access_key, cred.secret_key),
            "uiVersion": "minio-tpu"}


def _m_presigned_get(h, p: dict, ak: str):
    """Presigned GET URL for the console's share dialog."""
    from .auth import presign_v4
    bucket, obj = p.get("bucket", ""), p.get("object", "")
    _check(h, ak, "s3:GetObject", bucket, obj)
    expiry = min(int(p.get("expiry", 3600) or 3600), 7 * 24 * 3600)
    scheme = "https" if getattr(h.s3, "tls", False) else "http"
    url = presign_v4(
        "GET", scheme, h.hdr.get("host", ""), f"/{bucket}/{obj}",
        ak, h.s3.lookup_secret(ak), h.s3.region, expiry)
    return {"url": url}


_METHODS = {
    "login": _m_login,
    "serverinfo": _m_server_info,
    "storageinfo": _m_storage_info,
    "makebucket": _m_make_bucket,
    "deletebucket": _m_delete_bucket,
    "listbuckets": _m_list_buckets,
    "listobjects": _m_list_objects,
    "removeobject": _m_remove_object,
    "setauth": _m_set_auth,
    "createurltoken": _m_create_url_token,
    "presignedget": _m_presigned_get,
    "getbucketpolicy": _m_get_bucket_policy,
    "listallbucketpolicies": _m_list_all_bucket_policies,
    "setbucketpolicy": _m_set_bucket_policy,
    "getdiscoverydoc": _m_get_discovery_doc,
    "loginsts": _m_login_sts,
}

#: methods callable without a JWT: Login issues tokens, LoginSTS trades
#: an IDP token for one, and the login page needs the discovery doc
#: before any credential exists (reference web-router registers these
#: the same way)
_NO_AUTH = {"login", "loginsts", "getdiscoverydoc"}


# -- static console -----------------------------------------------------------


_CONSOLE_CACHE: bytes | None = None


def handle_console(h) -> None:
    """GET /minio/ — the embedded single-file console SPA (reference
    cmd/web-router.go:1 serves the compiled browser/ React app from an
    in-binary asset FS; here the app is one static HTML file beside this
    module, no build step)."""
    global _CONSOLE_CACHE
    if h.command != "GET":
        return h._error("MethodNotAllowed", "console is GET-only", 405)
    if _CONSOLE_CACHE is None:
        import os
        path = os.path.join(os.path.dirname(__file__), "console.html")
        with open(path, "rb") as f:
            _CONSOLE_CACHE = f.read()
    h._send(200, _CONSOLE_CACHE, "text/html; charset=utf-8")


# -- upload / download routes -------------------------------------------------


def handle_upload(h, bucket: str, object: str) -> None:
    """PUT /minio/upload/<bucket>/<object> with Bearer JWT (reference
    web-handlers.go Upload; the router binds it to PUT only)."""
    if h.command != "PUT":
        return h._error("MethodNotAllowed", "upload is PUT-only", 405)
    ak = _auth(h, {})
    if not ak:
        return h._error("AccessDenied", "invalid token", 401)
    try:
        _check(h, ak, "s3:PutObject", bucket, object)
        size = int(h.hdr.get("content-length", "0") or "0")
        from ..utils.hashreader import HashReader
        # _body_stream bounds the socket read to Content-Length
        # (keep-alive sockets never EOF) and handles aws-chunked bodies
        hr = HashReader(h._body_stream(size), size)
        from ..utils.mimedb import content_type
        ct = h.hdr.get("content-type") or content_type(
            object, "application/octet-stream")
        oi = h.s3.obj.put_object(
            bucket, object, hr, size,
            dt.ObjectOptions(user_defined={"content-type": ct}))
    except dt.ObjectAPIError as e:
        return h._api_error(e)
    h._send(200, json.dumps({"etag": oi.etag}).encode(),
            "application/json")


def _disposition_name(object: str) -> str:
    """Filename for Content-Disposition: the key's last segment with
    header-breaking characters stripped (CR/LF would split the response;
    a double quote would escape the parameter)."""
    name = object.rsplit("/", 1)[-1]
    return "".join(c for c in name
                   if c not in '"\\\r\n' and ord(c) >= 0x20) or "download"


def handle_download(h, bucket: str, object: str) -> None:
    """GET /minio/download/<bucket>/<object>?token=... (reference
    web-handlers.go Download: the token rides the query string because
    browser downloads can't set headers)."""
    if h.command != "GET":
        return h._error("MethodNotAllowed", "download is GET-only", 405)
    q = {k: v[0] for k, v in h.query.items()}
    ak = check_jwt(q.get("token", ""), h.s3.lookup_secret)
    if not ak:
        return h._error("AccessDenied", "invalid token", 401)
    try:
        _check(h, ak, "s3:GetObject", bucket, object)
        oi, body = h.s3.obj.get_object_n_info(bucket, object)
        # same read context as the S3 GET path: decrypt SSE-S3/KMS with
        # the unsealed OEK, inflate compressed objects (SSE-C correctly
        # errors here — a browser download can't carry the customer key)
        h.bucket, h.key = bucket, object
        sse = h._sse_read_ctx(oi)
    except dt.ObjectAPIError as e:
        return h._api_error(e)
    plain_size = _logical_size(h, oi, sse)
    h.send_response(200)
    h.send_header("Content-Type",
                  oi.content_type or "application/octet-stream")
    h.send_header("Content-Length", str(plain_size))
    h.send_header("Content-Disposition",
                  f'attachment; filename="{_disposition_name(object)}"')
    h.end_headers()
    if plain_size > 0:
        h._write_plain(body, oi, sse, h.wfile)


def _logical_size(h, oi, sse) -> int:
    from ..utils import compress as cz
    if sse:
        return sse.plain_size
    return oi.actual_size if oi.internal.get(cz.META_COMPRESSION) \
        else oi.size


def handle_download_zip(h) -> None:
    """POST /minio/zip?token=... body {bucketName, prefix, objects: []}
    — the console's multi-select download (reference web-handlers.go
    DownloadZip): entries ending in "/" expand to every object under
    them; each entry streams through the logical read context.

    Every REQUESTED entry (object or folder prefix) is authorized
    up-front — so a read-denied caller gets a proper 403 before any
    prefix walk or data read happens — then the archive STREAMS chunked
    with entries resolved and re-authorized LAZILY: folder prefixes
    expand via iter_objects while streaming and each object's
    metadata/SSE context is fetched just before its bytes go out, so a
    multi-GB selection never pre-buffers O(#objects) ObjectInfo +
    unsealed-OEK tuples (the reference checks each requested entry
    before listing and streams the same way). A mid-stream denial or
    failure cuts the connection — with chunked framing the client sees
    a truncated archive, never a silent success."""
    import zipfile
    if h.command != "POST":
        return h._error("MethodNotAllowed", "zip is POST-only", 405)
    q = {k: v[0] for k, v in h.query.items()}
    ak = check_jwt(q.get("token", ""), h.s3.lookup_secret)
    if not ak:
        return h._error("AccessDenied", "invalid token", 401)
    try:
        req = json.loads(h._read_body() or b"{}")
        bucket = req.get("bucketName", "")
        prefix = req.get("prefix", "")
        names = req.get("objects") or []
        if not isinstance(bucket, str) or not bucket or \
                not isinstance(prefix, str) or \
                not isinstance(names, list) or not names or \
                not all(isinstance(n, str) for n in names):
            raise ValueError("bucketName and string objects[] required")
    except (ValueError, AttributeError) as e:
        return h._error("InvalidRequest", f"bad zip request: {e}", 400)
    try:
        # authorize every REQUESTED entry before any walk/read: folder
        # prefixes gate on the prefix itself (a deny on bucket/prefix/*
        # matches), explicit objects on their key — nothing is listed or
        # resolved for a caller the policy rejects. Explicitly named
        # objects also get a cheap existence probe so a typo answers a
        # proper pre-stream NoSuchKey (the result is discarded: no
        # ObjectInfo/OEK buffering; folder contents stay fully lazy).
        h.s3.obj.get_bucket_info(bucket)
        for name in names:
            full = prefix + name
            _check(h, ak, "s3:GetObject", bucket, full)
            if not full.endswith("/"):
                h.s3.obj.get_object_info(bucket, full)
    except dt.ObjectAPIError as e:
        return h._api_error(e)
    h.send_response(200)
    h.send_header("Content-Type", "application/zip")
    h.send_header("Transfer-Encoding", "chunked")
    h.send_header("Content-Disposition",
                  'attachment; filename="download.zip"')
    h.end_headers()
    from .s3api import _ChunkedWriter
    out = _ChunkedWriter(h.wfile)

    def keys():
        for name in names:
            full = prefix + name
            if full.endswith("/"):
                yield from (oi.name for oi in
                            h.s3.obj.iter_objects(bucket, full))
            else:
                yield full

    try:
        # ZipFile handles the non-seekable sink via data descriptors
        with zipfile.ZipFile(out, "w", zipfile.ZIP_STORED,
                             allowZip64=True) as zf:
            for key in keys():
                # PER-OBJECT authorization, like handle_download and the
                # reference: per-key Deny statements must hold inside a
                # multi-select zip too — re-checked lazily as each entry
                # streams, with metadata/SSE resolved just-in-time
                _check(h, ak, "s3:GetObject", bucket, key)
                oi, body = h.s3.obj.get_object_n_info(bucket, key)
                h.bucket, h.key = bucket, key
                sse = h._sse_read_ctx(oi)
                arc = key[len(prefix):] if key.startswith(prefix) else key
                with zf.open(zipfile.ZipInfo(arc or key), "w",
                             force_zip64=True) as entry:
                    if _logical_size(h, oi, sse) > 0:
                        h._write_plain(body, oi, sse, entry)
    except Exception:  # noqa: BLE001 — mid-stream failure/denial: cut
        h.close_connection = True  # the connection, the client sees EOF
        return
    out.close()
