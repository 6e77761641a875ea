"""Server-side encryption (SSE-C / SSE-S3) — reference cmd/crypto/ +
cmd/encryption-v1.go, redesigned small: envelope encryption with a random
per-object key (OEK) sealed by the request key (SSE-C) or a KMS data key
(SSE-S3), and an AES-256-GCM package stream (64 KiB packages, sequence
numbers bound into nonce+AAD) that supports ranged reads by package
alignment."""
from .kms import (KESClient, KMS, KMSError, KMSUnreachable, LocalKMS,
                  VaultClient,
                  get_kms, set_kms)
from .sse import (CIPHER_AESGCM, CIPHER_CHACHA20, META_CIPHER, META_SCHEME,
                  PKG_SIZE, DecryptWriter, EncryptReader,
                  RangeDecryptWriter, SSEInfo, SSERead, cipher_of,
                  decrypt_range_bounds, default_cipher, derive_part_key,
                  enc_size, package_cipher,
                  parse_sse_headers, plain_size_of, plan_range,
                  seal_object_key, sse_kms_context, unseal_object_key)

__all__ = [
    "KESClient", "KMS", "KMSError", "KMSUnreachable", "LocalKMS",
    "VaultClient",
    "get_kms", "set_kms",
    "CIPHER_AESGCM", "CIPHER_CHACHA20", "META_CIPHER",
    "META_SCHEME", "PKG_SIZE", "DecryptWriter", "EncryptReader",
    "RangeDecryptWriter", "SSEInfo", "SSERead",
    "cipher_of", "decrypt_range_bounds", "default_cipher",
    "derive_part_key", "enc_size", "package_cipher", "parse_sse_headers",
    "plain_size_of", "plan_range", "seal_object_key", "sse_kms_context",
    "unseal_object_key",
]
