"""ChaCha20-Poly1305 (crypto/chacha20poly1305.py + ops/chacha_pallas.py):
RFC 8439 vectors, batched-vs-scalar Poly1305 pinning, and the device
keystream kernel pinned bit-identical to the numpy reference — the same
contract mur3/rs_pallas carry (docs/sse.md)."""
import importlib.util

import numpy as np
import pytest

from minio_tpu.crypto import chacha20poly1305 as ccp
from minio_tpu.ops import chacha_pallas as cp

RNG = np.random.default_rng(11)

HAVE_CRYPTOGRAPHY = importlib.util.find_spec("cryptography") is not None


# --------------------------------------------------------------------------
# RFC 8439 vectors


def test_rfc8439_chacha_block():
    key = bytes(range(32))
    nonce = bytes.fromhex("000000090000004a00000000")
    out = ccp.chacha20_blocks(key, ccp.nonce_words(nonce).reshape(1, 3),
                              np.array([1], np.uint32))
    want = [0xE4E7F110, 0x15593BD1, 0x1FDD0F50, 0xC47120A3,
            0xC7F4D1C7, 0x0368C033, 0x9AAA2204, 0x4E6CD4C3,
            0x466482D2, 0x09AA9F07, 0x05D7C214, 0xA2028BD9,
            0xD19C12B5, 0xB94E16DE, 0xE883D0CB, 0x4E3C50A2]
    assert out[0].tolist() == want


def test_rfc8439_poly1305():
    key = bytes.fromhex("85d6be7857556d337f4452fe42d506a8"
                        "0103808afb0db2fd4abff6af4149f51b")
    tag = ccp.poly1305_tag(key, b"Cryptographic Forum Research Group")
    assert tag == bytes.fromhex("a8061dc1305136c6c22b8baf0c0127a9")


def test_rfc8439_aead_seal_open():
    key = bytes.fromhex("808182838485868788898a8b8c8d8e8f"
                        "909192939495969798999a9b9c9d9e9f")
    nonce = bytes.fromhex("070000004041424344454647")
    aad = bytes.fromhex("50515253c0c1c2c3c4c5c6c7")
    plain = (b"Ladies and Gentlemen of the class of '99: If I could "
             b"offer you only one tip for the future, sunscreen would "
             b"be it.")
    sealed = ccp.seal_one(key, nonce, aad, plain)
    assert sealed[-16:] == bytes.fromhex("1ae10b594f09e26a7e902ecbd0600691")
    assert ccp.open_one(key, nonce, aad, sealed) == plain
    with pytest.raises(ccp.BadTag):
        ccp.open_one(key, nonce, aad, sealed[:-1] + b"\x00")
    with pytest.raises(ccp.BadTag):
        ccp.open_one(key, nonce, b"x" + aad[1:], sealed)


@pytest.mark.skipif(not HAVE_CRYPTOGRAPHY,
                    reason="cryptography wheel absent")
def test_cross_check_with_cryptography_wheel():
    from cryptography.hazmat.primitives.ciphers.aead import \
        ChaCha20Poly1305 as LibCCP
    key = RNG.integers(0, 256, 32, dtype=np.uint8).tobytes()
    nonce = RNG.integers(0, 256, 12, dtype=np.uint8).tobytes()
    aad = b"cross-check-aad"
    for n in (0, 1, 63, 64, 65, 1000):
        plain = RNG.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert ccp.seal_one(key, nonce, aad, plain) == \
            LibCCP(key).encrypt(nonce, plain, aad)


# --------------------------------------------------------------------------
# batched Poly1305 == scalar (the seal path's tag engine)


@pytest.mark.parametrize("mlen", [16, 48, 1024, 65584])
def test_poly1305_batched_equals_scalar(mlen):
    pkgs = 4
    keys = RNG.integers(0, 256, (pkgs, 32), dtype=np.uint8)
    msgs = RNG.integers(0, 256, (pkgs, mlen), dtype=np.uint8)
    got = ccp.poly1305_tags(keys, msgs)
    for p in range(pkgs):
        assert got[p].tobytes() == ccp.poly1305_tag(
            keys[p].tobytes(), msgs[p].tobytes()), (mlen, p)


def test_mac_datas_matches_scalar_mac_data():
    cts = RNG.integers(0, 256, (3, 64), dtype=np.uint8)
    aads = [b"aad-%d-0123456789abcdef" % i for i in range(3)]
    batched = ccp.mac_datas(aads, cts)
    for i in range(3):
        assert batched[i].tobytes() == ccp.mac_data(aads[i],
                                                    cts[i].tobytes())


# --------------------------------------------------------------------------
# device kernel pin (interpret mode off-TPU, like mur3_pallas)


def _pin_shapes(shapes):
    key = RNG.integers(0, 256, 32, dtype=np.uint8).tobytes()
    base = RNG.integers(0, 256, 8, dtype=np.uint8).tobytes()
    for pkgs, ln in shapes:
        data = RNG.integers(0, 256, (pkgs, ln), dtype=np.uint8)
        nonces = np.stack([
            ccp.nonce_words(base + int(s).to_bytes(4, "big"))
            for s in range(pkgs)])
        ref_ct, ref_pk = ccp.keystream_xor(key, nonces, data)
        ct_d, pk_d = cp.xor_packages_device(
            key, nonces, data.view("<u4").reshape(pkgs, ln // 4))
        assert np.array_equal(
            np.asarray(ct_d).view(np.uint8).reshape(pkgs, ln), ref_ct)
        assert np.array_equal(
            np.asarray(pk_d).astype("<u4").view(np.uint8).reshape(
                pkgs, 32), ref_pk)


def test_pallas_kernel_pinned_to_numpy_reference():
    # 64 B is shared with test_workloads' routing test: one jit cache
    # entry serves both
    _pin_shapes(((1, 64), (3, 1024)))


def test_pallas_kernel_pinned_wider_shapes():
    _pin_shapes(((2, 4096), (5, 128)))


def test_xor_roundtrip_and_seal_consistency():
    """keystream_xor is its own inverse, and batched tag material equals
    the scalar AEAD's."""
    key = RNG.integers(0, 256, 32, dtype=np.uint8).tobytes()
    data = RNG.integers(0, 256, (2, 256), dtype=np.uint8)
    nonces = np.stack([ccp.nonce_words(bytes([i] * 12)) for i in (1, 2)])
    ct, pks = ccp.keystream_xor(key, nonces, data)
    back, _ = ccp.keystream_xor(key, nonces, ct)
    assert np.array_equal(back, data)
    for i in (0, 1):
        ref = ccp.seal_one(key, bytes([i + 1] * 12), b"",
                           data[i].tobytes())
        assert ct[i].tobytes() == ref[:-16]
        assert ccp.poly1305_tag(
            pks[i].tobytes(),
            ccp.mac_data(b"", ct[i].tobytes())) == ref[-16:]
