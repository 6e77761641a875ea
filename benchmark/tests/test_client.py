"""The client module imports neither JAX nor the program, and the plain
references it carries agree with the program's native code."""
import os
import subprocess
import sys

import numpy as np
import pytest

LIB = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib")
REPO = os.path.dirname(os.path.dirname(LIB))


def test_client_imports_neither_jax_nor_the_program():
    code = (
        "import sys; sys.path.insert(0, %r); import client, refmodel\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')"
        " or m.startswith('jaxlib') or m.startswith('minio_tpu')]\n"
        "assert not bad, bad\n") % LIB
    # from another directory, so that the repo is not on the path by chance
    p = subprocess.run([sys.executable, "-c", code], cwd="/",
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr


@pytest.fixture(scope="module")
def program():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    sys.path.insert(0, LIB)
    from minio_tpu.erasure.bitrot import HIGHWAY_KEY
    from minio_tpu.native import highwayhash
    from minio_tpu.utils.hashreader import pipeline_etag_reference
    return HIGHWAY_KEY, highwayhash, pipeline_etag_reference


@pytest.mark.parametrize("length", [1, 3, 17, 31, 32, 33, 50, 1000, 16384])
def test_numpy_highwayhash_equals_native(program, length):
    import hh_ref
    key, native, _ = program
    rows = np.random.default_rng(length).integers(0, 256, (5, length),
                                                  dtype=np.uint8)
    want = np.stack([np.frombuffer(native.hash256(key, r.tobytes()),
                                   np.uint8) for r in rows])
    assert np.array_equal(hh_ref.hh256_rows(key, rows), want)


@pytest.mark.parametrize("k,size", [(8, 10 << 20), (4, 10 << 20),
                                    (4, (3 << 20) + 12345), (8, 1000)])
def test_reference_etag_equals_the_programs(program, k, size):
    import hashlib
    import hh_ref
    key, _, pipeline_etag_reference = program
    geom = {"data": k, "block_bytes": 4 << 20, "bitrot_chunk_bytes": 16384,
            "bitrot_key_hex": key.hex(), "etag_min_bytes": 1 << 20}
    bodies = [np.random.default_rng([k, size, i]).bytes(size)
              for i in range(2)]
    want = [pipeline_etag_reference(b, k, 4 << 20, 16384, 0)
            if size >= 1 << 20 else hashlib.md5(b).hexdigest()
            for b in bodies]
    assert hh_ref.reference_etags(bodies, geom) == want
