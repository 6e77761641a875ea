"""Device math for the erasure hot path: GF(256) tables/matrices (numpy, host)
and bit-sliced Reed-Solomon encode/reconstruct/verify (JAX + Pallas, device).

Importing this package is where the program first pulls in JAX for device
work (every jitted kernel lives below it), so the persistent compile cache
is placed here, once — see :func:`configure_compile_cache`."""
import os

#: kernels that took at least this long to compile are kept on disk (JAX's
#: default of 1 s would drop most of the served path's 1-6 s programs'
#: smaller siblings; sub-100 ms jnp fragments are not worth a file each)
COMPILE_CACHE_MIN_COMPILE_S = 0.25


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — leave it
    alone and set no other directory. Unset: ``<checkout>/.jax_cache``, a
    fixed git-ignored path (the path is part of the cache key's
    environment: a directory built from a temp name, pid or time never
    hits)."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      COMPILE_CACHE_MIN_COMPILE_S)
    return env or jax.config.jax_compilation_cache_dir


COMPILE_CACHE_DIR = configure_compile_cache()
