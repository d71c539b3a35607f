"""Persistent XLA compile cache for the entry scripts.

Called by the programs a user starts (chip_smoke.py, benchmark/run.py)
before their first compile —
never on ``import deepspeed_tpu``, so a library user's own cache choice is
left alone and the tests run with the cache off.
"""
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Returns the cache directory in effect.  JAX_COMPILATION_CACHE_DIR,
    where set, is JAX's own setting and nothing is done in code; otherwise
    the cache is ``<checkout>/.jax_cache``.  The path is part of the cache
    key, so it is fixed: no temp name, pid or time in it."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def disable_persistent_compile_cache(why: str) -> bool:
    """Turns JAX's persistent compile cache off for the rest of the process
    and says so; returns whether it was on.  For a caller whose programs
    the TPU runtime cannot run once they are read back from the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from deepspeed_tpu.utils.logging import logger

    if not (jax.config.jax_enable_compilation_cache
            and jax.config.jax_compilation_cache_dir):
        return False
    logger.warning(
        f"persistent compile cache {jax.config.jax_compilation_cache_dir} "
        f"turned OFF for this process: {why}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return True
