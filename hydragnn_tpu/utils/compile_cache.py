"""Persistent XLA compilation cache.

First compilation of the fused training programs costs tens of seconds on
TPU (the whole-training ``fit_staged`` program most of all). JAX can
persist compiled executables across processes; enabling it makes every run
after the first start hot. No reference counterpart (torch eager has no
compile step).

Where the cache lives is decided from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and this module
sets no directory at all; otherwise the cache goes to the one fixed path
:data:`DEFAULT_CACHE_DIR` inside the checkout (the directory is part of the
cache key, so a path that moves between runs never hits).
``HYDRAGNN_COMPILE_CACHE=0`` is the off switch.
"""

import importlib.util
import os

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

_enabled = False


def _accelerator_expected() -> bool:
    """Will this process compile for an accelerator? Read from config/env
    ONLY — ``jax.default_backend()`` would initialize the XLA backend here,
    and this runs before ``jax.distributed.initialize()`` in the
    multi-host driver path. With no platform pinned (the TPU host's own
    default) JAX picks the accelerator whose plugin is installed."""
    import jax

    platforms = jax.config.jax_platforms or os.getenv("JAX_PLATFORMS") or ""
    if platforms:
        return platforms.split(",")[0] != "cpu"
    return importlib.util.find_spec("libtpu") is not None


def enable_compile_cache():
    """Idempotent; call before the first jit compilation for best effect.
    Mutates process-global JAX config (cache directory, persistence
    floors). A directory that cannot be created or set is an error."""
    global _enabled
    if _enabled:
        return
    if os.getenv("HYDRAGNN_COMPILE_CACHE", "1") == "0":
        return
    import jax

    if not os.getenv("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # persist SUB-second programs on accelerator backends: a training
    # startup runs ~40 tiny compiles (put_batch layouts, metric readbacks)
    # and none clear JAX's default 1.0 s floor, so they would recur in
    # every process. CPU keeps a small floor: millisecond compiles gain
    # nothing and the cache has no eviction, so persisting them is pure
    # disk growth. HYDRAGNN_COMPILE_CACHE_MIN_SECS overrides either way.
    env_floor = os.getenv("HYDRAGNN_COMPILE_CACHE_MIN_SECS")
    if env_floor is not None:
        try:
            floor = float(env_floor)
        except ValueError:
            print(
                "HYDRAGNN_COMPILE_CACHE_MIN_SECS="
                f"{env_floor!r} is not a number; ignoring"
            )
            env_floor = None
    if env_floor is None:
        floor = 0.0 if _accelerator_expected() else 0.1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    _enabled = True
