"""One persistent XLA compilation cache for every entry point.

Each process of this system — the mining job, the API server, every
``bench.py`` phase, every ``chip_smoke.py`` leg — compiles the same
kernels (the serve warm-up grid alone is length buckets × batch buckets ×
two kernels per device). A persistent cache makes the second process, and
the second run, load executables instead of compiling them. The
directory is part of the cache key's lookup path, so it must be the SAME
path in every process and across runs:

- ``JAX_COMPILATION_CACHE_DIR`` set (a PVC mount in the k8s manifests, a
  path the machine's owner chose): JAX reads it itself at import; this
  module sets no directory of its own.
- unset: the fixed in-checkout path :data:`DEFAULT_CACHE_DIR`
  (git-ignored) — never a temp name, a pid or a time.

This module imports jax only inside :func:`enable_compilation_cache`, so
parents that must stay off the device (``bench.py``, ``chip_smoke.py``)
can use :func:`cache_dir` / :func:`child_env`.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("kmlserver_tpu.jaxcache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# The one storage threshold. JAX's default (1 s) skips exactly the many
# small serving-bucket kernels the cache exists to keep warm; any value
# above zero also makes "was it stored" depend on run-to-run compile-time
# jitter, so a warm start could still add entries.
MIN_COMPILE_TIME_S = 0.0


def cache_dir() -> str:
    """Where the cache lives: the outside placement if there is one, else
    the fixed in-checkout path."""
    return os.environ.get(ENV_VAR) or DEFAULT_CACHE_DIR


def child_env() -> dict[str, str]:
    """Env entries that make a child process (which reads them at ``import
    jax``) use this cache. Creates the directory; raises ``OSError`` if it
    cannot — a driver of children decides whether that is fatal."""
    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    return {
        ENV_VAR: path,
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": str(MIN_COMPILE_TIME_S),
    }


def enable_compilation_cache() -> str | None:
    """Turn the persistent cache on for THIS process; → its path, or None
    when the directory cannot be created (logged: a mis-mounted cache must
    not take down the job or the API — they compile cold instead).

    Call before the first jit compile. With ``JAX_COMPILATION_CACHE_DIR``
    set nothing is written to ``jax_compilation_cache_dir`` — JAX already
    read the variable; otherwise the fixed path is applied and exported so
    children inherit it."""
    import jax

    placed = bool(os.environ.get(ENV_VAR))
    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        logger.warning(
            "compilation cache %s cannot be created; compiling cold",
            path, exc_info=True,
        )
        return None
    if not placed:
        os.environ[ENV_VAR] = path
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_TIME_S
    )
    logger.info("persistent XLA compilation cache at %s", path)
    return path
