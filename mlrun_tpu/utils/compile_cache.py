"""Persistent XLA compilation cache wiring.

Every JobSet (re)start — including the monitor's preemption-resume
resubmits (docs/fault_tolerance.md) — used to pay full XLA recompilation
before step one. This module wires ``jax``'s persistent compilation
cache from ``mlconf.training.compile_cache_dir`` so a restarted slice
(or a second ``Trainer.warmup()``) loads the compiled executable from
disk instead: the service threads the dir into resubmitted JobSets via
``COMPILE_CACHE_ENV`` (service/runtime_handlers.TpuJobHandler), which is
exactly the mlconf env mapping for the same key, so the in-pod trainer
sees it through the ordinary config layer.

Thresholds are forced permissive (min compile time / entry size = 0) so
CPU-mesh tests and the tiny-model bench exercise the identical code path
as a pod-slice run.

Process entry points that run on the chip (``chip_smoke.py``, ``bench.py``
default mode, ``mlrun-tpu serve``) take the cache from outside through
:func:`configure_default`: ``JAX_COMPILATION_CACHE_DIR`` when set — jax
reads that variable itself, so nothing is set in code — otherwise the
fixed ``<checkout>/.jax_cache`` (the path is part of what a cache hit
depends on: a directory that moves never hits).
"""

from __future__ import annotations

import os
import threading

from ..common.runtimes_constants import COMPILE_CACHE_ENV  # noqa: F401
from .helpers import logger

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_configured_dir: str | None = None


def configured_dir() -> str | None:
    """The cache dir currently wired into jax.config (None = disabled)."""
    return _configured_dir


def configure(cache_dir: str) -> str | None:
    """Point jax's persistent compilation cache at ``cache_dir``.

    Idempotent; re-pointing at a different dir is allowed (tests).
    Returns the resolved absolute dir, or None when ``cache_dir`` is
    empty (cache left as-is).
    """
    global _configured_dir

    if not cache_dir:
        return None
    cache_dir = os.path.abspath(os.path.expanduser(str(cache_dir)))
    with _lock:
        if _configured_dir == cache_dir:
            return cache_dir
        import jax

        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax materializes its cache object lazily from the config and
        # keeps it — (re)pointing the dir mid-process needs an explicit
        # reset or writes keep landing in the old location
        _reset_jax_cache()
        # cache everything: the default min-compile-time/entry-size
        # thresholds would skip the tiny CPU-mesh kernels tests compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _configured_dir = cache_dir
        logger.info("persistent compile cache enabled", dir=cache_dir)
        return cache_dir


def _reset_jax_cache():
    """Drop jax's materialized cache object so the next compile re-reads
    the (updated) config."""
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def disable():
    """Turn the persistent cache back off (test isolation)."""
    global _configured_dir

    with _lock:
        if _configured_dir is None:
            return
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
        _reset_jax_cache()
        _configured_dir = None


def configure_from_mlconf() -> str | None:
    """Wire the cache from ``mlconf.training.compile_cache_dir`` (which
    the env layer maps from ``COMPILE_CACHE_ENV``). No-op when unset —
    and when ``JAX_COMPILATION_CACHE_DIR`` placed the cache from outside,
    which the config key does not override."""
    from ..config import mlconf

    return os.environ.get(CACHE_DIR_ENV) or configure(
        str(mlconf.training.get("compile_cache_dir") or ""))


def configure_default() -> str:
    """The cache of a process that runs on the chip (module docstring):
    the directory ``JAX_COMPILATION_CACHE_DIR`` names, untouched, else
    ``<checkout>/.jax_cache``. Returns the directory in use."""
    return os.environ.get(CACHE_DIR_ENV) or configure(DEFAULT_CACHE_DIR)
