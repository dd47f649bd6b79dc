"""Persistent XLA compilation cache, placed from outside the program.

Every entry point (``tnn-serve``, ``tnn-trainer``, ``tnn-train-gpt2``,
``tnn-gpt2-inference``, ``chip_smoke.py``) calls
:func:`enable` once before its first compile. Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself and this
  module sets no directory at all; a ``--compile-cache`` argument does not
  override it. The machine's owner decides.
* not set — ``cache_dir`` when the caller passed one (``tnn-serve
  --compile-cache DIR``), else ``<checkout>/.jax_cache``, derived from this
  package's own location. The path is part of the cache key, so it is never
  a temp name, a pid or a time: a directory that moves never hits.
* not set, no ``cache_dir`` and the backend is the CPU — off. Compiles there
  are cheap, this jaxlib's XLA:CPU logs a 2 KB machine-feature error on every
  cache load (even on the machine that wrote the entry), and a CPU cache
  carried to another machine is the one case where a cached executable can
  really be wrong for its host.

An engine build jit-compiles a family of step programs (prefill buckets,
mixed buckets, decode, COW copy, adopt) and under ``sp``/``tp`` each of them
lowers through ``shard_map``; a WRN-16-8 train step is one large program.
On a cold machine that is minutes of XLA work repeated identically on every
process start. The cache is content-addressed and safe to share between
processes of the same build: a changed jaxlib or flag set changes the key
and misses cleanly, never serving a stale executable. Eviction is the
operator's problem (it is a plain directory).
"""
from __future__ import annotations

import os
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the directory the cache was last enabled on (None = never enabled / disabled)
_active_dir: Optional[str] = None


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — a fixed path next to the package."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable(cache_dir: Optional[str] = None) -> Optional[str]:
    """Switch on JAX's persistent compilation cache and return its directory
    (see the module docstring for which directory wins), or None where the
    default is off.

    The two threshold overrides make the cache unconditional: by default
    JAX only persists compiles that took >1 s and produced a large
    executable, which for the engine's many small step programs would
    silently cache nothing. Idempotent.
    """
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        cache_dir = env_dir
    elif cache_dir is None and jax.default_backend() == "cpu":
        return None
    else:
        cache_dir = os.path.abspath(os.path.expanduser(
            cache_dir or default_dir()))
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # JAX initializes its cache object at most once per process, and ANY
    # compile before this call pins it to the config visible at that moment.
    # reset_cache() drops the memoized object so the next compile
    # re-initializes against the settings above.
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()
    global _active_dir
    _active_dir = cache_dir
    return cache_dir


def active_dir() -> Optional[str]:
    """The enabled cache directory, or None when the cache is off."""
    return _active_dir


def disable() -> None:
    """Switch the persistent cache back off (tests and embedders; the entry
    points never need this). Safe to call when already off."""
    jax.config.update("jax_compilation_cache_dir", None)
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()
    global _active_dir
    _active_dir = None


def entry_count(cache_dir: str) -> int:
    """Number of persisted executables under ``cache_dir``.

    Counts non-hidden directory entries (each cache entry is one file
    keyed by its content hash; JAX may add dot-prefixed bookkeeping).
    A missing or unreadable directory counts as empty rather than
    raising — callers use this for gauges and warm/cold log lines, not
    control flow.
    """
    try:
        return sum(1 for name in os.listdir(cache_dir)
                   if not name.startswith("."))
    except OSError:
        return 0


def describe(cache_dir: Optional[str]) -> str:
    """``DIR (cold)`` / ``DIR (warm, N entries)`` / ``off`` for startup
    lines; takes what :func:`enable` returned."""
    if cache_dir is None:
        return "off (cpu backend)"
    n = entry_count(cache_dir)
    return f"{cache_dir} ({'warm, %d entries' % n if n else 'cold'})"
