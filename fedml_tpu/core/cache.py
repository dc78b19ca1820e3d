"""Persistent XLA compilation-cache placement.

One rule, applied by every entry point (``fedml_tpu.init``, ``bench.py``,
``tests/conftest.py``, ``chip_smoke.py``, the ``__graft_entry__`` dry run):

- ``JAX_COMPILATION_CACHE_DIR`` set: the cache lives where the operator put
  it.  JAX reads the variable itself, so no directory is set in code — only
  the thresholds below.
- unset: one fixed path, ``<checkout>/.jax_cache`` (git-ignored).

The path must not move: JAX hashes the cache directory into every entry's
key (checked under jax 0.9.0 — a byte-identical copy of a warm directory
under another name misses on every program), so a name that varies by host
can never be pre-warmed or carried along with the checkout.

An earlier version of this module suffixed the directory with a digest of
``/proc/cpuinfo`` because XLA:CPU entries written on another host loaded
with "could lead to SIGILL" warnings.  Under jaxlib 0.9.0 that is settled
upstream: the serialized CPU topology that goes into every cache key lists
the host's machine features (``+avx512f``, ``+amx-bf16``, ...), so an entry
from a host with a different feature set has a different key and is never
read; and the warning itself now fires on the *same* host for every hit
(it trips on the ``+prefer-no-scatter``/``+prefer-no-gather`` tuning
pseudo-features), so it no longer identifies a foreign entry.  TPU entries
key on the device topology the same way.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """Where compiled programs persist: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache`` (the parent of the ``fedml_tpu``
    package)."""
    env = os.environ.get(ENV_VAR)
    if env:
        return os.path.abspath(env)
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def setup_persistent_cache() -> str:
    """Enable the persistent compilation cache and return its directory.
    Call after any platform forcing and before the first compile;
    idempotent."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
