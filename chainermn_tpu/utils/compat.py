"""The two places where the framework leans on JAX internals or process
configuration: the axis-environment query and the persistent compile
cache's location."""

from __future__ import annotations

import os

__all__ = ["axis_env_contains", "configure_persistent_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def axis_env_contains(name):
    """True when ``name`` is bound as a mapped axis by an enclosing
    ``shard_map``/``pmap`` of the current trace — the explicit check
    behind ``Communicator._axis_in_scope`` (no traced-probe-and-catch).
    A jax upgrade that moves the query surfaces as an ImportError here,
    not as a silent flip of eager/traced mode selection."""
    from jax._src.core import get_axis_env
    return bool(get_axis_env().axis_exists(name))


def configure_persistent_cache():
    """Turn on JAX's persistent compile cache; the one place that
    decides where it lives.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no
    directory is set in code.  Unset: ``<checkout>/.jax_cache``,
    resolved from this package's location — the path is part of the
    cache key, so it must not depend on the cwd, the pid or the clock.
    Returns the directory in effect.  (Warm-cache replays of scan and
    params-donated step programs were re-run clean on jax 0.9.0's CPU
    backend, so no program kind is excluded.)
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
