"""Weights made on the device from ``--seed``, in one jitted call.

The benchmark makes them, not the program: the system under test has
them loaded into its links, and the plain reference gets the very same
arrays under the same path names, so neither takes anything the other
made.  A leaf's rule is ``("normal", std)``, ``("full", value)``, ``("ones",)`` or
``("zeros",)``.
"""

from __future__ import annotations

import functools


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31 (more
    than 32 signed bits hold): the low 31 bits seed, the rest fold in."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.lru_cache(maxsize=8)
def _maker(spec):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (path, shape, rule) in enumerate(spec):
            if rule[0] == "normal":
                out[path] = rule[1] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif rule[0] == "full":
                out[path] = jnp.full(shape, rule[1], jnp.float32)
            elif rule[0] == "ones":
                out[path] = jnp.ones(shape, jnp.float32)
            elif rule[0] == "zeros":
                out[path] = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError(f"unknown init rule {rule!r} for {path}")
        return out

    return jax.jit(make)


def make_params(spec, seed, sharding=None):
    """``{path: float32 array}`` for ``spec``, a tuple of
    ``(path, shape, rule)``.  ``sharding`` places the result (replicated
    over a mesh for the data-parallel cells)."""
    import jax
    key = seed_key(seed)
    if sharding is not None:
        key = jax.device_put(key, sharding)
    return _maker(tuple(spec))(key)
