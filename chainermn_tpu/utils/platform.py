"""Platform selection helpers behind the examples' ``--platform`` and
``--simulate-devices`` flags.

``JAX_PLATFORMS`` in the environment does the same as
:func:`use_platform`; the flags exist so a rehearsal command is
self-contained.  Both helpers take effect only before JAX first
initializes a backend.
"""

from __future__ import annotations

import os

import jax

__all__ = ["use_platform", "simulate_devices"]


def use_platform(name: str | None):
    """Pin the JAX platform ('cpu'/'tpu'); None keeps the default."""
    if name:
        jax.config.update("jax_platforms", name)


def simulate_devices(n: int):
    """Request n simulated host devices (effective only before the CPU
    backend first initializes — call early)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} " + flags)
