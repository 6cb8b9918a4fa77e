"""Test harness configuration.

Multi-chip behavior is tested on a simulated 8-device CPU mesh
(SURVEY.md §4: the TPU analog of the reference's ``mpiexec -n N`` on one
host).

The platform is pinned to the CPU here too, so a bare ``pytest`` without
``JAX_PLATFORMS=cpu`` in the environment cannot open an accelerator;
``XLA_FLAGS`` applies because the CPU backend reads it at first
initialization (which happens after this file runs).
"""

import os

import jax

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    # tier-1 is a correctness tier on (often single-vCPU) CI: the CPU
    # backend's O2/LLVM pipeline buys nothing we assert on and costs
    # ~40% of suite wall time in compiles.  Parity tests compare runs
    # compiled under the SAME flags, so self-consistency is untouched;
    # explicitly-set XLA_FLAGS still win (later flags override).
    "--xla_backend_optimization_level=0 "
    + os.environ.get("XLA_FLAGS", ""))
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
