"""Pin `_axis_in_scope`'s dispatch (VERDICT open item 7).

The check selects between eager collectives (outside any mapped trace)
and rank-local bodies (inside a shard_map binding the communicator's
axis).  It must be an EXPLICIT axis-environment query — these tests pin
the observable behavior so a jax upgrade that changes how an unbound
``lax.axis_index`` fails cannot silently flip the mode selection.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from chainermn_tpu.communicators import create_communicator
from jax import shard_map
from chainermn_tpu.utils.compat import axis_env_contains


def test_out_of_scope_is_false():
    comm = create_communicator("jax_ici")
    assert comm._axis_in_scope() is False
    assert axis_env_contains(comm.axis_name) is False


def test_in_scope_inside_shard_map():
    comm = create_communicator("jax_ici")
    seen = []

    def body(x):
        seen.append(comm._axis_in_scope())
        return jax.lax.psum(x, comm.axis_name)

    x = jnp.arange(comm.size, dtype=jnp.float32).reshape(comm.size, 1)
    mapped = shard_map(body, mesh=comm.mesh, in_specs=P(comm.axis_name),
                       out_specs=P(comm.axis_name), check_vma=False)
    out = jax.jit(mapped)(x)
    assert seen and all(seen)
    np.testing.assert_allclose(
        np.asarray(out).ravel(), [np.arange(comm.size).sum()] * comm.size)


def test_other_axis_name_stays_out_of_scope():
    """Binding some OTHER axis must not count as this communicator's."""
    comm = create_communicator("jax_ici")
    seen = []

    def body(x):
        seen.append((axis_env_contains("not_the_axis"),
                     axis_env_contains(comm.axis_name)))
        return x

    x = jnp.zeros((comm.size, 1), jnp.float32)
    mapped = shard_map(body, mesh=comm.mesh, in_specs=P(comm.axis_name),
                       out_specs=P(comm.axis_name), check_vma=False)
    jax.jit(mapped)(x)
    assert seen and all(other is False and own is True
                        for other, own in seen)


def test_scope_check_restored_after_trace():
    """The query reads the CURRENT trace's env: once the shard_map trace
    ends, the axis is unbound again (no sticky state)."""
    comm = create_communicator("jax_ici")

    def body(x):
        return jax.lax.psum(x, comm.axis_name)

    x = jnp.ones((comm.size, 1), jnp.float32)
    comm.run_spmd(body, x)
    assert comm._axis_in_scope() is False
