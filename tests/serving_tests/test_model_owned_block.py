"""The engine asks the model for its block (ISSUE 27): moving GPT-2's
block out of ``serving/engine.py`` into ``TransformerLM.serve_*`` may not
change one letter of what the engine compiles for it.  The hashes below
are of the lowered text of the parent commit's (d5abeda) three jitted
programs at these shapes, made by the same calls on that tree; a change
that moves one of them moves ``gpt2m-serve-chat``'s programs too, and has
to say so.  The lowered text is JAX's, so the constants hold for the JAX
they were made with.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import ServingEngine

PARENT = {
    ("float32", "_prefill"): "fd77f5cd4ad4aa1b",
    ("float32", "_prefix_prefill"): "9a0150a993547517",
    ("float32", "_decode"): "748604d8dad8c83a",
    ("bfloat16", "_prefill"): "7096ed5187f5c962",
    ("bfloat16", "_prefix_prefill"): "a82da97872f7f128",
    ("bfloat16", "_decode"): "3fbcee48157f6a91",
}


def _lowered(engine, program):
    k, v = engine.kv.k_pool, engine.kv.v_pool
    N = engine.n_block_entries
    row = jnp.zeros(N, jnp.int32)
    if program == "_prefill":
        return engine._prefill_fn.lower(
            engine.state, k, v, jnp.zeros((1, 32), jnp.int32), np.int32(0),
            row)
    if program == "_prefix_prefill":
        return engine._prefix_prefill_fn.lower(
            engine.state, k, v, jnp.zeros((1, 16), jnp.int32), np.int32(0),
            np.int32(0), row)
    return engine._decode_fn.lower(
        engine.state, k, v, jnp.zeros(4, jnp.int32),
        jnp.full(4, -1, jnp.int32), jnp.zeros((4, N), jnp.int32))


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the parent's hashes were made with JAX 0.9.0")
@pytest.mark.parametrize("dtype,program", list(PARENT))
def test_gpt2_lowered_program_is_the_parents(dtype, program):
    model = TransformerLM(
        n_vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=64, seed=0,
        compute_dtype=None if dtype == "float32" else jnp.bfloat16)
    engine = ServingEngine(model, num_pages=32, page_size=8, max_batch=4,
                           max_context=64)
    text = _lowered(engine, program).as_text()
    assert f"@jit_{program}" in text     # the name the trace is read by
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT[dtype, program]


def test_gpt2_declares_k_and_v_and_keeps_float32_parameters():
    model = TransformerLM(n_vocab=97, d_model=32, n_heads=4, n_layers=2,
                          max_len=64, seed=0, compute_dtype=jnp.bfloat16)
    engine = ServingEngine(model, num_pages=32, page_size=8, max_batch=4,
                           max_context=64)
    assert model.serve_cache_entry() == ((4, 8), (4, 8))
    assert [p.shape for p in engine.kv.pools] == [(2, 32, 8, 4, 8)] * 2
    assert engine.kv.page_bytes == 2 * 8 * 4 * 8 * 2
    assert engine.kv.pools[0].dtype == jnp.bfloat16
    assert all(a.dtype == jnp.float32
               for a in engine.state["params"].values())
    with pytest.raises(ValueError, match="max_len=64"):
        ServingEngine(model, max_context=65)
