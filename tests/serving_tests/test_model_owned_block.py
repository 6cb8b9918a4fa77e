"""The engine asks the model for its block (ISSUE 27): moving GPT-2's
block out of ``serving/engine.py`` into ``TransformerLM.serve_*`` did not
change one letter of what the engine compiles for it.  The hashes below
are of the lowered text of the three jitted programs at these shapes; a
change that moves one of them moves ``gpt2m-serve-chat``'s programs too,
and has to say so.  They were re-pinned once (PR 43: the pools became
``[L, P, S, H · D]``, written and read in place at a layer), on the tree
of that PR by the same calls.  The lowered text is JAX's, so the
constants hold for the JAX they were made with.

What that PR changed is held here too: no program takes a layer's slab
out of a pool or puts one back, and the served tokens are the ones the
``[H, D]`` pools gave.
"""

import hashlib
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import Request, ServingEngine

PINNED = {
    ("float32", "_prefill"): "3a3b88a2bc29815a",
    ("float32", "_prefix_prefill"): "8edbea865d65105f",
    ("float32", "_decode"): "e818684fdfdef85a",
    ("bfloat16", "_prefill"): "8a5b00870f4ac227",
    ("bfloat16", "_prefix_prefill"): "db9a2201b9a8d560",
    ("bfloat16", "_decode"): "dc72279fd475a76e",
}


def _call(engine, program):
    """``(the engine's jitted program, its operands)`` at the shapes the
    hashes were made at."""
    k, v = engine.kv.k_pool, engine.kv.v_pool
    N = engine.n_block_entries
    row = jnp.zeros(N, jnp.int32)
    lanes = (jnp.full(4, -1, jnp.int32), jnp.zeros((4, N), jnp.int32))
    operands = {
        "_prefill": (jnp.zeros((1, 32), jnp.int32), np.int32(0), row),
        "_prefix_prefill": (jnp.zeros((1, 16), jnp.int32), np.int32(0),
                            np.int32(0), row),
        "_decode": (jnp.zeros(4, jnp.int32),) + lanes,
        "_spec_verify": (jnp.zeros((4, engine.spec_k + 1), jnp.int32),
                         lanes[0], jnp.zeros(4, jnp.int32), lanes[1]),
    }[program]
    return getattr(engine, program + "_fn"), \
        (engine.state, k, v) + operands


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the hashes were made with JAX 0.9.0")
@pytest.mark.parametrize("dtype,program", list(PINNED))
def test_gpt2_lowered_program_is_the_pinned_one(dtype, program):
    model = TransformerLM(
        n_vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=64, seed=0,
        compute_dtype=None if dtype == "float32" else jnp.bfloat16)
    engine = ServingEngine(model, num_pages=32, page_size=8, max_batch=4,
                           max_context=64)
    fn, operands = _call(engine, program)
    text = fn.lower(*operands).as_text()
    assert f"@jit_{program}" in text     # the name the trace is read by
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PINNED[dtype, program]


def test_gpt2_declares_k_and_v_and_keeps_float32_parameters():
    model = TransformerLM(n_vocab=97, d_model=32, n_heads=4, n_layers=2,
                          max_len=64, seed=0, compute_dtype=jnp.bfloat16)
    engine = ServingEngine(model, num_pages=32, page_size=8, max_batch=4,
                           max_context=64)
    # a token's heads side by side: the pool's minor axis is the row
    assert model.serve_cache_entry() == ((32,), (32,))
    assert [p.shape for p in engine.kv.pools] == [(2, 32, 8, 32)] * 2
    assert engine.kv.page_bytes == 2 * 8 * 4 * 8 * 2
    assert engine.kv.pools[0].dtype == jnp.bfloat16
    assert all(a.dtype == jnp.float32
               for a in engine.state["params"].values())
    with pytest.raises(ValueError, match="max_len=64"):
        ServingEngine(model, max_context=65)


@pytest.mark.parametrize("program", ["_prefill", "_prefix_prefill",
                                     "_decode", "_spec_verify"])
def test_no_program_moves_a_layer_slab_or_a_pool(program):
    """Compiled (for the CPU): a write is the scatter into the whole
    pool and a read the gather from it.  Nothing takes a layer's ``[P,
    S, H · D]`` out (``slice``), puts one back (``dynamic-update-slice``)
    or copies one; on the chip the slab rewrites were 13.3 ms of a 53 ms
    decode step (PERF.md section 6, PR 43).  64 pages, so that a slab is
    larger than the context a batch gathers."""
    model = TransformerLM(n_vocab=97, d_model=32, n_heads=4, n_layers=2,
                          max_len=64, seed=0)
    engine = ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                           max_context=64, page_dtype=jnp.float32,
                           spec_k=2)
    # the pools donated, as the engine donates them on a chip: without,
    # a program first copies each pool it will return
    fn, operands = _call(engine, program)
    text = jax.jit(fn.__wrapped__, donate_argnums=(1, 2)).lower(
        *operands).compile().as_text()
    slab = math.prod(engine.kv.pools[0].shape[1:])
    moved = [
        (op, shape) for shape, op in re.findall(
            r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", text)
        if op in ("slice", "dynamic-slice", "dynamic-update-slice", "copy")
        and math.prod(map(int, shape.split(","))) >= slab]
    assert not moved
    assert " scatter(" in text


SERVED = [[43, 96, 27, 95, 71, 27, 43, 37],
          [12, 26, 75, 20, 29, 33, 90, 31],
          [65, 95, 37, 96, 52, 75, 27, 34]]


@pytest.mark.parametrize("asked", [{}, {"spec_k": 2}, {"tp": 2}],
                         ids=["plain", "spec_k", "tp"])
def test_served_tokens_are_those_of_the_head_split_pools(asked):
    """Greedy, float32, three requests behind a shared 16-token prefix
    (two prefix hits): ``SERVED`` was made by these calls on the parent
    of PR 43, whose pools were ``[L, P, S, H, D]``.  The layout of the
    cache changes no token, speculating or sharded over heads."""
    if asked.get("tp", 1) > len(jax.devices()):
        pytest.skip("needs 2 devices")
    model = TransformerLM(n_vocab=97, d_model=32, n_heads=4, n_layers=2,
                          max_len=64, seed=0)
    rng = np.random.RandomState(43)
    shared = rng.randint(0, 97, 16).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.randint(0, 97, n).astype(np.int32)])
               for n in (5, 7, 9)]
    engine = ServingEngine(model, num_pages=32, page_size=8, max_batch=4,
                           max_context=64, page_dtype=jnp.float32,
                           prefix_cache=True, **asked)
    for i, p in enumerate(prompts):
        engine.submit(Request(p, max_new_tokens=8, arrival_time=float(i)))
    t = 0.0
    while engine.running or engine.prefilling \
            or engine.scheduler.pending():
        engine.step(now=t)
        t += 1.0
    assert engine.prefix_hits == 2
    done = sorted(engine.completed, key=lambda r: r.request_id)
    assert [[int(x) for x in r.tokens] for r in done] == SERVED
