"""A model whose cache has more layers than it has blocks
(``LoopedLM``: ``R`` passes over ``L`` blocks keep ``R x L`` cache layers)
behind ``ServingEngine``, against the plain reference at tiny widths in
float32 on seeded weights: the pool the engine makes; prefill then decode
through the cache, and a prefix hit's suffix prefill, equal to the
reference's full pass; and every writer and reader of the pool at a
TRACED layer (the cache layer inside a device loop) equal to its static
form at every layer."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import paged_attention as pa
from chainermn_tpu.serving import Request, kv_cache

from tests.models_tests.test_latent_moe import Recorded
from tests.models_tests.test_looped import ATOL, L, R, build

from benchmark import harness


@pytest.fixture(scope="module")
def built():
    return build() + (harness.load_module("reference", "looped"),)


def engine_kw(**over):
    return dict(dict(num_pages=48, page_size=8, max_batch=4,
                     max_context=64), **over)


def reference_rows(built, req):
    cfg, _, params, ref = built
    full = np.zeros(64, np.int32)
    n = req.prompt.size + len(req.tokens)
    full[:req.prompt.size] = req.prompt
    full[req.prompt.size:n] = req.tokens
    rows = slice(req.prompt.size - 1, n - 1)
    return np.asarray(ref.sequence_logits(cfg, params, full))[rows]


# case: (the live holder's prompt, tokens of it the probe shares): 16
# ends on a page boundary (page size 8); a holder whose prompt ends
# inside a page, at 19, shares that page too, and the probe forks it
CASES = {"full_prefill": (27, None), "suffix_prefill": (27, 16),
         "forked_page": (19, 19)}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_looped_cache_matches_the_reference(built, case):
    """Prefill, then decoding through the cache (each pass reading its
    own layers), give the reference's full-pass logits, for a whole
    prompt and for the suffix of a prefix hit alike; tolerance: float32
    against float32, the order of the sums alone (test_looped.ATOL)."""
    _, model, _, _ = built
    prompt_len, shared = CASES[case]
    rng = np.random.RandomState(5)
    rec = Recorded(model, **engine_kw())
    e = rec.engine
    assert e.kv.n_layers == R * L == R * len(model.blocks)
    assert [p.shape for p in e.kv.pools] == [(R * L, 48, 8, 128)]
    holder = Request(rng.randint(0, 128, prompt_len).astype(np.int32), 30,
                     tenant="a", request_id=1)
    e.submit(holder)
    e.step()
    probe = holder
    if shared is not None:
        probe = Request(np.concatenate(
            [holder.prompt[:shared],
             rng.randint(0, 128, 11).astype(np.int32)]), 20, tenant="a",
            request_id=2)
        e.submit(probe)
    while e.running or e.scheduler.pending():
        e.step()
        assert e.allocator.check()
    assert e.prefix_hits == (shared is not None)
    assert e.forks == (case == "forked_page")
    for req in {holder, probe}:
        got = np.stack(rec.rows[req.request_id])
        want = reference_rows(built, req)
        assert got.shape == want.shape == (len(req.tokens), 128)
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    # one compile a program and bucket: the loop adds no retrace
    assert e.decode_traces <= 3 and e.prefill_traces == 1


def test_the_spans_carry_the_loops_counts(built):
    """``serve/decode_window`` gains ``ctx_tokens``, ``passes`` and
    ``exit_expected_pass`` from the model's ``serve_span_stats``."""
    from chainermn_tpu import observability
    _, model, _, _ = built
    stats = model.serve_span_stats(np.int32(57), np.float32(1.75))
    assert stats == {"ctx_tokens": 57, "passes": R,
                     "exit_expected_pass": 1.75}
    pool = jnp.zeros((R * L, 8, 8, 128), jnp.float32)
    toks = jnp.asarray([3, 5, 0, 0], jnp.int32)
    pos = jnp.asarray([4, 9, -1, -1], jnp.int32)
    bts = jnp.zeros((1, 4, 8), jnp.int32).at[0, 0, :2].set(
        jnp.asarray([1, 2])).at[0, 1, :2].set(jnp.asarray([3, 4]))
    _, logits, (ctx_tokens, expected) = model.serve_decode(
        (pool,), toks, pos, bts)
    assert int(ctx_tokens) == 5 + 10            # the live lanes' contexts
    assert 1.0 <= float(expected) <= R
    assert logits.shape == (4, 128)
    assert "loop" not in observability.ROLES     # a scope, not a role


# -- a traced layer against the static form, every layer -------------------

P, S, E, LAYERS = 6, 4, 32, 5


def _pool(seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(LAYERS, P, S, E),
                       jnp.float32)


def _writers():
    rng = np.random.RandomState(1)
    row = jnp.asarray([2, 5, 1], jnp.int32)
    tables = jnp.asarray([[2, 5, 1], [0, 3, 4]], jnp.int32)
    return {
        "write_prompt_kv": lambda pool, layer: kv_cache.write_prompt_kv(
            pool, jnp.asarray(rng.randn(10, E), jnp.float32), row, 7,
            layer=layer),
        "write_prompt_kv_at": lambda pool, layer:
            kv_cache.write_prompt_kv_at(
                pool, jnp.asarray(rng.randn(6, E), jnp.float32), row, 5, 4,
                layer=layer),
        "write_token_kv": lambda pool, layer: kv_cache.write_token_kv(
            pool, jnp.asarray(rng.randn(2, E), jnp.float32), tables,
            jnp.asarray([9, -1], jnp.int32), layer=layer),
    }


@pytest.mark.parametrize("writer", ["write_prompt_kv", "write_prompt_kv_at",
                                    "write_token_kv"])
def test_a_writer_at_a_traced_layer_is_its_static_form(writer):
    """The same scatter whether the layer is a Python number or a value
    known on the device alone (jitted, so that it IS traced), at every
    layer; nothing lands in another layer, and padding still drops."""
    pool = _pool()
    for layer in range(LAYERS):
        static = _writers()[writer](pool, layer)
        traced = jax.jit(_writers()[writer])(pool, jnp.int32(layer))
        np.testing.assert_array_equal(static, traced)
        changed = np.asarray(static != pool).any(axis=(1, 2, 3))
        assert changed.tolist() == [c == layer for c in range(LAYERS)]


def _kv_pool(seed=0, heads=2, dim=16):
    return jnp.asarray(np.random.RandomState(seed).randn(
        LAYERS, P, S, 2 * heads * dim), jnp.float32)


def test_gather_pages_at_a_traced_layer_is_its_static_form():
    pool, bt = _kv_pool(), jnp.asarray([[2, 5], [0, 3]], jnp.int32)
    for layer in range(LAYERS):
        np.testing.assert_array_equal(
            pa._gather_pages(pool, layer, bt),
            jax.jit(pa._gather_pages)(pool, jnp.int32(layer), bt))
        np.testing.assert_array_equal(pa._gather_pages(pool, layer, bt),
                                      pool[layer][bt])


@pytest.mark.parametrize("reader", ["paged_decode_attention",
                                    "paged_prefill_attention",
                                    "paged_decode_kernel"])
def test_a_reader_at_a_traced_layer_is_its_static_form(reader):
    """``paged_decode_attention`` and ``paged_prefill_attention`` over
    grouped heads, and the Pallas decode kernel (interpreted here) whose
    layer is a prefetched scalar, each against its static form at every
    layer."""
    pool = _kv_pool(3)
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(2, 4, 16), jnp.float32)
    bt = jnp.asarray([[2, 5, 1], [0, 3, 4]], jnp.int32)
    ctx = jnp.asarray([9, 0], jnp.int32)
    if reader == "paged_decode_attention":
        def read(layer):
            return pa.paged_decode_attention(q, pool, None, bt, ctx,
                                             layer=layer, kv_heads=2)
    elif reader == "paged_prefill_attention":
        qs = jnp.asarray(rng.randn(4, 4, 16), jnp.float32)

        def read(layer):
            return pa.paged_prefill_attention(qs, pool, None, bt[0], 5, 3,
                                              layer=layer, kv_heads=2)
    else:
        def read(layer):
            return pa.paged_decode_kernel(q, pool, bt, ctx, kv_heads=2,
                                          layer=layer, interpret=True)
    outs = []
    for layer in range(LAYERS):
        static = read(layer)
        traced = jax.jit(read)(jnp.int32(layer))
        np.testing.assert_allclose(static, traced, atol=1e-6, rtol=0)
        outs.append(np.asarray(static))
    # the layers hold different pages: a reader that ignored its layer
    # would give one answer five times
    assert all(np.abs(outs[0] - o).max() > 1e-3 for o in outs[1:])
    if reader == "paged_decode_kernel":
        # and the kernel is the gather form's answer
        np.testing.assert_allclose(
            read(2), pa._grouped_decode(q, pool, bt, ctx, 16 ** -0.5, None,
                                        2, 2), atol=2e-5, rtol=0)


@pytest.mark.parametrize("layer, scalars", [
    (2, 2), (np.int32(2), 2), (np.int64(2), 2), (jnp.int32(2), 3)])
def test_the_kernel_prefetches_only_a_layer_that_is_not_a_number(layer,
                                                                 scalars):
    """Any whole number, numpy's too, is baked into the kernel (the form
    the windowed and hybrid models lower); an array, traced or not, rides
    as a third prefetched scalar."""
    pool = _kv_pool(3)
    q = jnp.ones((2, 4, 16), jnp.float32)
    bt = jnp.asarray([[2, 5, 1], [0, 3, 4]], jnp.int32)
    ctx = jnp.asarray([9, 0], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q: pa.paged_decode_kernel(
        q, pool, bt, ctx, kv_heads=2, layer=layer, interpret=True))(q)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].num_index_operands == scalars
