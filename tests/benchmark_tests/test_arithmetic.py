"""The yardstick's arithmetic: percentiles with their sample counts, the
traffic generator as a pure function of the seed, the weight maker, and
the FLOP counts against a hand count and the compiler's own."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, harness, traffic_gen, weights

MIX = {"rate": 8.0, "tenants": 4, "prefix_len": 64, "tail": [32, 448],
       "output": [16, 128], "schedule_seed": 0}


def test_percentile_matches_numpy_and_states_its_sample_count():
    xs = list(np.random.default_rng(0).random(137))
    for q in (50, 95, 99):
        assert harness.percentile(xs, q) == pytest.approx(
            np.percentile(xs, q))
    s = harness.timing_summary("t", xs, 95)
    assert s["n"] == 137 and s["samples_beyond"] == 6
    assert "only 6 samples beyond p95" in s["note"]
    many = harness.timing_summary("t", list(range(400)), 95)
    assert many["samples_beyond"] == 20 and "note" not in many
    with pytest.raises(ValueError):
        harness.percentile([], 95)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_traffic_is_a_pure_function_of_the_seed(seed):
    a = traffic_gen.generate(MIX, 50257, seed, 10.0)
    b = traffic_gen.generate(MIX, 50257, seed, 10.0)
    assert len(a) == 80
    for x, y in zip(a, b):
        assert x.due == y.due and x.tenant == y.tenant
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    dues = [x.due for x in a]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 10.0


def test_the_schedule_is_the_mix_s_and_the_tokens_are_the_seed_s():
    a = traffic_gen.generate(MIX, 50257, 1, 10.0)
    b = traffic_gen.generate(MIX, 50257, 2, 10.0)
    c = traffic_gen.generate(dict(MIX, schedule_seed=1), 50257, 1, 10.0)
    same = lambda r: [(x.due, x.tenant, len(x.prompt), x.max_new_tokens)
                      for x in r]
    assert same(a) == same(b) and same(a) != same(c)
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another schedule holds the same sizes and gaps, in another order
    assert sorted(len(x.prompt) for x in a) == \
        sorted(len(x.prompt) for x in c)
    gaps = lambda r: sorted(np.round(np.diff([0] + [x.due for x in r]), 9))
    assert gaps(a) == gaps(c)
    lens = [len(x.prompt) - 64 for x in a]
    assert min(lens) >= 32 and max(lens) <= 448
    assert 50 < np.mean([x.max_new_tokens for x in a]) < 58
    # tenants re-send their own system prompt
    by_tenant = {}
    for x in a:
        by_tenant.setdefault(x.tenant, []).append(x.prompt[:64])
    assert len(by_tenant) == 4
    for prompts in by_tenant.values():
        assert all(np.array_equal(prompts[0], p) for p in prompts)


def test_weights_follow_the_seed_and_take_large_seeds():
    spec = (("/a/W", (8, 4), ("normal", 0.5)), ("/a/b", (8,), ("zeros",)),
            ("/n/gamma", (4,), ("ones",)))
    big = 2**31 + 99
    a, b = weights.make_params(spec, big), weights.make_params(spec, big)
    c = weights.make_params(spec, big + 1)
    assert np.array_equal(a["/a/W"], b["/a/W"])
    assert not np.array_equal(a["/a/W"], c["/a/W"])
    assert float(a["/a/b"].sum()) == 0 and float(a["/n/gamma"].sum()) == 4
    with pytest.raises(ValueError):
        weights.seed_key(-1)


def test_transformer_flops_against_a_hand_count():
    # d=8, L=2, V=32, T=4: weights 12*2*64 + 8*32 = 1792 multiply-adds a
    # token; attention 2 layers * 2*8 * (4+1)/2 = 80
    assert flops.transformer_forward_flops_per_token(8, 2, 32, 4) == \
        2 * (1792 + 80)


def test_resnet_flops_against_the_published_count():
    # He et al. 2015, table 1: 3.8e9 multiply-adds for the 50-layer net
    # (stride on the 1x1); with the stride on the 3x3 it is 4.1e9
    fwd = flops.resnet_forward_flops_per_image([3, 4, 6, 3], 1000, 224)
    assert 2 * 4.0e9 < fwd < 2 * 4.2e9


def _cost(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


def test_transformer_flops_against_cost_analysis():
    from benchmark.reference import gpt2
    from benchmark.models import transformer_lm, _init
    # one layer: the reference scans its blocks, and the compiler counts a
    # scanned body once whatever the trip count
    cfg = dict(vocab_size=512, n_embd=128, n_layer=1, n_head=2,
               n_positions=128)
    model = transformer_lm.build(cfg)
    params = weights.make_params(
        _init.param_spec(model, transformer_lm.init_rule), 0)
    T = 128
    toks = jnp.zeros((T,), jnp.int32)
    counted = _cost(lambda p, t: gpt2.logits_one(p, t, 2), params, toks)
    ours = T * flops.transformer_forward_flops_per_token(128, 1, 512, T)
    # the compiler counts the full score matrix (causal masking skips no
    # product in the plain reference) and the elementwise work
    full = ours + T * 2 * 1 * 128 * 2 * (T - 1) / 2
    assert ours < counted < 1.25 * full


def test_resnet_flops_against_cost_analysis():
    from benchmark.reference import resnet50
    from benchmark.models import resnet, _init
    cfg = dict(harness.load_json(os.path.join(harness.HERE, "configs",
                                              "resnet50.json")),
               block_counts=[1, 1, 1, 1], num_classes=10, image_size=64)
    model = resnet.build(cfg)
    params = weights.make_params(
        _init.param_spec(model, resnet.init_rule), 0)
    x = jnp.zeros((2, 64, 64, 3), jnp.float32)
    counted = _cost(lambda p, x: resnet50.logits(p, x, (1, 1, 1, 1), 2e-5),
                    params, x)
    ours = 2 * flops.resnet_forward_flops_per_image([1, 1, 1, 1], 10, 64)
    # the compiler leaves out the products that fall on a convolution's
    # zero padding (4 % of them at 64 px) and adds the elementwise work
    assert 0.94 * ours < counted < 1.2 * ours


def test_flash_counts_and_roofline_share():
    fl, by = flops.flash_forward(4, 16, 1024, 64)
    assert fl == 4 * 4 * 16 * (1024 * 1025 / 2) * 64
    assert by == 4 * 4 * 16 * 1024 * 64 * 2 + 4 * 4 * 16 * 1024
    bfl, _ = flops.flash_backward(4, 16, 1024, 64)
    assert bfl == 2.5 * fl
    peaks = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    share, bound = flops.roofline_share(fl, by, fl / 197e12 * 2, peaks)
    assert share == pytest.approx(50.0) and bound == "compute"


def test_a_sets_range_leaves_out_its_farthest_run():
    """How the driver reckons a cell's noise against a bound (PR 41's
    refusal): the range of the runs less the one farthest from their
    median, as a share of the median."""
    from benchmark.tools import spreads
    runs = [100.0, 100.2, 99.9, 100.1, 100.0, 103.0]
    assert spreads.range_less_farthest(runs) == pytest.approx(0.3 / 100.05)
    assert spreads.range_less_farthest([100.0, 101.0]) == pytest.approx(
        1.0 / 100.5)
    assert spreads.spread(runs) > 0
