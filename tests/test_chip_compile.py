"""Ask the TPU's compiler, without a TPU: the main path's kernels and
serving programs compile for a described v5e at real widths.

The topology is described inside a fixture (never at import, never in
conftest): only one process may load the TPU library, and under xdist
every worker imports this file.  Code that asks ``jax.default_backend()``
sees the CPU here, so the kernels and program functions are compiled
directly, with ``interpret=False`` steered from the test.  A compile that
passes is not a chip run; what it catches is what the chip's compiler
would refuse (tiling, VMEM, HBM, donation that does not alias).
"""

import functools
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports a function of the same name over the module
fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

# the d768 x 12 transformer of chip_smoke.py
D_MODEL, N_HEADS, N_LAYERS, VOCAB, D_HEAD = 768, 12, 12, 32768, 64
PAGES, PAGE, MAX_BATCH, MAX_CONTEXT = 256, 16, 8, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_trace_from_another_backend():
    """A jitted body inside the models (``moe._held_share``,
    ``window_moe._decode_attention``, ``looped._decode_attention``)
    keeps its trace by its shapes, not by what ``_on_tpu()`` answered
    when it was made; the tests here answer it both ways at the same
    shapes."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
            for _ in range(3)]


def _fwd_bwd():
    def loss(q, k, v):
        return jnp.sum(fa._flash_diff(q, k, v, True, None, False)
                       .astype(jnp.float32))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


def _lse_fwd_bwd():
    def loss(q, k, v):
        out, lse = fa._flash_lse_diff(q, k, v, True, 0.125, False)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


def _has_axis(text, n):
    """Whether any array of the compiled program has an axis of ``n``."""
    return re.search(rf"[\[,]{n}[,\]]", text) is not None


def _compile(fn, *specs, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*specs).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("shape", [(8, 12, 1024, 64), (2, 12, 8192, 64)],
                         ids=["bs8_seq1024", "bs2_seq8192"])
def test_flash_fwd_and_fused_bwd_compile(one_chip, no_persistent_cache,
                                         shape):
    _, text = _compile(_fwd_bwd(), *_qkv(shape, one_chip))
    assert text.count("tpu_custom_call") >= 2
    for name in ("_flash_kernel_lse", "_flash_bwd_fused_kernel"):
        assert name in text


@pytest.mark.parametrize("shape", [(2, 12, 2048, 64), (2, 3, 8192, 64)],
                         ids=["ring_seq8192_over4", "ulysses_seq8192_over4"])
def test_lse_kernel_pair_compiles_at_sequence_parallel_shapes(
        one_chip, no_persistent_cache, shape):
    """``attention_with_lse``'s kernels at what each of 4 chips holds of
    a seq-8192 batch: a 2048-token block (ring) / 3 of 12 heads
    (Ulysses)."""
    _, text = _compile(_lse_fwd_bwd(), *_qkv(shape, one_chip))
    for name in ("_flash_kernel_lse", "_flash_bwd_fused_kernel"):
        assert name in text


def test_rows_form_compiles_between_its_gemms_and_moves_nothing(
        one_chip, no_persistent_cache):
    """GPT-2-medium's attention layer a chip (``[4, 1024, 1024]``
    bfloat16, 16 heads of 64): qkv GEMM -> the rows form of both
    training kernels -> output GEMM, forwards and backwards.  The custom
    calls take the qkv GEMM's ``[4, 1024, 3072]`` and the output GEMM's
    ``[4, 1024, 1024]`` in the GEMMs' own row-major layout, and nothing
    of head shape is copied, transposed or padded on the way."""
    import re
    B, T, H, D = 4, 1024, 16, 64

    def layer(x, w_qkv, w_proj):
        qkv = (x.reshape(B * T, H * D) @ w_qkv).reshape(B, T, 3 * H * D)
        assert fa._rows_heads(qkv, H) == 2
        out = fa._flash_rows_diff(qkv, H, True, None, False)
        return x + (out.reshape(B * T, H * D) @ w_proj).reshape(B, T, H * D)

    def loss(x, w_qkv, w_proj):
        return jnp.sum(layer(x, w_qkv, w_proj).astype(jnp.float32) ** 2)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    _, text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                       spec(B, T, H * D), spec(H * D, 3 * H * D),
                       spec(H * D, H * D))
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert len(calls) == 2, calls
    fwd = next(c for c in calls if "_flash_kernel_lse" in c)
    bwd = next(c for c in calls if "_flash_bwd_fused_kernel" in c)
    rows, heads = "bf16[4,1024,3072]{2,1,0}", "bf16[4,1024,1024]{2,1,0}"
    assert fwd.count(rows) >= 3 and bwd.count(rows) >= 3
    assert bwd.count(heads) >= 2          # g and out, beside dq, dk, dv
    moved = re.compile(
        r"= \S*\[(4,16,1024,64|4,1024,16,64|4,1024,3,16,64)\]\S* "
        r"(copy|transpose|pad)\(")
    assert not [line for line in text.splitlines() if moved.search(line)]


def test_the_loss_reads_the_logits_once_and_writes_no_float32_copy(
        one_chip, no_persistent_cache):
    """GPT-2-medium's vocabulary a chip: ``ln_f`` -> the head's GEMM in
    bfloat16 -> ``F.softmax_cross_entropy`` over ``[4096, 50257]`` logits,
    forwards and backwards.  The loss picks the target's logit inside the
    row reduction, so the bfloat16 logits are read by ONE loop fusion (sum
    of exponentials and the target's logit together) and by the two
    backward GEMMs, which rebuild the softmax in their prologues; no
    float32 array of the logits' shape is an instruction.  With the target
    gathered from ``log_softmax`` the same piece kept 1 236 MB of
    temporaries.  (``softmax_cotangent._on_tpu()`` is false here: this is
    the plain form, which a TPU still runs for N-D logits, rows that are
    no whole blocks of 16 and other dtypes.)"""
    import re
    from chainermn_tpu.nn import functions as F
    N, D, V = 4096, 1024, 50257

    def loss(h, gamma, beta, W, t):
        y = F.layer_normalization(h, gamma, beta)
        return F.softmax_cross_entropy(y @ W.astype(jnp.bfloat16).T, t)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled, text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
        spec((N, D), jnp.bfloat16), spec((D,), jnp.float32),
        spec((D,), jnp.float32), spec((V, D), jnp.float32),
        spec((N,), jnp.int32))
    entry = text[text.index("\nENTRY "):].splitlines()
    assert not [line for line in entry
                if re.search(r" = \(?[^=]*f32\[4096,50257\]", line)]
    logits = [m.group(1) for line in entry for m in [re.match(
        r"\s*%(\S+) = bf16\[4096,50257\]\S* get-tuple-element\(", line)]
        if m]
    assert len(logits) == 1, logits
    readers = [line for line in entry
               if re.search(rf"\(.*%{re.escape(logits[0])}[,)]", line)]
    loops = [line for line in readers if "kind=kLoop" in line]
    gemms = [line for line in readers if "kind=kOutput" in line]
    assert len(readers) == 3 and len(loops) == 1 and len(gemms) == 2, readers
    # the one pass gives both row statistics
    assert re.search(r" = \(f32\[4096\]\S*, f32\[4096\]\S*\) fusion\(",
                     loops[0])
    assert compiled.memory_analysis().temp_size_in_bytes < 500e6


def test_the_loss_reads_the_logits_once_and_hands_both_gemms_one_cotangent(
        one_chip, no_persistent_cache, monkeypatch):
    """GPT-2-medium's vocabulary a chip: ``ln_f`` -> the head's GEMM in
    bfloat16 -> ``F.softmax_cross_entropy`` over ``[4096, 50257]`` logits
    as a TPU takes it (``ops.softmax_cotangent.weighted_nll``), forwards
    and backwards.  The GEMM writes the logits by rows with their row
    maximum in its epilogue; ONE ``_softmax_cotangent_kernel`` reads
    them (beside the gather of the 4096 targets' logits) and writes
    their cotangent, ``bf16[4096,50257]``; the two backward GEMM fusions
    take that as a plain operand where each used to rebuild the softmax
    from the logits in its prologue.  Nothing of the logits' shape is
    copied, none is float32.  Logits and cotangent are 412 MB each; with
    the target gathered from ``log_softmax`` the same piece kept 1 236 MB
    of temporaries."""
    import re
    from chainermn_tpu.nn import functions as F
    from chainermn_tpu.ops import softmax_cotangent
    monkeypatch.setattr(softmax_cotangent, "_on_tpu", lambda: True)
    N, D, V = 4096, 1024, 50257

    def loss(h, gamma, beta, W, t):
        y = F.layer_normalization(h, gamma, beta)
        return F.softmax_cross_entropy(y @ W.astype(jnp.bfloat16).T, t)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled, text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
        spec((N, D), jnp.bfloat16), spec((D,), jnp.float32),
        spec((D,), jnp.float32), spec((V, D), jnp.float32),
        spec((N,), jnp.int32))
    entry = text[text.index("\nENTRY "):].splitlines()
    assert not [line for line in entry
                if re.search(r" = \(?[^=]*f32\[4096,50257\]", line)]
    assert not [line for line in entry
                if re.search(r" = \S+\[4096,50257\]\S* (copy|transpose)\(",
                             line)]

    def named(pattern):
        return [m.group(1) for line in entry
                for m in [re.match(r"\s*%(\S+) = " + pattern, line)] if m]

    def readers(name):
        return [line for line in entry
                if re.search(rf"\(.*%{re.escape(name)}[,)]", line)]

    kernels = named(r"\(f32\[4096,1\]\S*, bf16\[4096,50257\]\{1,0\S* "
                    r"custom-call\(.*_softmax_cotangent_kernel")
    assert len(kernels) == 1, kernels
    # the logits, by rows, out of the GEMM that also takes their row maximum
    logits = named(r"bf16\[4096,50257\]\{1,0\S* get-tuple-element\(%fusion")
    assert len(logits) == 1, logits
    read = readers(logits[0])
    assert len(read) == 2 and sum("custom-call(" in l for l in read) == 1 \
        and sum("gather" in l for l in read) == 1, read
    cotangent = named(r"bf16\[4096,50257\]\{1,0\S* get-tuple-element\(%"
                      + re.escape(kernels[0]))
    assert len(cotangent) == 1, cotangent
    gemms = readers(cotangent[0])
    assert len(gemms) == 2 and all("kind=kOutput" in l for l in gemms), gemms
    assert sorted(re.match(r"\s*%\S+ = \(?(\w+\[[\d,]*\])", l).group(1)
                  for l in gemms) == ["f32[1024]", "f32[50257,1024]"]
    assert compiled.memory_analysis().temp_size_in_bytes < 900e6


@pytest.mark.parametrize("shape,dtype", [
    ((4096, 50257), jnp.bfloat16), ((8192, 32768), jnp.bfloat16),
    ((16, 4096), jnp.bfloat16), ((16384, 8192), jnp.bfloat16),
    ((64, 160000), jnp.bfloat16)])
def test_softmax_cotangent_kernel_compiles(one_chip, no_persistent_cache,
                                           shape, dtype):
    """The kernel at GPT-2-medium's logits, at ``chip_smoke.py``'s, at
    the least shape it takes, at many rows of few classes and at a
    vocabulary of 160 000."""
    from chainermn_tpu.ops import softmax_cotangent
    assert softmax_cotangent.fits(shape, dtype)
    n = shape[0]
    _compile(softmax_cotangent.softmax_sums_and_cotangent,
             jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip),
             jax.ShapeDtypeStruct((n, 1), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
             jax.ShapeDtypeStruct((n, 1), jnp.float32, sharding=one_chip))


# -- the serving programs ----------------------------------------------------

@pytest.fixture(scope="module")
def serving(one_chip):
    """The d768 x 12 model, its state as shapes on the described chip,
    and the pool pair ``[L, P, S, H · D]`` in bf16."""
    from chainermn_tpu.core.link import extract_state
    from chainermn_tpu.models import TransformerLM
    model = TransformerLM(n_vocab=VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
                          n_layers=N_LAYERS, max_len=MAX_CONTEXT, seed=0,
                          compute_dtype=jnp.bfloat16)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                         extract_state(model))
    (entry, _) = model.serve_cache_entry()
    pool = spec((N_LAYERS, PAGES, PAGE) + entry, jnp.bfloat16)
    return model, state, pool, spec


def _assert_pools_in_place(compiled, text, pool):
    """Both pools donated and aliased, stored in ONE layout (rows of
    whole lane tiles, minor-most), never copied and never cut into a
    layer's slab: with heads of 64 as a minor axis of their own the chip
    kept each pool in one layout and computed on another, and converted
    all of it four times a step (PERF.md section 6, PR 43)."""
    _assert_no_pool_is_copied(compiled, text, [pool, pool])
    slab = "bf16[%s]" % ",".join(map(str, pool.shape[1:]))
    assert slab not in text and slab.replace("[", "[1,") not in text


@pytest.mark.parametrize("bucket", [16, MAX_CONTEXT])
def test_prefill_program_compiles_with_pools_donated(
        serving, no_persistent_cache, monkeypatch, bucket):
    from chainermn_tpu.serving import prefill_program
    model, state, pool, spec = serving
    # the dispatcher would take its CPU branch here: steer it onto the
    # compiled kernel, as it goes on the chip
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    compiled, text = _compile(
        functools.partial(prefill_program, model), state, pool, pool,
        spec((1, bucket), jnp.int32), spec((), jnp.int32),
        spec((MAX_CONTEXT // PAGE,), jnp.int32), donate_argnums=(1, 2))
    assert "_flash_kernel" in text and "tpu_custom_call" in text
    _assert_pools_in_place(compiled, text, pool)


def test_suffix_prefill_program_compiles_with_pools_donated(
        serving, no_persistent_cache):
    from chainermn_tpu.serving import prefix_prefill_program
    model, state, pool, spec = serving
    compiled, text = _compile(
        functools.partial(prefix_prefill_program, model), state, pool, pool,
        spec((1, 64), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
        spec((MAX_CONTEXT // PAGE,), jnp.int32), donate_argnums=(1, 2))
    _assert_pools_in_place(compiled, text, pool)


@pytest.mark.parametrize("lanes", [1, MAX_BATCH])
def test_decode_program_compiles_with_pools_donated(
        serving, no_persistent_cache, lanes):
    from chainermn_tpu.serving import decode_program
    model, state, pool, spec = serving
    compiled, text = _compile(
        functools.partial(decode_program, model, mode="paged"),
        state, pool, pool, spec((lanes,), jnp.int32),
        spec((lanes,), jnp.int32),
        spec((lanes, MAX_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2))
    _assert_pools_in_place(compiled, text, pool)


def test_spec_verify_program_compiles_with_pools_donated(
        serving, no_persistent_cache):
    from chainermn_tpu.serving import spec_verify_program
    model, state, pool, spec = serving
    spec_k = 4
    compiled, text = _compile(
        functools.partial(spec_verify_program, model),
        state, pool, pool, spec((MAX_BATCH, spec_k + 1), jnp.int32),
        spec((MAX_BATCH,), jnp.int32), spec((MAX_BATCH,), jnp.int32),
        spec((MAX_BATCH, MAX_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2))
    _assert_pools_in_place(compiled, text, pool)


@pytest.mark.parametrize("lanes", [16, 32, 64])
def test_the_gather_between_two_decode_runs_is_one_program_a_lane_count(
        one_chip, no_persistent_cache, lanes):
    """PR 46: the tokens of a decode run dispatched behind one in flight
    come through ONE program whatever pair of batch buckets follow each
    other, at the lane counts of the serving cells (16: GPT-2, Olmo,
    Ouro; 32: Laguna; 64: Kimi, SmallThinker): one vector a bucket in,
    the tokens cut to every bucket out, a few KB of temporaries."""
    from chainermn_tpu.serving.engine import (_pow2_buckets,
                                              next_tokens_program)
    buckets = _pow2_buckets(1, lanes)
    prevs = tuple(jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
                  for b in buckets)
    sel = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip)
    compiled, text = _compile(next_tokens_program, prevs, sel)
    assert [o.shape for o in compiled.out_info] == [(b,) for b in buckets]
    assert "tpu_custom_call" not in text       # no kernel: plain XLA
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 16


# -- the latent-attention MoE share (kimi-k2.6-share), published widths ------

@pytest.fixture(scope="module")
def latent(one_chip):
    """The configuration's builder at the published widths, cut to the
    dense layer and ONE expert layer (a compile of the alike layers says
    nothing more), its bfloat16 state as shapes on the described chip,
    and the cell's latent pool."""
    from benchmark import harness
    config = harness.load_json(os.path.join(
        harness.HERE, "configs", "kimi-k2.6-share.json"))
    config["num_hidden_layers"] = 2
    model = harness.load_module("models", "latent_moe_lm").build(
        config, max_len=4608)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = {"params": {path: spec(p.shape, jnp.bfloat16)
                        for path, p in model.namedparams()}, "state": {}}
    pool = spec((2, 18432, 16) + model.serve_cache_entry()[0], jnp.bfloat16)
    return model, state, pool, spec


def _assert_pool_in_place(compiled, text, pool, temp=None):
    """Donated, aliased, and never copied whole: the pool's STORED layout
    (the runtime's choice for its shape) is the one the program computes
    in.  A latent of 576 values, 4.5 lane tiles, is stored page-axis
    minor-most and costs two whole-pool copies a call."""
    nbytes = 2 * 18432 * 16 * 640 * 2
    assert pool.shape[-1] == 640
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    assert "bf16[2,18432,16,640]{3,2,1,0" in text
    assert "bf16[2,18432,16,640]{1,3,2,0" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < (temp or nbytes)


@pytest.mark.parametrize("lanes", [1, 16, 64])
def test_latent_decode_program_updates_its_pool_in_place(
        latent, no_persistent_cache, monkeypatch, lanes):
    """The decode bucket's ends and its usual one.  The expert layer's
    held share is three grouped products over the stacked leaves (PR 47):
    no product over all 12 as one matrix of width 12 x 2048."""
    from chainermn_tpu.serving import decode_program
    model, state, pool, spec = latent
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    compiled, text = _compile(
        functools.partial(decode_program, model, mode=None), state, pool,
        spec((lanes,), jnp.int32), spec((lanes,), jnp.int32),
        spec((lanes, 288), jnp.int32), donate_argnums=(1,))
    _assert_pool_in_place(compiled, text, pool)
    assert len(_grouped_products(text)) == 3
    assert not _has_axis(text, 12 * 2048)


def test_latent_prefill_program_compiles_with_the_flash_kernel(
        latent, no_persistent_cache, monkeypatch):
    from chainermn_tpu.serving import prefill_program
    model, state, pool, spec = latent
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    compiled, text = _compile(
        functools.partial(prefill_program, model), state, pool,
        spec((1, 4096), jnp.int32), spec((), jnp.int32),
        spec((288,), jnp.int32), donate_argnums=(1,))
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sum("_flash_kernel" in c for c in calls) == 2
    assert len(_grouped_products(text)) == 3 and len(calls) == 5
    assert not _has_axis(text, 12 * 2048)   # no product over the 12 as one
    # the 32768 sorted copies' rows in and out are 0.47 GB each; a copy
    # of the pool would be 0.75 GB more
    _assert_pool_in_place(compiled, text, pool, temp=1.2e9)


@pytest.mark.parametrize("suffix", [64, 512])
def test_latent_suffix_program_groups_its_experts_by_sorting(
        latent, no_persistent_cache, monkeypatch, suffix):
    """The cell's first and last suffix bucket (512 and 4096 sorted
    copies: row tiles of 32 and 256, ``up`` tiles of 7168 x 256 and
    896 x 2048)."""
    from chainermn_tpu.serving import prefix_prefill_program
    model, state, pool, spec = latent
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    compiled, text = _compile(
        functools.partial(prefix_prefill_program, model), state, pool,
        spec((1, suffix), jnp.int32), spec((), jnp.int32),
        spec((), jnp.int32), spec((288,), jnp.int32), donate_argnums=(1,))
    assert len(_grouped_products(text)) == 3
    assert not _has_axis(text, 12 * 2048)
    # (the temporaries are the gathered context's, a pool's size and more)
    _assert_pool_in_place(compiled, text, pool, temp=1.2e9)


# -- window and full attention side by side (laguna-s-2.1-share) ---------------

LAGUNA_PAGES, LAGUNA_CONTEXT, LAGUNA_LANES = 21504, 10752, 32


@pytest.fixture(scope="module")
def windowed(one_chip):
    """The configuration's builder at the published widths and the
    cell's depth, its bfloat16 state as shapes on the described chip,
    and the cell's two pools, a token's K then its V in one row of 2048
    lanes: the full group's (21504 pages) and the window group's (sized
    by the engine: 3840)."""
    from benchmark import harness
    from chainermn_tpu.serving import ServingEngine
    config = harness.load_json(os.path.join(
        harness.HERE, "configs", "laguna-s-2.1-share.json"))
    model = harness.load_module("models", "window_moe_lm").build(
        config, max_len=LAGUNA_CONTEXT)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = {"params": {path: spec(p.shape, jnp.bfloat16)
                        for path, p in model.namedparams()}, "state": {}}
    window_pages = ServingEngine.window_group_pages(
        512, PAGE, LAGUNA_LANES, LAGUNA_CONTEXT)
    pools = [spec((n, pages, PAGE) + shape, jnp.bfloat16)
             for (_, n, entry, _), pages in zip(
                 model.serve_cache_groups(), (LAGUNA_PAGES, window_pages))
             for shape in entry]
    return model, state, pools, spec


def _pool_bytes(pools):
    import math
    return sum(math.prod(p.shape) * 2 for p in pools)


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["pages_in_place", "gather_form"])
@pytest.mark.parametrize("lanes", [1, LAGUNA_LANES])
def test_windowed_decode_program_at_its_first_and_last_bucket(
        windowed, no_persistent_cache, monkeypatch, lanes, kernel):
    """Both groups' pools donated and updated in place (1.51 GB of
    window pages where the same layers kept whole would be 8.46).  As
    the chip runs it, every layer's attention is `_paged_decode_kernel`,
    which reads the pages in place: no temporary of a layer's gathered
    pages.  The gather form (every other backend's) has a window layer
    gather 33 pages a lane and a full layer all 672, its temporaries the
    gathered ``[lanes, 672, 16, 2048]`` of one full layer (1.41 GB at 32
    lanes) and little else.  At ONE lane a pool that ended ``[8, 128]``
    was copied whole into a layout of 16-fold padding (22.5 GB: the chip
    refused the program, PR 31), so that bucket is compiled here too."""
    from chainermn_tpu.ops import paged_attention
    from chainermn_tpu.serving import decode_program
    model, state, pools, spec = windowed
    for module in (paged_attention, fa):
        monkeypatch.setattr(module, "_on_tpu", lambda: kernel)
    assert [p.shape for p in pools] == \
        [(3, 21504, 16, 2048), (6, 3840, 16, 2048)]
    assert _pool_bytes(pools[1:]) * 4 <= 6 * 21504 * 16 * 2048 * 2
    N = LAGUNA_CONTEXT // PAGE
    compiled, text = _compile(
        functools.partial(decode_program, model, mode=None), state, *pools,
        spec((lanes,), jnp.int32), spec((lanes,), jnp.int32),
        spec((2, lanes, N), jnp.int32), donate_argnums=(1, 2))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= _pool_bytes(pools)
    per_lane = N * PAGE * 2048 * 2              # one full layer's K and V
    if kernel:
        calls = [line for line in text.splitlines()
                 if "custom_call_target=\"tpu_custom_call\"" in line]
        assert sum("_paged_decode_kernel" in c for c in calls) == 9
        # the 8 expert layers' held shares, three grouped products each
        assert len(_grouped_products(text)) == 3 * 8
        assert not _has_axis(text, 16 * 1024)   # none over the 16 as one
        assert ma.temp_size_in_bytes < 0.1 * per_lane * lanes + 5e7
        assert ",672,16,2048]" not in text
        return
    # (since PR 48 a layer's index in its group is an operand of the one
    # read a kind of layer shares, and the gathered pages of a full layer
    # are no longer laid down whole: 21 MB at one lane where 44 were)
    assert ma.temp_size_in_bytes < 1.2 * per_lane * lanes + 5e7, \
        ma.temp_size_in_bytes
    if lanes > 1:                  # (one lane's unit axis is folded away)
        assert "bf16[32,33,16,2048]" in text     # a window layer's pages
        assert "bf16[32,672,16,2048]" in text    # a full layer's


def test_windowed_prefill_program_at_10752_tokens(windowed,
                                                  no_persistent_cache,
                                                  monkeypatch):
    """One call a layer of the serving forward: ``_flash_kernel`` with
    grouped K/V heads on the 3 full layers, ``_flash_window_kernel`` on
    the 6 sliding ones; pools in place; temporaries pinned."""
    from chainermn_tpu.serving import prefill_program
    model, state, pools, spec = windowed
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    compiled, text = _compile(
        functools.partial(prefill_program, model), state, *pools,
        spec((1, LAGUNA_CONTEXT), jnp.int32), spec((), jnp.int32),
        spec((2, LAGUNA_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2))
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sum("_flash_window_kernel" in c for c in calls) == 6
    assert sum("_flash_kernel" in c for c in calls) == 3
    # K and V go in as 8 heads: nothing of 48 or 72 K/V heads is made
    assert all("bf16[8,10752,128]" in c for c in calls if "_flash" in c)
    assert len(_grouped_products(text)) == 3 * 8
    assert not _has_axis(text, 16 * 1024)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= _pool_bytes(pools)
    # of which 0.66 GB a time are the 107520 sorted copies' rows
    assert 1.0e9 < ma.temp_size_in_bytes < 2.4e9, ma.temp_size_in_bytes


@pytest.mark.parametrize("suffix", [256, 2048])
def test_windowed_suffix_program_groups_its_experts_by_sorting(
        windowed, no_persistent_cache, monkeypatch, suffix):
    """The cell's first and last suffix bucket (2560 and 20480 sorted
    copies): pools in place, 24 grouped products."""
    from chainermn_tpu.serving import prefix_prefill_program
    model, state, pools, spec = windowed
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    compiled, text = _compile(
        functools.partial(prefix_prefill_program, model), state, *pools,
        spec((1, suffix), jnp.int32), spec((), jnp.int32),
        spec((), jnp.int32), spec((2, LAGUNA_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2))
    assert len(_grouped_products(text)) == 3 * 8
    assert not _has_axis(text, 16 * 1024)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= _pool_bytes(pools)


# -- a whole expert layer grouped by sorting (smallthinker-21b-a3b-stage) ----

PREROUTED_PAGES, PREROUTED_CONTEXT, PREROUTED_LANES = 17440, 8704, 32


@pytest.fixture(scope="module")
def prerouted(one_chip):
    """The configuration's builder at the published widths and the
    cell's depth (4 layers, all 64 experts, the whole vocabulary), its
    bfloat16 state as shapes on the described chip, and the cell's two
    pools: the one full layer's (17440 pages) and the three window
    layers' (sized by the engine for a window of 4096: 10496)."""
    from benchmark import harness
    from chainermn_tpu.serving import ServingEngine
    config = harness.load_json(os.path.join(
        harness.HERE, "configs", "smallthinker-21b-a3b-stage.json"))
    model = harness.load_module("models", "prerouted_moe_lm").build(
        config, max_len=PREROUTED_CONTEXT)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = {"params": {path: spec(p.shape, jnp.bfloat16)
                        for path, p in model.namedparams()}, "state": {}}
    window_pages = ServingEngine.window_group_pages(
        4096, PAGE, PREROUTED_LANES, PREROUTED_CONTEXT)
    pools = [spec((n, pages, PAGE) + shape, jnp.bfloat16)
             for (_, n, entry, _), pages in zip(
                 model.serve_cache_groups(),
                 (PREROUTED_PAGES, window_pages))
             for shape in entry]
    assert [p.shape for p in pools] == \
        [(1, 17440, 16, 1024), (3, 10496, 16, 1024)]
    return model, state, pools, spec


def _grouped_products(text):
    """The compiled program's calls of the grouped matmul kernel."""
    return [line for line in text.splitlines()
            if "custom_call_target=\"tpu_custom_call\"" in line
            and line.lstrip().startswith("%gmm")]


@pytest.mark.parametrize("lanes", [1, 16, PREROUTED_LANES])
def test_prerouted_decode_program_groups_its_experts_by_sorting(
        prerouted, no_persistent_cache, monkeypatch, lanes):
    """Every decode bucket's ends and its usual one: both pools donated
    and updated in place, every layer's attention the paged kernel at a
    window of 4096 (257 pages a lane) and a group of 7 query heads, and
    every layer's experts three grouped products over the stacked
    leaves: no temporary of an expert layer's 755 MB, which a gather or
    a copy of the weights would be."""
    from chainermn_tpu.serving import decode_program
    model, state, pools, spec = prerouted
    _on_the_chip(monkeypatch)
    N = PREROUTED_CONTEXT // PAGE
    compiled, text = _compile(
        functools.partial(decode_program, model, mode=None), state, *pools,
        spec((lanes,), jnp.int32), spec((lanes,), jnp.int32),
        spec((2, lanes, N), jnp.int32), donate_argnums=(1, 2))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= _pool_bytes(pools)
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sum("_paged_decode_kernel" in c for c in calls) == 4
    assert len(_grouped_products(text)) == 3 * 4
    assert ma.temp_size_in_bytes < 2e8, ma.temp_size_in_bytes


@pytest.mark.parametrize("program", ["prefill", "suffix_prefill"])
def test_prerouted_prefill_programs_at_8192_tokens(
        prerouted, no_persistent_cache, monkeypatch, program):
    """The cell's one prefill bucket and its one suffix bucket: the full
    layer through ``_flash_kernel`` and the three window layers through
    ``_flash_window_kernel`` at a window of 4096 (the full prefill), 12
    grouped products over 49152 sorted copies, pools in place, and
    temporaries far below the 12.9 GB the masked form's hidden
    activation ``[8192, 64 x 768]`` would need three times over."""
    from chainermn_tpu import serving
    model, state, pools, spec = prerouted
    _on_the_chip(monkeypatch)
    operands = (spec((1, 8192), jnp.int32), spec((), jnp.int32))
    if program == "suffix_prefill":
        operands += (spec((), jnp.int32),)
    fn = {"prefill": serving.prefill_program,
          "suffix_prefill": serving.prefix_prefill_program}[program]
    compiled, text = _compile(
        functools.partial(fn, model), state, *pools, *operands,
        spec((2, PREROUTED_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2))
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    if program == "prefill":
        assert sum("_flash_window_kernel" in c for c in calls) == 3
        assert sum("_flash_kernel" in c for c in calls) == 1
    assert len(_grouped_products(text)) == 3 * 4
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= _pool_bytes(pools)
    assert ma.temp_size_in_bytes < 2.5e9, ma.temp_size_in_bytes


# -- recurrent state beside pages (olmo-hybrid-7b-stage), published widths ---

HYBRID_PAGES, HYBRID_CONTEXT, HYBRID_LANES = 7168, 17920, 16


@pytest.fixture(scope="module")
def hybrid(one_chip):
    """The configuration's builder at the published widths and the
    cell's depth, its bfloat16 state as shapes on the described chip,
    and the cell's pools: the full layers' pages (a token's K then its V
    in one row of 7680 lanes) and the linear layers' slots, sized by the
    engine (56): every head's state ``[96, 5760]`` and the convolution's
    three last inputs, float32."""
    from benchmark import harness
    from chainermn_tpu.serving import ServingEngine
    config = harness.load_json(os.path.join(
        harness.HERE, "configs", "olmo-hybrid-7b-stage.json"))
    model = harness.load_module("models", "hybrid_delta_lm").build(
        config, max_len=HYBRID_CONTEXT)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = {"params": {path: spec(p.shape, jnp.bfloat16)
                        for path, p in model.namedparams()}, "state": {}}
    (_, n_full, (row,), _), (_, n_linear, entry, span) = \
        model.serve_cache_groups()
    slots = ServingEngine.state_group_slots(span.stride, HYBRID_LANES,
                                            HYBRID_CONTEXT)
    pools = [spec((n_full, HYBRID_PAGES, PAGE) + row, jnp.bfloat16)] \
        + [spec((n_linear, slots) + shape, jnp.float32) for shape in entry]
    assert [p.shape for p in pools] == [
        (2, 7168, 16, 7680), (6, 56, 96, 5760), (6, 56, 34560)]
    return model, state, pools, spec


def _on_the_chip(monkeypatch):
    """The three dispatchers take their TPU branch, as the chip would."""
    from chainermn_tpu.ops import gated_delta, paged_attention
    for module in (fa, paged_attention, gated_delta):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)


def _assert_no_pool_is_copied(compiled, text, pools):
    """Every pool donated, aliased and never copied whole, relaid or
    cut into pieces (a slot of state rows and convolution rows in ONE
    array had the whole 0.8 GB pool relaid between layers at 16 lanes,
    and copied at one; an XLA gather of the lanes' slots first sliced
    the whole pool into three ``[6, 56, 96, 1920]``, 13 ms of every
    decode step on the chip: PR 33)."""
    import math
    import re
    nbytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    for p in pools:
        shape = "%s[%s]" % ({"bfloat16": "bf16", "float32": "f32"}[
            str(p.dtype)], ",".join(map(str, p.shape)))
        assert not re.findall(re.escape(shape) + r"\{[^}]*\} copy\(", text)
        layouts = set(re.findall(re.escape(shape) + r"\{([\d,]+)", text))
        assert layouts == {",".join(map(str, reversed(range(p.ndim))))}
        lead = shape[:shape.rindex(",") + 1]        # all but the lanes
        assert set(re.findall(re.escape(lead) + r"\d+\]", text)) == {shape}


@pytest.mark.parametrize("lanes", [1, HYBRID_LANES])
def test_hybrid_decode_program_at_its_first_and_last_bucket(
        hybrid, no_persistent_cache, monkeypatch, lanes):
    """Pages and slots donated and updated in place; the 2 full layers'
    attention is `_paged_decode_kernel` over 30 K/V heads (3840 key
    lanes a row), the 6 linear layers read and write each lane's slot
    alone: the temporaries are a few slots a lane, not a pool."""
    from chainermn_tpu.serving import decode_program
    model, state, pools, spec = hybrid
    _on_the_chip(monkeypatch)
    compiled, text = _compile(
        functools.partial(decode_program, model, mode=None), state, *pools,
        spec((lanes,), jnp.int32), spec((lanes,), jnp.int32),
        spec((2, lanes, HYBRID_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2, 3))
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sum("_paged_decode_kernel" in c for c in calls) == 2
    _assert_no_pool_is_copied(compiled, text, pools)
    slot = 96 * 5760 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 24 * slot * lanes + 2e7


def test_hybrid_prefill_program_at_17920_tokens(hybrid,
                                                no_persistent_cache,
                                                monkeypatch):
    """One `_gated_delta_chunk_kernel` a linear layer (the pass over 280
    chunks, 9 states out) and one `_flash_kernel` a full layer; pools in
    place; temporaries pinned (weights 4.87 + pools 4.31 + these fit the
    chip's 16 GB)."""
    from chainermn_tpu.serving import prefill_program
    model, state, pools, spec = hybrid
    _on_the_chip(monkeypatch)
    compiled, text = _compile(
        functools.partial(prefill_program, model), state, *pools,
        spec((1, HYBRID_CONTEXT), jnp.int32), spec((), jnp.int32),
        spec((2, HYBRID_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2, 3))
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sum("_gated_delta_chunk_kernel" in c for c in calls) == 6
    assert sum("_flash_kernel" in c for c in calls) == 2
    assert all("f32[9,30,96,192]" in c for c in calls
               if "_gated_delta_chunk_kernel" in c)
    _assert_no_pool_is_copied(compiled, text, pools)
    assert 2.0e9 < compiled.memory_analysis().temp_size_in_bytes < 4.0e9


def test_hybrid_suffix_program_at_1024_tokens(hybrid, no_persistent_cache,
                                              monkeypatch):
    """A hit's suffix: the scan starts from the snapshot's slot (one
    state in, one out), the full layers read the shared pages through
    the block table."""
    from chainermn_tpu.serving import prefix_prefill_program
    model, state, pools, spec = hybrid
    _on_the_chip(monkeypatch)
    compiled, text = _compile(
        functools.partial(prefix_prefill_program, model), state, *pools,
        spec((1, 1024), jnp.int32), spec((), jnp.int32),
        spec((), jnp.int32), spec((2, HYBRID_CONTEXT // PAGE), jnp.int32),
        donate_argnums=(1, 2, 3))
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sum("_gated_delta_chunk_kernel" in c for c in calls) == 6
    assert all("f32[1,30,96,192]" in c for c in calls)
    _assert_no_pool_is_copied(compiled, text, pools)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


# -- layers run several times (ouro-2.6b), published widths, nothing cut ---

LOOPED_PAGES, LOOPED_CONTEXT, LOOPED_LANES = 320, 352, 16


@pytest.fixture(scope="module")
def looped(one_chip):
    """The configuration's builder at the published widths, its bfloat16
    state as shapes on the described chip, and the cell's one pool: 4
    passes x 48 blocks = 192 cache layers of 320 pages, a token's K then
    its V in one row of 4096 lanes, 8.05 GB."""
    from benchmark import harness
    config = harness.load_json(os.path.join(
        harness.HERE, "configs", "ouro-2.6b.json"))
    model = harness.load_module("models", "looped_lm").build(
        config, max_len=LOOPED_CONTEXT)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = {"params": {path: spec(p.shape, jnp.bfloat16)
                        for path, p in model.namedparams()}, "state": {}}
    (_, layers, (row,), _), = model.serve_cache_groups()
    pool = spec((layers, LOOPED_PAGES, PAGE) + row, jnp.bfloat16)
    assert pool.shape == (192, 320, 16, 4096)
    return model, state, pool, spec


def _looped_program(looped, program, size):
    from chainermn_tpu.serving import (decode_program, prefill_program,
                                       prefix_prefill_program)
    model, state, pool, spec = looped
    N = LOOPED_CONTEXT // PAGE
    scalar = spec((), jnp.int32)
    if program == "decode":
        fn = functools.partial(decode_program, model, mode=None)
        operands = (spec((size,), jnp.int32), spec((size,), jnp.int32),
                    spec((1, size, N), jnp.int32))
    elif program == "prefill":
        fn = functools.partial(prefill_program, model)
        operands = (spec((1, size), jnp.int32), scalar,
                    spec((1, N), jnp.int32))
    else:
        fn = functools.partial(prefix_prefill_program, model)
        operands = (spec((1, size), jnp.int32), scalar, scalar,
                    spec((1, N), jnp.int32))
    return _compile(fn, state, pool, *operands, donate_argnums=(1,))


@pytest.mark.parametrize("program, size, temporaries", [
    ("decode", 1, 0.06e9), ("decode", LOOPED_LANES, 0.13e9),
    ("prefill", 256, 0.11e9), ("suffix_prefill", 128, 0.13e9)])
def test_looped_program_carries_its_pool_in_place(
        looped, no_persistent_cache, monkeypatch, program, size,
        temporaries):
    """The three programs' body is ONE device loop over the 4 passes
    (the 48 blocks unrolled inside it, not 192 bodies), the 8.05 GB pool
    its carry, written and read at a cache layer that is a value of the
    loop: donated, aliased, never copied, relaid or cut (one copy does
    not fit beside it: weights 5.34 + pool 8.05 of 16 GB), and the
    temporaries a hundredth of the pool's order (compiled here: 46 MB
    the one-lane decode, 100 MB the 16-lane decode and the 128 suffix,
    85 MB the 256 prefill: the largest is 100 MB).  As the chip runs
    them, a decode step's attention is `_paged_decode_kernel` with the
    layer a prefetched scalar, a full prefill's `_flash_kernel`."""
    from chainermn_tpu.ops import paged_attention
    pool = looped[2]
    for module in (fa, paged_attention):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    compiled, text = _looped_program(looped, program, size)
    _assert_no_pool_is_copied(compiled, text, [pool])
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < temporaries, ma.temp_size_in_bytes
    # one loop over passes, and the kernels inside it once a block
    whiles = [line for line in text.splitlines()
              if " while(" in line and "/loop/" in line]
    assert len(whiles) == 1
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    kernel = {"decode": "_paged_decode_kernel",
              "prefill": "_flash_kernel"}.get(program)
    if kernel is None:
        assert not calls            # a suffix reads its pages by a gather
        assert f"bf16[{LOOPED_CONTEXT // PAGE},16,4096]" in text
    else:
        assert sum(kernel in c for c in calls) == len(calls) == 48


def _pallas_calls(jaxpr, name):
    """Every ``pallas_call`` equation called ``name`` in ``jaxpr`` and
    in what it holds (loops, branches, calls)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" \
                and eqn.params["name"] == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub, name)
    return found


# `_paged_decode_kernel` bakes a STATIC layer in and takes a TRACED one as
# one more prefetched scalar ahead of the block table and the contexts.
# The hybrid model reads its pages through it at a static layer and keeps
# the form its cell was accepted with, at every layer; the looped model's
# loop supplies the third scalar, and since PR 48 so does the windowed
# model (and the prerouted one, its subclass): the layers of a kind share
# one trace and one lowering of the kernel, the layer's index an operand
# (`models/window_moe._decode_attention`).
@pytest.mark.parametrize("which, calls, scalars", [
    ("windowed", None, 3), ("hybrid", None, 2), ("looped", 48, 3)])
def test_decode_kernel_prefetches_a_layer_only_inside_a_loop(
        request, monkeypatch, which, calls, scalars):
    from chainermn_tpu.serving import decode_program
    model, state, *pools, spec = request.getfixturevalue(which)
    if which != "looped":
        (pools,) = pools
    _on_the_chip(monkeypatch)
    lanes, context = {"windowed": (LAGUNA_LANES, LAGUNA_CONTEXT),
                      "hybrid": (HYBRID_LANES, HYBRID_CONTEXT),
                      "looped": (LOOPED_LANES, LOOPED_CONTEXT)}[which]
    groups = len(model.serve_cache_groups())
    jaxpr = jax.make_jaxpr(
        functools.partial(decode_program, model, mode=None))(
        state, *pools, spec((lanes,), jnp.int32), spec((lanes,), jnp.int32),
        spec((groups, lanes, context // PAGE), jnp.int32)).jaxpr
    found = _pallas_calls(jaxpr, "_paged_decode_kernel")
    assert found and (calls is None or len(found) == calls)
    for eqn in found:
        assert eqn.params["grid_mapping"].num_index_operands == scalars
        # the scalars, the padded queries and the whole pool: no more
        assert len(eqn.invars) == scalars + 2
