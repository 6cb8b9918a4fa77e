"""Property suite for the quantized gradient wire (ISSUE 8).

The quantize/dequantize pair and the error-feedback residual are the
numerical core of the compressed exchange — convergence parity rests on
four properties pinned here:

* round-trip error is BOUNDED (scale/2 per element for int8; relative
  2^-mantissa for fp8) — quantization is lossy but never unbounded;
* the scale is a DETERMINISTIC pure function of the buffer — every rank
  quantizing the same chunk derives the same codebook, which is what
  lets the dequantize-sum reconstruct a cross-rank mean at all;
* zero / inf / NaN gradients have DEFINED behavior (zeros stay zeros
  with scale 1; inf saturates without poisoning the scale; NaN encodes
  as 0 and contributes 0 residual) — one overflowed step must not
  destroy the buffer or the carried error;
* the residual TELESCOPES: over K steps of error feedback the sum of
  applied (dequantized) updates equals the sum of true gradients up to
  exactly the last residual — the carried error never accumulates.

The convergence-side counterpart lives in
tests/core_tests/test_quantized_parity.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.communicators._memory_utility import (
    QUANTIZED_DTYPES, dequantize_symmetric, is_quantized_dtype,
    quantization_residual, quantize_symmetric, quantized_hop_bytes,
    resolve_grad_dtype)

WIRES = ("int8", "float8_e4m3", "float8_e5m2")

#: per-wire relative round-trip bound: int8 is a uniform 127-level
#: codebook (half a step of the largest magnitude); fp8 is relative
#: floating-point rounding (2^-mantissa_bits of the element, but bounded
#: here against absmax for simplicity of the uniform statement)
REL_BOUND = {"int8": 0.5 / 127.0, "float8_e4m3": 2.0 ** -3,
             "float8_e5m2": 2.0 ** -2}


def _vec(seed=0, n=257, scale=3.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray((rng.normal(0, scale, n)).astype(np.float32))


@pytest.mark.parametrize("wire", WIRES)
def test_round_trip_error_bound(wire):
    v = _vec()
    q, s = quantize_symmetric(v, wire)
    err = np.abs(np.asarray(dequantize_symmetric(q, s)) - np.asarray(v))
    absmax = float(np.max(np.abs(np.asarray(v))))
    assert float(np.max(err)) <= absmax * REL_BOUND[wire] * (1 + 1e-6), wire


@pytest.mark.parametrize("wire", WIRES)
def test_wire_dtype_and_itemsize(wire):
    q, _ = quantize_symmetric(_vec(), wire)
    assert q.dtype == resolve_grad_dtype(wire)
    assert q.dtype.itemsize == 1  # the whole point: 1/4 of f32 bytes
    assert is_quantized_dtype(wire)
    assert is_quantized_dtype(str(resolve_grad_dtype(wire)))


def test_fp8_alias_resolution():
    """The ISSUE spells fp8 without jax's ``fn`` suffix; both resolve
    to the OCP finite-only e4m3 dtype."""
    assert resolve_grad_dtype("float8_e4m3") == jnp.dtype(jnp.float8_e4m3fn)
    assert resolve_grad_dtype("float8_e4m3fn") == \
        jnp.dtype(jnp.float8_e4m3fn)
    assert not is_quantized_dtype("bfloat16")
    assert not is_quantized_dtype(None)
    assert resolve_grad_dtype(None) is None


@pytest.mark.parametrize("wire", WIRES)
def test_scale_deterministic_across_ranks(wire):
    """Two independent quantizations of the same buffer (the cross-rank
    contract: same chunk → same codebook), eager AND under jit, agree
    bitwise."""
    v = _vec(seed=3)
    q1, s1 = quantize_symmetric(v, wire)
    q2, s2 = quantize_symmetric(jnp.asarray(np.asarray(v)), wire)
    assert float(s1) == float(s2)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    qj, sj = jax.jit(lambda x: quantize_symmetric(x, wire))(v)
    assert float(sj) == float(s1)
    np.testing.assert_array_equal(np.asarray(qj), np.asarray(q1))


@pytest.mark.parametrize("wire", WIRES)
def test_zero_buffer(wire):
    v = jnp.zeros((64,), jnp.float32)
    q, s = quantize_symmetric(v, wire)
    assert float(s) == 1.0  # never a 0/0
    np.testing.assert_array_equal(np.asarray(dequantize_symmetric(q, s)),
                                  np.zeros(64, np.float32))
    r = quantization_residual(v, q, s)
    np.testing.assert_array_equal(np.asarray(r), np.zeros(64, np.float32))


@pytest.mark.parametrize("wire", WIRES)
def test_inf_nan_handling(wire):
    """inf saturates to ±qmax·scale with the scale computed over the
    FINITE values only; NaN encodes as 0; the residual is 0 at every
    non-finite position (error feedback must not carry poison)."""
    v = jnp.asarray(np.asarray(
        [1.0, -2.0, np.inf, -np.inf, np.nan, 0.5], np.float32))
    q, s = quantize_symmetric(v, wire)
    qmax = QUANTIZED_DTYPES[wire]
    # scale derived from the finite absmax (2.0), not poisoned by inf
    assert float(s) == pytest.approx(2.0 / qmax)
    deq = np.asarray(dequantize_symmetric(q, s))
    assert np.isfinite(deq).all()
    assert deq[2] == pytest.approx(2.0, rel=0.26)   # +inf → +absmax
    assert deq[3] == pytest.approx(-2.0, rel=0.26)  # -inf → -absmax
    assert deq[4] == 0.0                            # NaN → 0
    r = np.asarray(quantization_residual(v, q, s))
    assert np.isfinite(r).all()
    assert r[2] == r[3] == r[4] == 0.0


@pytest.mark.parametrize("wire", WIRES)
def test_residual_telescopes(wire):
    """K steps of error feedback: sum of applied (dequantized) updates
    == sum of true gradients − the LAST residual, so the total applied
    error is bounded by ONE step's quantization error forever."""
    rng = np.random.RandomState(7)
    e = jnp.zeros((128,), jnp.float32)
    applied = np.zeros(128, np.float64)
    true_sum = np.zeros(128, np.float64)
    last_scale = 1.0
    for k in range(20):
        g = jnp.asarray(rng.normal(0, 1 + k % 3, 128).astype(np.float32))
        true_sum += np.asarray(g, np.float64)
        v = g + e
        q, s = quantize_symmetric(v, wire)
        applied += np.asarray(dequantize_symmetric(q, s), np.float64)
        e = quantization_residual(v, q, s)
        last_scale = float(s)
    gap = np.abs(true_sum - applied - np.asarray(e, np.float64))
    # the identity is exact up to f32 accumulation noise
    assert float(np.max(gap)) <= 1e-3 * max(1.0, last_scale * 127), wire
    # and the residual itself is one-step-sized, not K-step-sized
    qmax = QUANTIZED_DTYPES[wire]
    assert float(np.max(np.abs(np.asarray(e)))) \
        <= float(np.max(np.abs(true_sum))) * 0.5  # never accumulates


def test_residual_len_matches_transform(comm_factory=None):
    """comm.grad_residual_len agrees with the residual the transform
    actually emits, flat AND hierarchical (the zero-seed, the serialize
    template, and the hot path must agree)."""
    import chainermn_tpu as ct
    shapes = [(7,), (33,), (5, 5)]
    dtypes = [jnp.float32] * 3
    flat = ct.create_communicator("jax_ici", allreduce_grad_dtype="int8")
    assert flat.grad_residual_len(shapes, dtypes) == 7 + 33 + 25
    hier = ct.create_communicator("hierarchical", inter_size=2,
                                  allreduce_grad_dtype={"dcn": "int8"})
    # one flat bucket of 65 elems, padded to 68 (ici=4) → 17 per device
    assert hier.grad_residual_len(shapes, dtypes) == 17
    lossless = ct.create_communicator("hierarchical", inter_size=2)
    assert lossless.grad_residual_len(shapes, dtypes) == 0


def test_quantized_hop_bytes_pinned():
    """The wire-byte pricing of the quantized slow hop, unit-pinned:
    all_gather (allreduce hop) = chunk·(size−1) at 1 byte; all_to_all
    (sharded-update hop) = chunk·(size−1)/size — exactly the quantized
    fraction of the f32 reduce-scatter crossing."""
    from chainermn_tpu.communicators._memory_utility import exchanged_bytes
    chunk = 1024
    assert quantized_hop_bytes(chunk, 2, "psum", "int8") == chunk
    # f32 psum on the same chunk at inter=2: 2·4·chunk·(1/2) = 4·chunk
    assert exchanged_bytes(chunk * 4, 2, "psum") == 4 * chunk
    assert quantized_hop_bytes(chunk, 2, "psum", "int8") * 4 == \
        exchanged_bytes(chunk * 4, 2, "psum")
    # the all_to_all reduce-scatter: quantized fraction at ANY size
    for size in (2, 4, 8):
        assert quantized_hop_bytes(chunk, size, "reduce_scatter",
                                   "int8") * 4 == \
            exchanged_bytes(chunk * 4, size, "reduce_scatter")
    assert quantized_hop_bytes(chunk, 1, "psum", "int8") == 0
    with pytest.raises(ValueError):
        quantized_hop_bytes(chunk, 2, "all_gather", "int8")


def _trace_one_arg_transform(comm):
    """Trace comm.grad_transform's legacy 1-arg form inside a bound
    mesh axis (the warning fires at trace time, before any execution)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(g):
        return comm.grad_transform()({"w": g})["w"]

    jax.make_jaxpr(shard_map(
        body, mesh=comm.mesh, in_specs=(P("mn_world"),),
        out_specs=P("mn_world"), check_vma=False))(
        jnp.ones((comm.size * 8,)))


def test_legacy_one_arg_transform_warns_when_ef_inert():
    """A legacy 1-arg grad_transform call (e.g. the DCGAN updater's
    direct use) on an EF-enabled quantized communicator silently runs
    the EF-off ablation — it must warn once per process so the inert
    error_feedback=True is visible."""
    import warnings as _w
    import chainermn_tpu as ct
    from chainermn_tpu.communicators import mesh_communicator as mc
    comm = ct.create_communicator("jax_ici", allreduce_grad_dtype="int8")
    old = mc._warned_inert_ef
    try:
        mc._warned_inert_ef = False
        with pytest.warns(UserWarning, match="error feedback is inert"):
            _trace_one_arg_transform(comm)
        # once per process: second call stays quiet
        with _w.catch_warnings():
            _w.simplefilter("error")
            _trace_one_arg_transform(comm)
        # an explicit error_feedback=False ablation does not warn
        mc._warned_inert_ef = False
        quiet = ct.create_communicator("jax_ici",
                                       allreduce_grad_dtype="int8",
                                       error_feedback=False)
        with _w.catch_warnings():
            _w.simplefilter("error")
            _trace_one_arg_transform(quiet)
    finally:
        mc._warned_inert_ef = old


def test_quantized_exchange_matches_hand_mean():
    """The gather-based quantized exchange reconstructs the cross-rank
    mean of per-rank DEQUANTIZED buffers exactly (each rank's own scale
    travels with its codewords) — checked against a hand-computed
    reference on the 8-device mesh."""
    import chainermn_tpu as ct
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    comm = ct.create_communicator("jax_ici", allreduce_grad_dtype="int8")
    rng = np.random.RandomState(11)
    per_rank = rng.normal(0, 2, (comm.size, 40)).astype(np.float32)
    transform = comm.grad_transform()

    def body(g):
        return transform({"w": g})["w"]

    out = jax.jit(shard_map(
        body, mesh=comm.mesh, in_specs=(P("mn_world"),),
        out_specs=P("mn_world"), check_vma=False))(
        jnp.asarray(per_rank).reshape(comm.size * 40))
    got = np.asarray(out).reshape(comm.size, 40)[0]
    expect = np.zeros(40, np.float64)
    for r in range(comm.size):
        q, s = quantize_symmetric(jnp.asarray(per_rank[r]), "int8")
        expect += np.asarray(dequantize_symmetric(q, s), np.float64)
    expect /= comm.size
    np.testing.assert_allclose(got, expect.astype(np.float32),
                               rtol=1e-6, atol=1e-6)
