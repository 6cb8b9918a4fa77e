"""A grouped matrix product whose tile schedule is an OPERAND.

``lhs [M, K]`` holds its rows sorted by group; group ``g``'s rows are
multiplied by ``rhs[g]``.  The kernel is the forward grouped matmul that
ships with JAX (``jax.experimental.pallas.ops.tpu.megablox.gmm``): the
same tiles, the same grid over the ACTIVE row tiles alone (a group with
no rows is never visited, so its matrix is never read), the same store
mask where a tile of rows is shared by several groups.  What differs is
where the schedule comes from.  ``megablox.gmm`` derives it from the
group sizes inside every call (``make_group_metadata``: a cumulative sum,
two ``repeat``s that are each a ``searchsorted``, a histogram, two
rolls), so a gated expert layer, whose ``gate``, ``up`` and ``down`` are
grouped over the SAME sizes, rows and row tile, traces, lowers and runs
it once for every distinct static signature of the call: twice a layer
body, 0.24 of the 0.38 s that body took to trace for Kimi's widths
(PERF.md section 6, PR 48).  Here :func:`group_metadata` computes it once,
from two comparisons of ``[grid, groups]``, and :func:`gmm` takes it as
it takes ``lhs``.

Nothing else of ``megablox.gmm``'s is kept: no ``group_offset``, no
``existing_out``, and the tiles divide ``K`` and ``N`` (the caller's
``_tiles`` chooses them so), so no remainder is masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["group_metadata", "gmm"]


@functools.partial(jax.jit, static_argnames=("m", "tm"))
def group_metadata(sizes, m, tm):
    """The schedule of a grouped product over ``m`` rows in tiles of
    ``tm`` (which divides ``m``), from the groups' ``sizes [G] int32``
    (``sum(sizes) <= m``): ``(group_offsets [G + 1], group_ids [L],
    m_tile_ids [L], num_tiles)`` with ``L = m // tm + G - 1``, all int32.
    Grid step ``i < num_tiles`` multiplies row tile ``m_tile_ids[i]`` by
    group ``group_ids[i]``'s matrix and keeps the rows ``group_offsets[g]
    .. group_offsets[g + 1] - 1`` of it.  A group is visited once for
    each row tile it reaches into and an empty group not at all; the
    steps are in the order of the rows, so a row tile shared by several
    groups is visited by them consecutively.

    Equal, entry for entry, to ``megablox``'s ``make_group_metadata(...,
    visit_empty_groups=False)`` (``tests/ops_tests/
    test_grouped_matmul.py``), the entries behind ``num_tiles`` too: the
    last group's id, and the row tiles counted on to the last."""
    G, tiles_m = sizes.shape[0], m // tm
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    # the row tiles a group reaches into, and the grid step of its first
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 0)
    last = jnp.cumsum(tiles, dtype=jnp.int32)
    first = last - tiles
    # a group that starts inside a row tile visits that tile AGAIN, so
    # from its first step on the row tiles lag the grid by one more
    again = (sizes > 0) & (starts % tm != 0)
    step = jnp.arange(tiles_m + G - 1, dtype=jnp.int32)[:, None]
    group_ids = jnp.minimum(
        jnp.sum(last <= step, axis=1, dtype=jnp.int32), G - 1)
    m_tile_ids = jnp.minimum(
        step[:, 0] - jnp.sum(again & (first <= step), axis=1,
                             dtype=jnp.int32), tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, group_ids, m_tile_ids, last[-1]


@functools.partial(jax.jit,
                   static_argnames=("tiling", "transpose_rhs", "interpret"))
def gmm(lhs, rhs, metadata, tiling, transpose_rhs=False, interpret=False):
    """``lhs [M, K]`` times each group's own matrix of ``rhs`` (``[G, K,
    N]``, or ``[G, N, K]`` with ``transpose_rhs``) by ``metadata``
    (:func:`group_metadata` at ``tiling[0]``): ``[M, N]`` in ``lhs``'s
    dtype, products accumulated in float32; the rows of no group are
    undefined.  ``tiling = (tm, tk, tn)`` divides ``(M, K, N)``."""
    group_offsets, group_ids, m_tile_ids, num_tiles = metadata
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiles {tiling} do not divide {(m, k, n)}")
    tiles_k, tiles_n = k // tk, n // tn
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def kernel(group_offsets, group_ids, m_tile_ids, lhs, rhs, out, acc):
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += lax.dot_general(lhs[...], rhs[...], contract,
                                    preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _():
            # the rows of this tile that are this group's; the others
            # keep what the tile's earlier visits stored
            group = group_ids[step]
            row = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) \
                + m_tile_ids[step] * tm
            mine = (row >= group_offsets[group]) \
                & (row < group_offsets[group + 1])
            out[...] = lax.select(mine, acc[...],
                                  out[...].astype(jnp.float32)) \
                .astype(out.dtype)

    def lhs_tile(n_i, step, k_i, group_offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], k_i

    def rhs_tile(n_i, step, k_i, group_offsets, group_ids, m_tile_ids):
        return (group_ids[step],) + ((n_i, k_i) if transpose_rhs
                                     else (k_i, n_i))

    def out_tile(n_i, step, k_i, group_offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], n_i

    # what the compiler is told of the cost is ``megablox.gmm``'s: a
    # group's matrix read once for each step of the schedule's length
    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=lhs.size * lhs.itemsize * tiles_n
        + k * n * rhs.itemsize * group_ids.size + m * n * lhs.itemsize)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_tile),
                      pl.BlockSpec((None, tn, tk) if transpose_rhs
                                   else (None, tk, tn), rhs_tile)],
            out_specs=pl.BlockSpec((tm, tn), out_tile),
            grid=(tiles_n, num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, cost_estimate=cost,
    )(group_offsets, group_ids, m_tile_ids, lhs, rhs)
