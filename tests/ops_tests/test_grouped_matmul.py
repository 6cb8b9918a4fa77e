"""``ops.grouped_matmul``: the schedule it computes from the group sizes
is ``megablox``'s ``make_group_metadata(..., visit_empty_groups=False)``
entry for entry, over random sizes and the ones that break a schedule
(every row in one group, no row at all, a row a group, a row tile shared
by three groups, rows behind the last group); and the product it makes
from that schedule is ``megablox.gmm``'s, both interpreted on the CPU."""

import zlib

import numpy as np
import pytest

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import (
    gmm as megablox_gmm, make_group_metadata)

from chainermn_tpu.ops.grouped_matmul import gmm, group_metadata

# (groups, rows, row tile): a decode step's one tile, Kimi's and
# Laguna's held shares at a decode bucket, SmallThinker's 64 experts, a
# prefill's tall tiles, and one group
SHAPES = [(12, 16, 16), (12, 512, 32), (16, 320, 32), (64, 192, 32),
          (12, 32768, 256), (1, 64, 16)]


def _sizes(kind, G, m, tm, rng):
    if kind == "all_in_the_first":
        return [m] + [0] * (G - 1)
    if kind == "all_in_the_last":
        return [0] * (G - 1) + [m]
    if kind == "none":
        return [0] * G
    if kind == "one_row_a_group":
        return [1] * min(G, m) + [0] * (G - min(G, m))
    if kind == "three_groups_in_a_tile":
        # groups 0, 1 and 2 all begin in the first row tile
        return ([tm // 4, tm // 4, min(tm, m - tm // 2)] + [0] * G)[:G]
    total = rng.integers(0, m + 1)      # rows behind the last group too
    skew = rng.choice([0.05, 0.5, 5.0])
    return rng.multinomial(total, rng.dirichlet(np.full(G, skew)))


@pytest.mark.parametrize("G, m, tm", SHAPES)
@pytest.mark.parametrize("kind", [
    "all_in_the_first", "all_in_the_last", "none", "one_row_a_group",
    "three_groups_in_a_tile", "random_0", "random_1", "random_2",
    "random_3"])
def test_the_schedule_is_megabloxs(G, m, tm, kind):
    rng = np.random.default_rng(zlib.crc32(f"{G} {m} {kind}".encode()))
    sizes = jnp.asarray(_sizes(kind, G, m, tm, rng), jnp.int32)
    assert int(sizes.sum()) <= m
    (offsets, group_ids, m_tile_ids), num_tiles = make_group_metadata(
        group_sizes=sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=G, visit_empty_groups=False)
    got = group_metadata(sizes, m, tm)
    for want, have in zip((offsets, group_ids, m_tile_ids, num_tiles), got):
        assert have.dtype == want.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want))


def test_a_schedule_visits_a_group_once_a_row_tile_it_reaches_into():
    """Read off by hand: 64 rows in tiles of 16; groups of 5, 3, 4 and 20
    rows, two empty ones, 32 rows behind them."""
    offsets, group_ids, m_tile_ids, num_tiles = (
        np.asarray(a) for a in group_metadata(
            jnp.asarray([5, 3, 4, 20, 0, 0], jnp.int32), 64, 16))
    assert list(offsets) == [0, 5, 8, 12, 32, 32, 32]
    assert num_tiles == 5               # the first tile thrice, the second
    assert list(group_ids[:5]) == [0, 1, 2, 3, 3]
    assert list(m_tile_ids[:5]) == [0, 0, 0, 0, 1]


@pytest.mark.parametrize("transpose_rhs", [True, False])
def test_the_product_is_megabloxs(transpose_rhs):
    """One narrow shape a transpose, two tiles along ``K`` and three
    along ``N``, an empty group in the middle, groups that end inside a
    row tile and rows behind the last: bit for bit on the groups' rows
    (the others are undefined in both)."""
    rng = np.random.default_rng(11)
    G, K, N, m, tm = 4, 256, 384, 96, 32
    sizes = jnp.asarray([10, 0, 50, 7], jnp.int32)
    lhs = jnp.asarray(rng.normal(size=(m, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(G, N, K) if transpose_rhs
                                 else (G, K, N)), jnp.bfloat16)
    want = megablox_gmm(lhs, rhs, sizes, jnp.bfloat16, (tm, 128, 128),
                        transpose_rhs=transpose_rhs, interpret=True)
    got = gmm(lhs, rhs, group_metadata(sizes, m, tm), (tm, 128, 128),
              transpose_rhs=transpose_rhs, interpret=True)
    assert got.shape == want.shape == (m, N) and got.dtype == jnp.bfloat16
    live = int(sizes.sum())
    np.testing.assert_array_equal(np.asarray(got[:live], np.float32),
                                  np.asarray(want[:live], np.float32))
    plain = np.asarray(lhs[10:60], np.float32) @ (
        np.asarray(rhs[2], np.float32).T if transpose_rhs
        else np.asarray(rhs[2], np.float32))
    np.testing.assert_allclose(np.asarray(got[10:60], np.float32), plain,
                               rtol=2e-2, atol=0.5)


def test_tiles_that_do_not_divide_are_refused():
    lhs, rhs = jnp.zeros((32, 256), jnp.bfloat16), \
        jnp.zeros((2, 256, 384), jnp.bfloat16)
    schedule = group_metadata(jnp.asarray([3, 4], jnp.int32), 32, 16)
    with pytest.raises(ValueError, match="do not divide"):
        gmm(lhs, rhs, schedule, (16, 128, 256), interpret=True)
