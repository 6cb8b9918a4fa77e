"""1F1B pipeline schedule — O(S) activation memory.

GPipe (``parallel.pipeline``) keeps all M microbatch activations alive
until backward; 1F1B interleaves each stage's backward with later
microbatches' forwards so at most O(S) activations are in flight —
the schedule that makes deep pipelines memory-feasible (beyond the
reference, whose pipeline is sequential per minibatch, SURVEY §3.3).

JAX's AD cannot be told to reorder its backward, so this module *is* the
backward: one ``lax.scan`` over ``M + 2S - 1`` ticks where every tick a
stage may run one forward (storing only the stage *input* in a ring
buffer) and one backward (``jax.vjp`` recomputes the stage from the
stored input — activation rematerialization — and pulls the cotangent
back).  Activations ride ``ppermute`` forward, cotangents ride the
reversed ``ppermute``; gradients accumulate per-rank for that rank's
stage parameters.

Tick algebra: fwd of microbatch ``i`` on stage ``s`` at tick ``i + s``;
bwd at tick ``i + 2S - 1 - s``; input lifetime ``2(S - s) - 1`` ticks →
ring capacity ``2S`` suffices for every stage.

Returns ``(mean_loss, stage_grads)`` — a gradient function, not a
differentiable forward (it replaces ``jax.grad`` for the pipeline).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["one_f_one_b", "make_pipeline_train_step",
           "heterogeneous_stage_fn"]


def heterogeneous_stage_fn(stage_fns, axis_name):
    """Combine per-stage callables into one SPMD ``stage_fn``.

    The 1F1B schedule is one compiled SPMD program, so every rank runs
    the same code; per-stage *computation* differences are expressed as
    a ``lax.switch`` over the stage index (all branches trace, the
    device executes its own).  Constraints that remain (and are checked
    at trace time by JAX itself): every stage shares one parameter-tree
    structure and the activation shape is uniform across stage
    boundaries (``ppermute`` requires it).  Truly heterogeneous
    graphs — different shapes or parameter structures per stage —
    belong to ``MultiNodeChainList`` (reference semantics, SURVEY §3.3).

    Trace cost: the tick loop is a ``lax.scan``, so the ``lax.switch``
    body — and with it all ``S`` branches — is traced ONCE (plus once
    for its VJP), independent of tick count: O(S) traced stage bodies
    total.  Run time executes one branch per tick per device.  The cost
    of heterogeneity is therefore program SIZE linear in S, not a
    quadratic compile blow-up.
    """
    def stage_fn(params, h):
        branches = [lambda p, hh, f=f: f(p, hh) for f in stage_fns]
        s = lax.axis_index(axis_name)
        return lax.switch(s, branches, params, h)
    return stage_fn


def one_f_one_b(comm, stage_fn, loss_fn, stage_params, x_microbatches,
                y_microbatches):
    """Run the 1F1B schedule inside ``shard_map`` over ``comm``'s axis.

    ``stage_fn(params, h) -> h`` (shape-preserving, same code per stage —
    homogeneous pipelines; heterogeneous graphs belong to
    ``MultiNodeChainList``).  ``loss_fn(out, y) -> scalar`` evaluated on
    the last stage per microbatch.  ``x_microbatches``: [M, mb, ...]
    replicated; ``y_microbatches``: [M, ...] replicated targets.

    Returns ``(loss, grads)``: mean per-microbatch loss (replicated) and
    this rank's stage-parameter gradients (d mean-loss / d params_s).
    """
    axis = comm.axis_name
    S = comm.size
    stage = lax.axis_index(axis)
    M = x_microbatches.shape[0]
    mb_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype
    perm_fwd = [(i, (i + 1) % S) for i in range(S)]
    perm_bwd = [((i + 1) % S, i) for i in range(S)]
    RING = 2 * S
    T = M + 2 * S - 1

    def tick(carry, t):
        ring, fwd_msg, bwd_msg, grad_acc, loss_acc = carry

        # ---- forward half: stage s computes microbatch f = t - s -------
        f = t - stage
        f_valid = (f >= 0) & (f < M)
        feed = lax.dynamic_index_in_dim(x_microbatches,
                                        jnp.clip(f, 0, M - 1), 0, False)
        act_in = jnp.where(stage == 0, feed, fwd_msg)
        # invalid ticks run stage_fn anyway; give them real microbatch
        # data, not the rotating zeros, so a stage singular at 0 (|h|,
        # sqrt, 1/h) never evaluates at the singular point — keeps
        # jax_debug_nans clean (same hardening as gpipe_apply)
        act_in = jnp.where(f_valid, act_in, feed)
        out = stage_fn(stage_params, act_in)
        # store the stage input for backward-time recomputation
        ring = jnp.where(
            f_valid,
            lax.dynamic_update_index_in_dim(ring, act_in, f % RING, 0),
            ring)
        fwd_send = jnp.where(f_valid, out, jnp.zeros(mb_shape, dtype))

        # ---- backward half: stage s backs microbatch b ------------------
        b = t - (2 * S - 1 - stage)
        b_valid = (b >= 0) & (b < M)
        act_saved = lax.dynamic_index_in_dim(
            ring, jnp.clip(b, 0, M - 1) % RING, 0, False)
        # same hardening for the recompute-VJP: never evaluate pullback
        # on an all-zeros ring slot (warmup) where the stage may be
        # singular — a NaN there would survive the 0-gate (0 × NaN = NaN)
        act_saved = jnp.where(b_valid, act_saved, feed)
        out_b, pullback = jax.vjp(lambda p, a: stage_fn(p, a),
                                  stage_params, act_saved)
        y_b = lax.dynamic_index_in_dim(y_microbatches,
                                       jnp.clip(b, 0, M - 1), 0, False)
        # last stage seeds the cotangent from the loss; others receive it
        loss_b, cot_from_loss = jax.value_and_grad(
            lambda o: loss_fn(o, y_b))(out_b)
        is_last = stage == S - 1
        cot = jnp.where(is_last, cot_from_loss, bwd_msg)
        dparams, dact = pullback(cot)
        gate = (b_valid).astype(jnp.float32)
        grad_acc = jax.tree.map(
            lambda acc, g: acc + gate * g.astype(acc.dtype),
            grad_acc, dparams)
        loss_acc = loss_acc + gate * jnp.where(is_last, loss_b, 0.0)
        bwd_send = jnp.where(b_valid, dact, jnp.zeros(mb_shape, dtype))

        # ---- neighbor exchanges (uniform collectives every tick) --------
        fwd_next = lax.ppermute(fwd_send, axis, perm_fwd)
        bwd_next = lax.ppermute(bwd_send, axis, perm_bwd)
        return (ring, fwd_next, bwd_next, grad_acc, loss_acc), None

    ring0 = jnp.zeros((RING,) + mb_shape, dtype)
    zeros_mb = jnp.zeros(mb_shape, dtype)
    grad0 = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                         stage_params)
    (ring, _, _, grads, loss_sum), _ = lax.scan(
        tick, (ring0, zeros_mb, zeros_mb, grad0, jnp.float32(0.0)),
        jnp.arange(T))
    # loss lives on the last stage; share it (replication-aware scaling:
    # the grads here are true per-stage grads already — no redundant-loss
    # accumulation happened because each cotangent entered exactly once)
    loss = lax.psum(jnp.where(stage == S - 1, loss_sum, 0.0), axis) / M
    grads = jax.tree.map(lambda g: g / M, grads)
    return loss, grads


def make_pipeline_train_step(comm, stage_fn, loss_fn, tx, n_microbatches):
    """Build a jitted 1F1B training step integrated with an optax
    transform: ``step(stage_params, opt_state, x, y) -> (params,
    opt_state, loss)``.

    ``stage_params`` is the stacked [S, ...] tree sharded ``P(axis)`` on
    the leading dim; batches are replicated and split into microbatches
    internally.  The whole schedule + update compiles to one program —
    the pipeline counterpart of ``create_multi_node_optimizer``'s DP step.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from .pipeline import split_microbatches
    axis = comm.axis_name

    def rank_step(params_stacked, opt_state, x, y):
        params = jax.tree.map(lambda p: p[0], params_stacked)
        xm = split_microbatches(x, n_microbatches)
        ym = split_microbatches(y, n_microbatches)
        loss, grads = one_f_one_b(comm, stage_fn, loss_fn, params, xm, ym)
        updates, new_opt_state = tx.update(
            jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params),
            opt_state, params)
        new_params = jax.tree.map(lambda p, u: p + u, params, updates)
        return (jax.tree.map(lambda p: p[None], new_params),
                new_opt_state, loss)

    p_stage = P(axis)
    mapped = shard_map(
        rank_step, mesh=comm.mesh,
        in_specs=(p_stage, P(), P(), P()),
        out_specs=(p_stage, P(), P()),
        check_vma=False)
    return jax.jit(mapped)
