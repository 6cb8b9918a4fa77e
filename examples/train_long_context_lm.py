"""Long-context transformer LM with sequence parallelism.

Beyond-reference capability (SURVEY.md §5 long-context note): the
sequence dimension is sharded over the mesh; attention runs as ring
attention (--sp-mode ring) or Ulysses (--sp-mode ulysses); all other ops
stay position-local.  Per-rank memory scales as T/n, enabling contexts n×
longer than one chip holds.
"""

import argparse
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq-len", type=int, default=1024,
                        help="global sequence length")
    parser.add_argument("--batchsize", "-b", type=int, default=2)
    parser.add_argument("--d-model", type=int, default=128)
    parser.add_argument("--n-heads", type=int, default=8)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--sp-mode", choices=["ring", "zigzag", "ulysses"],
                        default="ring",
                        help="'zigzag' = causally balanced ring schedule "
                             "(inputs are zigzag-sharded along T)")
    parser.add_argument("--remat", default=None,
                        help="per-block rematerialization: 'full' "
                             "(save nothing), 'dots' (keep GEMM outputs"
                             " — the better-MFU long-context trade), or"
                             " any jax.checkpoint_policies name")
    parser.add_argument("--platform", default=None)
    parser.add_argument("--simulate-devices", type=int, default=0)
    args = parser.parse_args()

    if args.simulate_devices:
        from chainermn_tpu.utils import simulate_devices
        simulate_devices(args.simulate_devices)
    if args.platform:
        from chainermn_tpu.utils import use_platform
        use_platform(args.platform)
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    import chainermn_tpu as ct
    from chainermn_tpu.core.link import bind_state, extract_state
    from chainermn_tpu.models.transformer import TransformerLM

    comm = ct.create_communicator("jax_ici", axis_name="seq")
    if args.seq_len % comm.size:
        raise SystemExit(f"--seq-len must be divisible by {comm.size}")

    model = TransformerLM(args.vocab, d_model=args.d_model,
                          n_heads=args.n_heads, n_layers=args.n_layers,
                          max_len=args.seq_len, sp_comm=comm,
                          sp_mode=args.sp_mode,
                          remat=args.remat or False)
    state = extract_state(model)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, args.vocab,
                                (args.batchsize, args.seq_len))
                    .astype(np.int32))
    t = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))
    if args.sp_mode == "zigzag":
        # the balanced schedule works on the two-half-chunk layout; the
        # model supplies matching position ids (TransformerLM docstring)
        from chainermn_tpu.parallel import zigzag_shard
        if args.seq_len % (2 * comm.size):
            raise SystemExit(f"--seq-len must be divisible by "
                             f"{2 * comm.size} for zigzag")
        x = zigzag_shard(x, comm.size, axis=1)
        t = zigzag_shard(t, comm.size, axis=1)

    def step(params, pstate, x, t):
        def loss_fn(p):
            with bind_state(model, {"params": p, "state": pstate}):
                return model(x, t)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "seq"), grads)
        new_params = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
        return new_params, jax.lax.pmean(loss, "seq")

    compiled = jax.jit(shard_map(
        step, mesh=comm.mesh,
        in_specs=(P(), P(), P(None, "seq"), P(None, "seq")),
        out_specs=(P(), P()), check_vma=False))

    params = state["params"]
    loss = None
    start = time.perf_counter()
    for i in range(args.steps):
        params, loss = compiled(params, state["state"], x, t)
        if i == 0:
            jax.block_until_ready(loss)
            start = time.perf_counter()  # exclude compile
    jax.block_until_ready(loss)
    dt = time.perf_counter() - start
    tokens = args.batchsize * args.seq_len * max(args.steps - 1, 1)
    print(f"mode={args.sp_mode} seq={args.seq_len} "
          f"final_loss={float(loss):.4f} "
          f"tokens/sec={tokens / dt:,.0f}")


if __name__ == "__main__":
    main()
