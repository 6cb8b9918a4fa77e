"""Data-parallel MNIST (reference: ``examples/mnist/train_mnist.py`` under
``mpiexec`` — BASELINE config #1).

Reference flow (SURVEY.md §7 step 3): create_communicator →
scatter_dataset → bcast_data → create_multi_node_optimizer (fwd/bwd/mean-
psum/update as one compiled step) → rank-0 logging →
create_multi_node_evaluator.

Run on a simulated mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python examples/train_mnist_dp.py
"""

import argparse

import chainermn_tpu as ct
from chainermn_tpu import F, L
from chainermn_tpu.core.optimizer import Adam
from chainermn_tpu.dataset import SerialIterator, get_mnist
from chainermn_tpu.training import StandardUpdater, Trainer, extensions


class MLP(ct.Chain):
    def __init__(self, n_units, n_out):
        super().__init__()
        with self.init_scope():
            self.l1 = L.Linear(None, n_units)
            self.l2 = L.Linear(None, n_units)
            self.l3 = L.Linear(None, n_out)

    def forward(self, x):
        return self.l3(F.relu(self.l2(F.relu(self.l1(x)))))


class Classifier(ct.Chain):
    def __init__(self, predictor):
        super().__init__()
        with self.init_scope():
            self.predictor = predictor

    def forward(self, x, t):
        y = self.predictor(x)
        loss = F.softmax_cross_entropy(y, t)
        ct.report({"loss": loss, "accuracy": F.accuracy(y, t)}, self)
        return loss


def main():
    parser = argparse.ArgumentParser(description="chainermn_tpu: MNIST DP")
    parser.add_argument("--batchsize", "-b", type=int, default=32,
                        help="per-rank batch size")
    parser.add_argument("--epoch", "-e", type=int, default=3)
    parser.add_argument("--unit", "-u", type=int, default=100)
    parser.add_argument("--communicator", "-c", default="jax_ici")
    parser.add_argument("--out", "-o", default="result_dp")
    parser.add_argument("--platform", default=None,
                        help="force JAX platform (e.g. 'cpu' to use the "
                             "simulated multi-device mesh)")
    parser.add_argument("--simulate-devices", type=int, default=0)
    parser.add_argument("--zero", action="store_true",
                        help="ZeRO-1: shard optimizer state over the DP "
                             "axis (reduce-scatter grads, 1/n-chunk "
                             "update, all-gather params)")
    parser.add_argument("--grad-dtype", default=None,
                        help="gradient wire dtype: bfloat16 (cast) or "
                             "int8/float8_e4m3/float8_e5m2 (quantized; "
                             "on -c hierarchical compresses the DCN hop "
                             "only — docs/performance.md §9)")
    parser.add_argument("--no-error-feedback", action="store_true",
                        help="ablation: drop the quantization residual "
                             "instead of carrying it")
    args = parser.parse_args()

    if args.simulate_devices:
        from chainermn_tpu.utils import simulate_devices
        simulate_devices(args.simulate_devices)
    if args.platform:
        from chainermn_tpu.utils import use_platform
        use_platform(args.platform)
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()

    comm = ct.create_communicator(
        args.communicator, allreduce_grad_dtype=args.grad_dtype,
        error_feedback=not args.no_error_feedback)
    model = Classifier(MLP(args.unit, 10))
    comm.bcast_data(model)

    optimizer = ct.create_multi_node_optimizer(
        Adam(), comm, zero_sharding=args.zero).setup(model)

    train, test = get_mnist()
    train = ct.scatter_dataset(train, comm, shuffle=True, seed=0)
    test = ct.scatter_dataset(test, comm, shuffle=False)

    # per-rank batchsize b → host iterator feeds the global batch b*size
    train_iter = SerialIterator(train, args.batchsize * comm.size)
    test_iter = SerialIterator(test, args.batchsize * comm.size,
                               repeat=False, shuffle=False)

    updater = StandardUpdater(train_iter, optimizer)
    trainer = Trainer(updater, (args.epoch, "epoch"), out=args.out)

    evaluator = extensions.Evaluator(test_iter, model)
    evaluator = ct.create_multi_node_evaluator(evaluator, comm)
    trainer.extend(evaluator)

    if comm.rank == 0:  # rank-0-only extension attachment (reference pattern)
        trainer.extend(extensions.LogReport())
        trainer.extend(extensions.PrintReport(
            ["epoch", "main/loss", "validation/main/loss", "main/accuracy",
             "validation/main/accuracy", "elapsed_time"]))

    trainer.run()


if __name__ == "__main__":
    main()
