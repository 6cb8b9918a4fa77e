"""Model-parallel MNIST (reference:
``examples/mnist/train_mnist_model_parallel.py``): the MLP split across
two stage ranks via MultiNodeChainList.
"""

import argparse

import chainermn_tpu as ct
from chainermn_tpu import F, L
from chainermn_tpu.core.optimizer import Adam
from chainermn_tpu.dataset import SerialIterator, get_mnist
from chainermn_tpu.links import MultiNodeChainList
from chainermn_tpu.training import StandardUpdater, Trainer, extensions


class MLP0(ct.Chain):
    def __init__(self, n_units):
        super().__init__()
        with self.init_scope():
            self.l1 = L.Linear(784, n_units)
            self.l2 = L.Linear(n_units, n_units)

    def forward(self, x, t):
        return F.relu(self.l2(F.relu(self.l1(x))))


class MLP1(ct.Chain):
    def __init__(self, n_units, n_out):
        super().__init__()
        with self.init_scope():
            self.l3 = L.Linear(n_units, n_out)

    def forward(self, h, x, t):
        y = self.l3(h)
        loss = F.softmax_cross_entropy(y, t)
        return loss


class SplitMLP(MultiNodeChainList):
    def __init__(self, comm, n_units, n_out):
        super().__init__(comm)
        self.add_link(MLP0(n_units), rank_in=None, rank_out=1, rank=0)
        self.add_link(MLP1(n_units, n_out), rank_in=0, rank_out=None,
                      rank=1, pass_inputs=True)

    def forward(self, x, t):
        loss = super().forward(x, t)
        ct.report({"loss": loss}, self)
        return loss


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batchsize", "-b", type=int, default=100)
    parser.add_argument("--epoch", "-e", type=int, default=3)
    parser.add_argument("--unit", "-u", type=int, default=100)
    parser.add_argument("--out", "-o", default="result_mp")
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

    comm = ct.create_communicator("jax_ici", axis_name="stage")
    model = SplitMLP(comm, args.unit, 10)
    optimizer = Adam().setup(model)

    train, _ = get_mnist()
    train_iter = SerialIterator(train, args.batchsize)
    updater = StandardUpdater(train_iter, optimizer)
    trainer = Trainer(updater, (args.epoch, "epoch"), out=args.out)
    if comm.rank == 0:
        trainer.extend(extensions.LogReport())
        trainer.extend(extensions.PrintReport(
            ["epoch", "main/loss", "elapsed_time"]))
    trainer.run()


if __name__ == "__main__":
    main()
