"""MNIST MLP training example (reference: ``examples/mnist/train_mnist.py``).

Single-process version; the data-parallel sibling is
``examples/train_mnist_dp.py`` (communicator + multi-node optimizer).
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
        h1 = F.relu(self.l1(x))
        h2 = F.relu(self.l2(h1))
        return self.l3(h2)


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
    parser = argparse.ArgumentParser(description="chainermn_tpu: MNIST")
    parser.add_argument("--batchsize", "-b", type=int, default=100)
    parser.add_argument("--epoch", "-e", type=int, default=5)
    parser.add_argument("--unit", "-u", type=int, default=100)
    parser.add_argument("--out", "-o", default="result")
    parser.add_argument("--resume", "-r", default="")
    parser.add_argument("--platform", default=None,
                        help="force JAX platform (e.g. 'cpu'); env-var "
                             "pinning is unreliable on hosted TPU images")
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

    model = Classifier(MLP(args.unit, 10))
    optimizer = Adam().setup(model)

    train, test = get_mnist()
    train_iter = SerialIterator(train, args.batchsize)
    test_iter = SerialIterator(test, args.batchsize, repeat=False,
                               shuffle=False)

    updater = StandardUpdater(train_iter, optimizer)
    trainer = Trainer(updater, (args.epoch, "epoch"), out=args.out)
    trainer.extend(extensions.Evaluator(test_iter, model))
    trainer.extend(extensions.LogReport())
    trainer.extend(extensions.PrintReport(
        ["epoch", "main/loss", "validation/main/loss", "main/accuracy",
         "validation/main/accuracy", "elapsed_time"]))
    trainer.extend(extensions.snapshot(), trigger=(args.epoch, "epoch"))

    if args.resume:
        from chainermn_tpu.serializers import load_npz
        load_npz(args.resume, trainer)

    trainer.run()


if __name__ == "__main__":
    main()
