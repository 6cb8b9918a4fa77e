"""Data-parallel CIFAR DCGAN (reference: ``examples/dcgan/train_dcgan.py``;
BASELINE config #5): multi-node optimizers for both nets, multi-node
evaluator-style generated-sample statistics, bcast + distributed
checkpointing.
"""

import argparse

import numpy as np

import chainermn_tpu as ct
from chainermn_tpu.core.optimizer import Adam
from chainermn_tpu.dataset import SerialIterator
from chainermn_tpu.dataset.datasets import get_cifar10
from chainermn_tpu.models import DCGANUpdater, Discriminator, Generator
from chainermn_tpu.training import Trainer, extensions


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batchsize", "-b", type=int, default=16)
    parser.add_argument("--epoch", "-e", type=int, default=2)
    parser.add_argument("--n-hidden", type=int, default=64)
    parser.add_argument("--ch", type=int, default=64)
    parser.add_argument("--communicator", "-c", default="pure_nccl")
    parser.add_argument("--out", "-o", default="result_dcgan")
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

    comm = ct.create_communicator(args.communicator)
    gen = Generator(n_hidden=args.n_hidden, ch=args.ch)
    dis = Discriminator(ch=args.ch)
    comm.bcast_data(gen)
    comm.bcast_data(dis)
    opt_gen = ct.create_multi_node_optimizer(
        Adam(alpha=2e-4, beta1=0.5), comm).setup(gen)
    opt_dis = ct.create_multi_node_optimizer(
        Adam(alpha=2e-4, beta1=0.5), comm).setup(dis)

    train, _ = get_cifar10(withlabel=False, n_train=512)
    train = ct.scatter_dataset(train, comm, shuffle=True, seed=0)
    train_iter = SerialIterator(train, args.batchsize * comm.size)

    updater = DCGANUpdater(train_iter, opt_gen, opt_dis)
    trainer = Trainer(updater, (args.epoch, "epoch"), out=args.out)
    checkpointer = ct.create_multi_node_checkpointer(comm, name="dcgan")
    trainer.extend(checkpointer, trigger=(1, "epoch"))
    resumed = checkpointer.maybe_load(trainer, path=args.out)
    if resumed and comm.rank == 0:
        print(f"resumed from iteration {resumed}")
    if comm.rank == 0:
        trainer.extend(extensions.LogReport(trigger=(10, "iteration")))
        trainer.extend(extensions.PrintReport(
            ["epoch", "iteration", "gen/loss", "dis/loss", "elapsed_time"]))
    trainer.run()


if __name__ == "__main__":
    main()
