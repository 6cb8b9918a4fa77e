"""Seq2seq NMT with double-buffered allreduce (reference:
``examples/seq2seq/seq2seq.py``; BASELINE config #3) and, with
``--model-parallel``, the enc/dec split over stage ranks (config #4).
"""

import argparse

import jax.numpy as jnp
import numpy as np

import chainermn_tpu as ct
from chainermn_tpu.core.optimizer import Adam
from chainermn_tpu.dataset import SerialIterator
from chainermn_tpu.dataset.datasets import TupleDataset
from chainermn_tpu.models import (ModelParallelSeq2seq, Seq2seq,
                                  make_synthetic_translation_data)
from chainermn_tpu.training import StandardUpdater, Trainer, extensions


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batchsize", "-b", type=int, default=16)
    parser.add_argument("--epoch", "-e", type=int, default=5)
    parser.add_argument("--unit", "-u", type=int, default=64)
    parser.add_argument("--layers", "-l", type=int, default=2)
    parser.add_argument("--communicator", "-c", default="pure_nccl")
    parser.add_argument("--model-parallel", action="store_true")
    parser.add_argument("--no-double-buffering", action="store_true")
    parser.add_argument("--out", "-o", default="result_seq2seq")
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

    xs, ys_in, ys_out = make_synthetic_translation_data(n=512)
    dataset = TupleDataset(xs, ys_in, ys_out)

    if args.model_parallel:
        comm = ct.create_communicator(args.communicator, axis_name="stage")
        model = ModelParallelSeq2seq(comm, 40, 40, args.unit,
                                     n_layers=args.layers)
        optimizer = Adam().setup(model)  # stages share the mesh axis
        batch = args.batchsize
        train = dataset
    else:
        comm = ct.create_communicator(args.communicator)
        model = Seq2seq(40, 40, args.unit, n_layers=args.layers)
        comm.bcast_data(model)
        optimizer = ct.create_multi_node_optimizer(
            Adam(), comm,
            double_buffering=not args.no_double_buffering).setup(model)
        train = ct.scatter_dataset(dataset, comm, shuffle=True, seed=0)
        batch = args.batchsize * comm.size

    train_iter = SerialIterator(train, batch)
    updater = StandardUpdater(train_iter, optimizer)
    trainer = Trainer(updater, (args.epoch, "epoch"), out=args.out)
    if comm.rank == 0:
        trainer.extend(extensions.LogReport())
        trainer.extend(extensions.PrintReport(
            ["epoch", "main/loss", "elapsed_time"]))
    trainer.run()


if __name__ == "__main__":
    main()
