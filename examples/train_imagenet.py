"""Data-parallel ImageNet ResNet-50 (reference:
``examples/imagenet/train_imagenet.py``; BASELINE config #2).

Synthetic ImageNet-shaped data (no network on this box); the input
pipeline shards per host via ``scatter_dataset`` and the compiled step
shards the batch across chips.
"""

import argparse

import jax.numpy as jnp

import chainermn_tpu as ct
from chainermn_tpu.core.optimizer import MomentumSGD
from chainermn_tpu.dataset import SerialIterator, MultithreadIterator
from chainermn_tpu.dataset.datasets import get_synthetic_imagenet
from chainermn_tpu.models import (AlexNet, Classifier, GoogLeNet, NIN,
                                  ResNet50, VGG16)
from chainermn_tpu.training import StandardUpdater, Trainer, extensions


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batchsize", "-b", type=int, default=32,
                        help="per-chip batch size")
    parser.add_argument("--arch", "-a", default="resnet50",
                        choices=["resnet50", "alex", "nin", "vgg16",
                                 "googlenet"])
    parser.add_argument("--epoch", "-e", type=int, default=1)
    parser.add_argument("--iterations", type=int, default=0,
                        help="stop after N iterations (overrides --epoch)")
    parser.add_argument("--size", type=int, default=224)
    parser.add_argument("--n-train", type=int, default=512)
    parser.add_argument("--communicator", "-c", default="pure_nccl")
    parser.add_argument("--grad-dtype", default="bfloat16")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialize ResNet stages (larger batches)")
    parser.add_argument("--layout", default="NHWC",
                        choices=["NHWC", "NCHW"],
                        help="activation layout (NHWC = TPU-native "
                             "channels-last; resnet50 only)")
    parser.add_argument("--device-prefetch", type=int, default=2,
                        help="batches kept resident in HBM ahead of the "
                             "step (0 disables the device-feed stage)")
    parser.add_argument("--out", "-o", default="result_imagenet")
    parser.add_argument("--platform", default=None)
    parser.add_argument("--simulate-devices", type=int, default=0)
    parser.add_argument("--mnbn", action="store_true",
                        help="rewrite BatchNormalization links to the "
                             "multi-node (sync) variant — the reference "
                             "recipe for small per-device batches, where "
                             "local BN statistics degenerate")
    parser.add_argument("--lr", type=float, default=None,
                        help="initial lr (default: 0.1 for resnet50, "
                             "whose BN tames it; 0.01 for the BN-less "
                             "archs per the reference recipes)")
    parser.add_argument("--fused", type=int, default=0,
                        help="fuse K optimizer steps per dispatch "
                             "(FusedUpdater/update_scan; 0 = per-step)")
    parser.add_argument("--zero", action="store_true",
                        help="ZeRO-1 sharded optimizer state: "
                             "reduce-scatter grads, 1/n-chunk momentum "
                             "+ update, all-gather params — same "
                             "trajectory as plain DP, 1/n state memory")
    parser.add_argument("--uint8-input", action="store_true",
                        help="ship raw uint8 pixels and normalize "
                             "IN-GRAPH on device (any arch): the host "
                             "f32 cast is what bounds a one-core input "
                             "pipeline, the uint8 gather is not")
    parser.add_argument("--native-loader", action="store_true",
                        help="deprecated alias for --loader native")
    parser.add_argument("--loader", default=None,
                        choices=["thread", "native", "multiprocess"],
                        help="host batch assembly: thread "
                             "(MultithreadIterator, GIL-releasing "
                             "transforms), native (C++ gather engine "
                             "over plain arrays), multiprocess "
                             "(process pool + shared-memory slots — "
                             "the escape hatch for GIL-bound Python "
                             "transforms; docs/input_pipeline.md)")
    parser.add_argument("--loader-workers", type=int, default=4,
                        help="worker processes for --loader "
                             "multiprocess")
    args = parser.parse_args()
    if args.native_loader and args.loader not in (None, "native"):
        parser.error("--native-loader conflicts with "
                     f"--loader {args.loader}")
    args.loader = args.loader or \
        ("native" if args.native_loader else "thread")

    if args.simulate_devices:
        from chainermn_tpu.utils import simulate_devices
        simulate_devices(args.simulate_devices)
    if args.platform:
        from chainermn_tpu.utils import use_platform
        use_platform(args.platform)
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()

    comm = ct.create_communicator(args.communicator,
                                  allreduce_grad_dtype=args.grad_dtype)
    inorm = "imagenet" if args.uint8_input else None
    archs = {"resnet50": lambda: ResNet50(
                 compute_dtype=jnp.bfloat16, remat=args.remat,
                 layout=args.layout, input_norm=inorm),
             "alex": lambda: AlexNet(input_norm=inorm),
             "nin": lambda: NIN(input_norm=inorm),
             "vgg16": lambda: VGG16(input_norm=inorm),
             "googlenet": lambda: GoogLeNet(input_norm=inorm)}
    nhwc = args.arch == "resnet50" and args.layout == "NHWC"
    model = Classifier(archs[args.arch]())
    if args.mnbn:
        model = ct.links.create_mnbn_model(model, comm)
    comm.bcast_data(model)
    lr = args.lr if args.lr is not None \
        else (0.1 if args.arch == "resnet50" else 0.01)
    optimizer = ct.create_multi_node_optimizer(
        MomentumSGD(lr=lr, momentum=0.9), comm,
        zero_sharding=args.zero).setup(model)
    optimizer.add_hook(ct.core.WeightDecay(1e-4))

    train = get_synthetic_imagenet(
        n=args.n_train, size=args.size,
        dtype="uint8" if args.uint8_input else "float32")
    if nhwc:
        from chainermn_tpu.dataset import TransformDataset
        train = TransformDataset(
            train, lambda ex: (ex[0].transpose(1, 2, 0), ex[1]))
    train = ct.scatter_dataset(train, comm, shuffle=True, seed=0)

    from chainermn_tpu.dataset import concat_examples, identity_converter
    converter = concat_examples  # both updaters' default
    if args.loader == "native":
        # C++ gather engine over the materialized local shard: batches
        # arrive pre-stacked (x, t) tuples, so downstream converters are
        # identity.  With --uint8-input the rows stay uint8 end to end
        # and the cast happens in-graph on device.
        from chainermn_tpu.dataset import NativeBatchIterator
        xs, ys = concat_examples([train[i] for i in range(len(train))])
        train_iter = NativeBatchIterator((xs, ys),
                                         args.batchsize * comm.size,
                                         seed=0)
        converter = identity_converter
    elif args.loader == "multiprocess":
        # process pool + shared-memory slots: per-example work (the
        # TransformDataset above included) runs in worker processes —
        # the reference MultiprocessIterator path for GIL-bound
        # transforms (docs/input_pipeline.md)
        from chainermn_tpu.dataset import MultiprocessIterator
        train_iter = MultiprocessIterator(train,
                                          args.batchsize * comm.size,
                                          n_processes=args.loader_workers,
                                          as_arrays=True, seed=0)
        converter = identity_converter
    else:
        train_iter = MultithreadIterator(train,
                                         args.batchsize * comm.size)

    if args.device_prefetch and not args.fused:
        # device-feed stage: a feeder thread converts and device_puts
        # the next batch while this step computes (overlapped H2D;
        # FusedUpdater stacks K batches itself, so per-batch prefetch
        # placement doesn't apply there)
        from chainermn_tpu.dataset import DevicePrefetchIterator
        train_iter = DevicePrefetchIterator(
            train_iter, size=args.device_prefetch,
            converter=concat_examples if args.loader == "thread"
            else None)
        converter = identity_converter

    if args.fused:
        from chainermn_tpu.training import FusedUpdater
        updater = FusedUpdater(train_iter, optimizer, n_fused=args.fused,
                               converter=converter)
    else:
        updater = StandardUpdater(train_iter, optimizer,
                                  converter=converter)
    stop = (args.iterations, "iteration") if args.iterations \
        else (args.epoch, "epoch")
    trainer = Trainer(updater, stop, out=args.out)
    if comm.rank == 0:
        trainer.extend(extensions.LogReport(trigger=(10, "iteration")))
        trainer.extend(extensions.PrintReport(
            ["epoch", "iteration", "main/loss", "main/accuracy",
             "elapsed_time"]))
    trainer.run()


if __name__ == "__main__":
    main()
