"""Public API surface guard.

Asserts the documented export surface (docs/api.md, PARITY.md) resolves —
a cheap tripwire against accidental API breaks in later rounds.
"""

import importlib

import chainermn_tpu as ct


TOP_LEVEL = [
    "create_communicator", "CommunicatorBase", "MeshCommunicator",
    "DummyCommunicator", "create_multi_node_optimizer",
    "create_multi_node_evaluator", "scatter_dataset", "create_empty_dataset",
    "scatter_index", "create_multi_node_iterator",
    "create_synchronized_iterator", "create_multi_node_checkpointer",
    "rescatter_dataset",
    "Parameter", "Link", "Chain", "ChainList", "Sequential",
    "report", "using_config", "F", "L",
]

MODULES = {
    "chainermn_tpu.functions": [
        "send", "recv", "pseudo_connect", "point_to_point", "allgather",
        "alltoall", "bcast", "gather", "scatter", "allreduce",
        "psum_gradient"],
    "chainermn_tpu.links": [
        "MultiNodeChainList", "MultiNodeBatchNormalization",
        "create_mnbn_model", "ParallelConvolution2D"],
    "chainermn_tpu.extensions": [
        "create_multi_node_checkpointer", "ObservationAggregator",
        "OrbaxCheckpointer",
        # round 11 (elastic, docs/resilience.md §7)
        "FailureRecovery", "RecoveryGivingUp", "ElasticRecovery",
        "ElasticConfigError", "create_elastic_membership",
        "global_batch_plan"],
    "chainermn_tpu.communicators": [
        "ElasticMembership", "MembershipView", "ElasticMeshCommunicator",
        "RankPreempted", "FaultSchedule", "FaultSpec",
        "FaultInjectionCommunicator", "multicast_tree_plan"],
    "chainermn_tpu.parallel": [
        "ring_self_attention", "ring_attention", "ulysses_attention",
        "gpipe_apply", "one_f_one_b", "make_pipeline_train_step",
        "switch_moe", "moe_dispatch_combine", "make_mesh",
        "axis_communicators", "split_microbatches", "merge_microbatches"],
    "chainermn_tpu.ops": ["attention", "flash_attention", "xla_attention",
                          "paged_decode_attention", "paged_attn_mode"],
    "chainermn_tpu.serving": [
        "ServingEngine", "Request", "RequestScheduler", "BlockAllocator",
        "PagedKVCache", "prefill_program", "decode_program",
        "write_prompt_kv", "write_token_kv", "ServingError",
        "PagePoolExhaustedError", "QueueSaturatedError",
        # round 16 (elastic serving fleet, docs/serving.md)
        "ReplicaFleet", "FleetRouter", "FleetWorker", "RemoteReplica",
        "QueueDepthScalePolicy", "fleet_mode", "NoLiveReplicaError"],
    # round 16: the fleet module itself is a documented import surface
    "chainermn_tpu.serving.fleet": [
        "ReplicaFleet", "LocalReplica", "RemoteReplica", "FleetWorker",
        "QueueDepthScalePolicy", "fleet_mode", "serialize_state",
        "deserialize_state", "FLEET_ENV", "FLEET_ROLE"],
    "chainermn_tpu.models": [
        "MLP", "Classifier", "ResNet18", "ResNet50", "ResNet101",
        "AlexNet", "NIN", "VGG16", "GoogLeNet", "Seq2seq",
        "ModelParallelSeq2seq", "Generator", "Discriminator",
        "DCGANUpdater", "TransformerLM", "MoETransformerLM"],
    "chainermn_tpu.core.optimizer": [
        "SGD", "MomentumSGD", "NesterovAG", "Adam", "AdamW", "RMSprop",
        "AdaGrad", "AdaDelta", "WeightDecay", "GradientClipping"],
    "chainermn_tpu.training.extensions": [
        "LogReport", "PrintReport", "ProgressBar", "snapshot",
        "snapshot_object", "Evaluator", "ExponentialShift", "LinearShift",
        "observe_lr", "FailOnNonNumber", "ParameterStatistics"],
    "chainermn_tpu.dataset": [
        "TupleDataset", "DictDataset", "SubDataset", "TransformDataset",
        "SerialIterator", "MultiprocessIterator", "MultithreadIterator",
        "concat_examples", "identity_converter", "get_mnist", "get_cifar10"],
    "chainermn_tpu.serializers": ["save_npz", "load_npz"],
    "chainermn_tpu.utils": ["use_platform", "simulate_devices", "trace",
                            "annotate", "Profile"],
    # round 15 (observability, docs/observability.md)
    "chainermn_tpu.observability": [
        "span", "instant", "tracer", "SpanTracer", "validate_events",
        "set_mode", "enabled", "MetricsRegistry", "Counter", "Gauge",
        "Histogram", "registry"],
}

F_FUNCTIONS = [
    "relu", "sigmoid", "tanh", "gelu", "softmax", "log_softmax",
    "softmax_cross_entropy", "sigmoid_cross_entropy", "mean_squared_error",
    "accuracy", "dropout", "linear", "embed_id", "convolution_2d",
    "deconvolution_2d", "max_pooling_2d", "average_pooling_2d",
    "unpooling_2d", "batch_normalization", "layer_normalization", "concat",
    "reshape", "select_item", "normalize", "einsum", "logsumexp"]

L_LINKS = [
    "Linear", "Convolution2D", "Deconvolution2D", "BatchNormalization",
    "GroupNormalization", "LayerNormalization", "EmbedID", "LSTM",
    "StatelessLSTM", "GRU", "StatelessGRU", "NStepLSTM", "NStepGRU",
    "Highway", "Maxout", "Scale", "Classifier"]


def test_top_level_exports():
    missing = [n for n in TOP_LEVEL if not hasattr(ct, n)]
    assert not missing, missing


def test_module_exports():
    problems = []
    for mod_name, names in MODULES.items():
        mod = importlib.import_module(mod_name)
        for n in names:
            if getattr(mod, n, None) is None:
                problems.append(f"{mod_name}.{n}")
    assert not problems, problems


def test_F_and_L_surfaces():
    missing = [n for n in F_FUNCTIONS if not hasattr(ct.F, n)]
    missing += [f"L.{n}" for n in L_LINKS if getattr(ct.L, n, None) is None]
    assert not missing, missing


def test_communicator_names_accepted():
    for name in ("naive", "flat", "hierarchical", "two_dimensional",
                 "single_node", "non_cuda_aware", "pure_nccl", "jax_ici",
                 "dummy", "debug"):
        assert ct.create_communicator(name) is not None


def test_communicators_all_resolves_and_holds_no_harness_table():
    """Every name ``communicators.__all__`` promises exists, and the
    package exports no table for a measurement script to share
    (``EXCHANGES`` / ``exchange_knobs`` went with the scripts, PR 29)."""
    mod = importlib.import_module("chainermn_tpu.communicators")
    assert [n for n in mod.__all__ if getattr(mod, n, None) is None] == []
    assert not {"EXCHANGES", "exchange_knobs"} & set(mod.__all__)
    assert not hasattr(mod, "exchange_knobs")
