"""Driver ``train``: a model under the library's data-parallel trainer
path, ``create_communicator`` -> ``create_multi_node_optimizer`` ->
``SerialIterator`` -> ``StandardUpdater.update`` (the call
``Trainer.run`` makes each iteration), the loss fetched every
``fetch_every`` steps as ``LogReport`` would.

Set-up builds one updater with its compiled step and state, drives it
from the seed through its first three steps (whose losses, first
gradient and parameter change are kept for the check) and hands the same
object to the window.  The plain reference follows those three steps
after the window has closed and the program's state is freed.
"""

from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np

from .. import flops, harness, tracing, weights
from ..models import _init

CHECK_STEPS = 3


def make_dataset(data, config, n_rows, seed):
    """Host arrays ``(inputs, targets)`` of ``n_rows`` rows that all
    differ, from the seed."""
    rng = np.random.default_rng(seed)
    if data["kind"] == "lm_tokens":
        T = data["seq_len"]
        seq = rng.integers(0, config["vocab_size"], (n_rows, T + 1),
                           dtype=np.int32)
        return seq[:, :-1].copy(), seq[:, 1:].copy()
    if data["kind"] == "images":
        s = config["image_size"]
        x = rng.standard_normal((n_rows, s, s, 3), dtype=np.float32)
        t = rng.integers(0, config["num_classes"], n_rows, dtype=np.int32)
        return x, t
    raise harness.BenchmarkError(f"unknown data kind {data['kind']!r}")


def make_optimizer(spec):
    from chainermn_tpu.core import optimizer as O
    if spec["name"] == "adam":
        return O.Adam(alpha=spec["alpha"])
    if spec["name"] == "momentum_sgd":
        return O.MomentumSGD(lr=spec["lr"], momentum=spec["momentum"])
    raise harness.BenchmarkError(f"unknown optimizer {spec['name']!r}")


def leaf_norms(tree):
    """``{path: l2 norm}`` in one device call."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        t))(tree)
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def diff_norms(a, b):
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))(a, b)
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def leaf_gaps(got, want):
    """For each leaf the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero).
    The median is over the leaves whose reference norm is not exactly
    zero: behind a BN gain that starts at 0 a whole block's gradients
    are, and would drag the median to nothing."""
    nonzero = [w for w in want.values() if w > 0]
    floor = statistics.median(nonzero) if nonzero else 0.0
    gaps = {k: abs(got[k] - w) / max(w, floor, 1e-30)
            for k, w in want.items()}
    return {k: g if math.isfinite(g) else float("inf")
            for k, g in gaps.items()}


def worst_leaf_gap(got, want):
    """(the widest leaf gap, its leaf)."""
    gaps = leaf_gaps(got, want)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def median_leaf_gap(got, want):
    """The median leaf gap: steadier from seed to seed than the widest,
    which in a network with batch normalisation sits on whichever
    near-cancelling BN gradient the seed happens to give.  Over the
    leaves whose reference norm is not exactly zero."""
    gaps = leaf_gaps(got, want)
    return statistics.median([g for k, g in gaps.items() if want[k] > 0]
                             or [0.0])


class Program:
    """The system under test: one updater, its compiled step, its state."""

    def __init__(self, run):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        import chainermn_tpu as ct
        from chainermn_tpu.core import reporter
        from chainermn_tpu.training import StandardUpdater

        job = run.traffic
        self.run = run
        self.comm = ct.create_communicator("jax_ici", devices=run.devices)
        builder = harness.load_module("models", run.config["builder"])
        self.model = builder.build(run.config)
        self.spec = _init.param_spec(self.model, builder.init_rule)
        self.replicated = NamedSharding(self.comm.mesh, PartitionSpec())
        _init.load(self.model, weights.make_params(
            self.spec, run.seed, self.replicated))
        self.comm.bcast_data(self.model)
        self.opt = ct.create_multi_node_optimizer(
            make_optimizer(job["optimizer"]), self.comm).setup(self.model)
        self.global_batch = job["per_chip_batch"] * len(run.devices)
        n_rows = job["dataset_batches"] * self.global_batch
        self.data = make_dataset(job["data"], run.config, n_rows, run.seed)
        iterator = ct.SerialIterator(ct.TupleDataset(*self.data),
                                     self.global_batch, repeat=True,
                                     shuffle=False)
        self.updater = StandardUpdater(iterator, self.opt)
        self.reporter = reporter.Reporter()
        self.reporter.add_observer("main", self.model)
        self.reporter.add_observers(
            "main", self.model.namedlinks(skipself=True))
        self.losses = []
        self.dispatch_ms = []
        self.steps = 0
        self._last_loss = None

    def step(self, fetch):
        """One ``updater.update()``; with ``fetch`` the loss is brought to
        the host, which waits for the step."""
        import jax
        obs = {}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/update"):
            with self.reporter.scope(obs):
                self.updater.update()
        self.dispatch_ms.append((time.perf_counter() - t0) * 1e3)
        self.steps += 1
        self._last_loss = obs["main/loss"]
        return self.fetch_last() if fetch else None

    def fetch_last(self):
        """The last step's loss on the host: waits for that step."""
        import jax
        if self._last_loss is not None:
            with jax.profiler.TraceAnnotation("bench/loss_fetch"):
                self.losses.append(float(self._last_loss))
            self._last_loss = None
        return self.losses[-1]

    def params(self):
        return {path: p.array for path, p in self.model.namedparams()}

    def grads(self):
        return {path: p.grad for path, p in self.model.namedparams()}


def whole_leaves(tree, paths):
    """The leaves named, whole, on the host."""
    return {path: np.asarray(tree[path], np.float32) for path in paths}


def first_steps(prog):
    """The program's first CHECK_STEPS steps through the window's own call
    and feed: each step's loss, the first gradient as the optimizer got
    it (leaf norms, and whole the few leaves the job names under
    ``grad_diff_leaves``), and the parameters' change after the last."""
    out = {"losses": []}
    for i in range(CHECK_STEPS):
        out["losses"].append(prog.step(fetch=True))
        if i == 0:
            grads = prog.grads()
            out["grad_norms"] = leaf_norms(grads)
            out["grad_leaves"] = whole_leaves(
                grads, prog.run.traffic.get("grad_diff_leaves", ()))
            del grads
    start = weights.make_params(prog.spec, prog.run.seed, prog.replicated)
    out["delta_norms"] = diff_norms(prog.params(), start)
    return out


def reference_steps(run, spec, data, global_batch, precision="float32"):
    """The plain reference over the same first steps: same weights from
    the seed, same rows in the same order, the merged batch on one
    device."""
    from ..reference import _optim
    ref = harness.load_module("reference", run.config["reference"])
    name = run.traffic["optimizer"]["name"]
    init, update = _optim.OPTIMIZERS[name]
    hyper = {k: v for k, v in run.traffic["optimizer"].items()
             if k != "name"}
    params = weights.make_params(spec, run.seed)
    start = params
    state = init(params)
    out = {"losses": []}
    for i in range(CHECK_STEPS):
        rows = slice(i * global_batch, (i + 1) * global_batch)
        loss, grads = ref.batch_loss_and_grad(
            run.config, params, (data[0][rows], data[1][rows]),
            precision=precision)
        out["losses"].append(float(loss))
        if i == 0:
            out["grad_norms"] = leaf_norms(grads)
            out["grad_leaves"] = whole_leaves(
                grads, run.traffic.get("grad_diff_leaves", ()))
        params, state = update(params, grads, state, **hyper)
    out["delta_norms"] = diff_norms(params, start)
    return out


def grad_diff_gap(got, want):
    """The norm of the difference between the program's gradient and the
    reference's, over the reference's norm: the widest over the few
    leaves the job names, those whose gradient is conditioned well
    enough to tell the configuration's precision from the next one down
    (None where it names none)."""
    return max((float(np.linalg.norm(got["grad_leaves"][k] - w))
                / max(float(np.linalg.norm(w)), 1e-30)
                for k, w in want["grad_leaves"].items()), default=None)


def window(prog, seconds, fetch_every):
    """Steps for ``seconds``, the last one's loss fetched: (steps, the
    seconds they took)."""
    prog.dispatch_ms.clear()
    begin = time.perf_counter()
    steps = 0
    while time.perf_counter() - begin < seconds:
        steps += 1
        prog.step(fetch=steps % fetch_every == 0)
    prog.fetch_last()
    return steps, time.perf_counter() - begin


def compare(checks, got, want, limits):
    """The numbers compared, each beside its limit."""
    loss_gap = max(abs(a - b) for a, b in zip(got["losses"],
                                              want["losses"]))
    checks.limit("loss_gap", loss_gap, limits["loss_gap"])
    g, where = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    checks.limit("grad_norm_gap", g, limits["grad_norm_gap"])
    checks.limit("grad_norm_median_gap",
                 median_leaf_gap(got["grad_norms"], want["grad_norms"]),
                 limits["grad_norm_median_gap"])
    if want["grad_leaves"]:
        checks.limit("grad_diff_gap", grad_diff_gap(got, want),
                     limits["grad_diff_gap"])
    d, where_d = worst_leaf_gap(got["delta_norms"], want["delta_norms"])
    checks.limit("delta_norm_gap", d, limits["delta_norm_gap"])
    checks.limit("delta_norm_median_gap",
                 median_leaf_gap(got["delta_norms"], want["delta_norms"]),
                 limits["delta_norm_median_gap"])
    harness.say({"check_detail": "worst leaves", "grad_norm_gap": where,
                 "delta_norm_gap": where_d,
                 "program_losses": got["losses"],
                 "reference_losses": want["losses"]})


def run(run):
    import jax

    job = run.traffic
    checks = harness.Checks()
    with harness.watch_compiles() as watch:
        prog = Program(run)
        if len(prog.data[0]) < CHECK_STEPS * prog.global_batch:
            raise harness.BenchmarkError(
                "dataset_batches must cover the checked steps")
        got = first_steps(prog)
        for _ in range(job["warm_steps"]):
            prog.step(fetch=False)
        prog.step(fetch=True)
        setup_s = time.perf_counter() - run.t0
        mark = watch.snapshot()

        # -- the window: closed by fetching the last step's loss.  A
        # traced run measures two short windows: one plain, from which
        # the host-clock readings (dispatch, rate) are taken, then one
        # under the profiler, which slows a host-fed step.
        seconds = min(run.seconds, job["trace_seconds"]) if run.trace \
            else run.seconds
        steps, window_s = window(prog, seconds, job["fetch_every"])
        dispatch_ms = list(prog.dispatch_ms)
        tracer = None
        if run.trace:
            tracer = tracing.Tracing(tracing.trace_dir(run))
            with tracer:
                traced_steps, traced_s = window(prog, seconds,
                                                job["fetch_every"])
            harness.say({"traced_window_s": traced_s,
                         "traced_steps": traced_steps})
        compiled, traced = watch.since(mark)
        compile_summary = watch.summary()

    samples = steps * prog.global_batch
    rate = samples / window_s / len(run.devices)
    device = harness.device_block(run.devices, run.rehearsal)
    harness.say({"window_s": window_s, "steps": steps, "samples": samples,
                 "setup_s": setup_s, **compile_summary})
    harness.say(harness.timing_summary("train.dispatch_ms", dispatch_ms))
    checks.require("no_compile_in_window", compiled == 0 and traced == 0,
                   {"compiled_or_loaded": compiled, "traced": traced})
    checks.require("losses_finite",
                   all(math.isfinite(v) for v in prog.losses),
                   prog.losses[-3:])

    metrics = {
        "train_samples_per_s_per_chip": rate,
        "setup_s": setup_s,
        "train.dispatch_ms": statistics.median(dispatch_ms),
        "train.mfu": 100.0 * rate * flops.train_flops_per_sample(
            run.config, job) / (run.peaks["bf16_tflops"] * 1e12)
        if run.peaks else None,
    }

    # -- the reference, once the program's state is freed
    spec, data, global_batch = prog.spec, prog.data, prog.global_batch
    del prog
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    want = reference_steps(run, spec, data, global_batch)
    compare(checks, got, want, job["limits"])
    harness.say({"reference_s": time.perf_counter() - t_ref})
    checks.say()
    return {"correct": checks.ok, "attempted": steps, "failed": 0,
            "metrics": metrics, "device": device,
            "tracing": tracer, "steps_per_s": steps / window_s,
            "checks": checks.rows}
