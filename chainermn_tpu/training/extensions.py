"""Standard trainer extensions (consumed-Chainer surface).

Reference anchors: ``chainer/training/extensions/ · LogReport, PrintReport,
ProgressBar, snapshot, Evaluator, ExponentialShift, LinearShift``
(SURVEY.md §2.8, §5 metrics note).  ``Evaluator`` is the object
``chainermn_tpu.evaluators.create_multi_node_evaluator`` patches (SURVEY
§2.4), and ``snapshot`` the single-rank sibling of the distributed
checkpointer (SURVEY §3.5).
"""

from __future__ import annotations

import copy
import json
import os
import sys
import tempfile
import time

import numpy as np

from ..core import reporter as reporter_module
from ..core.config import using_config
from ..dataset.convert import concat_examples
from ..serializers.npz import save_npz
from .trainer import Extension, PRIORITY_WRITER
from .triggers import get_trigger

__all__ = ["LogReport", "PrintReport", "ProgressBar", "snapshot",
           "snapshot_object", "Evaluator", "ExponentialShift", "LinearShift",
           "observe_lr", "FailOnNonNumber", "ParameterStatistics"]


class LogReport(Extension):
    """Accumulates observations and writes a JSON log (reference name/shape)."""

    priority = PRIORITY_WRITER  # must see raw observations before readers

    def __init__(self, keys=None, trigger=(1, "epoch"), postprocess=None,
                 log_name="log"):
        self._keys = keys
        self._trigger = get_trigger(trigger)
        self.trigger = (1, "iteration")
        self._postprocess = postprocess
        self._log_name = log_name
        self._log = []
        self._summary = reporter_module.DictSummary()
        self._start_at = time.time()

    @property
    def log(self):
        return self._log

    def __call__(self, trainer):
        obs = trainer.observation
        if self._keys is None:
            self._summary.add(obs)
        else:
            self._summary.add({k: obs[k] for k in self._keys if k in obs})
        if self._trigger(trainer):
            stats = self._summary.compute_mean()
            entry = {k: float(v) for k, v in stats.items()}
            entry["epoch"] = trainer.updater.epoch
            entry["iteration"] = trainer.updater.iteration
            entry["elapsed_time"] = trainer.elapsed_time
            if self._postprocess is not None:
                self._postprocess(entry)
            self._log.append(entry)
            if self._log_name is not None:
                path = os.path.join(trainer.out, self._log_name)
                fd, tmp = tempfile.mkstemp(prefix=self._log_name,
                                           dir=trainer.out)
                with os.fdopen(fd, "w") as f:
                    json.dump(self._log, f, indent=4)
                os.replace(tmp, path)
            self._summary = reporter_module.DictSummary()

    def serialize(self, serializer):
        if hasattr(self._trigger, "serialize"):
            self._trigger.serialize(serializer["_trigger"])
        # persist accumulated log entries so resumed runs append to the
        # same history (reference LogReport behavior)
        if serializer.is_writer:
            payload = np.frombuffer(
                json.dumps(self._log).encode(), dtype=np.uint8)
            serializer("log_json", payload)
        else:
            try:
                data = serializer("log_json", None)
            except KeyError:
                data = None
            if data is not None and np.asarray(data).size:
                self._log = json.loads(np.asarray(
                    data, dtype=np.uint8).tobytes().decode())


class PrintReport(Extension):
    def __init__(self, entries, log_report="LogReport", out=sys.stdout):
        self._entries = entries
        self._log_report = log_report
        self._out = out
        self._log_len = 0
        header = "  ".join(f"{e:13}" for e in entries)
        self._header = header + "\n"

    def __call__(self, trainer):
        if self._header:
            self._out.write(self._header)
            self._header = None
        log_report = trainer.get_extension(self._log_report) \
            if isinstance(self._log_report, str) else self._log_report
        log = log_report.log
        while len(log) > self._log_len:
            entry = log[self._log_len]
            cells = []
            for key in self._entries:
                value = entry.get(key)
                if value is None:
                    cells.append(" " * 13)
                elif isinstance(value, float):
                    cells.append(f"{value:<13.6g}")
                else:
                    cells.append(f"{value:<13}")
            self._out.write("  ".join(cells) + "\n")
            self._log_len += 1
        self._out.flush()


class ProgressBar(Extension):
    def __init__(self, training_length=None, update_interval=100,
                 bar_length=50, out=sys.stdout):
        self._training_length = training_length
        self._update_interval = update_interval
        self._bar_length = bar_length
        self._out = out

    def __call__(self, trainer):
        iteration = trainer.updater.iteration
        if iteration % self._update_interval:
            return
        length = self._training_length
        if length is None:
            t = trainer.stop_trigger
            if hasattr(t, "period"):
                length = (t.period, t.unit)
        if length is None:
            return
        period, unit = length
        if unit == "iteration":
            rate = iteration / period
        else:
            rate = trainer.updater.epoch_detail / period
        rate = min(rate, 1.0)
        marks = "#" * int(rate * self._bar_length)
        self._out.write(f"\r[{marks:{self._bar_length}}] {rate:6.2%}")
        if rate >= 1.0:
            self._out.write("\n")
        self._out.flush()


def snapshot(savefun=save_npz, filename="snapshot_iter_{.updater.iteration}"):
    """Single-rank trainer snapshot (reference: ``extensions.snapshot``)."""

    @make_snapshot_extension
    def _snapshot(trainer):
        fname = filename.format(trainer)
        fd, tmp = tempfile.mkstemp(prefix=fname, dir=trainer.out)
        os.close(fd)
        try:
            savefun(tmp, trainer)
        except Exception:
            os.remove(tmp)
            raise
        os.replace(tmp, os.path.join(trainer.out, fname))

    return _snapshot


def snapshot_object(target, filename, savefun=save_npz):
    @make_snapshot_extension
    def _snapshot_object(trainer):
        fname = filename.format(trainer)
        fd, tmp = tempfile.mkstemp(prefix=fname, dir=trainer.out)
        os.close(fd)
        try:
            savefun(tmp, target)
        except Exception:
            os.remove(tmp)
            raise
        os.replace(tmp, os.path.join(trainer.out, fname))

    return _snapshot_object


def make_snapshot_extension(fn):
    fn.trigger = (1, "epoch")
    fn.priority = -100
    return fn


class Evaluator(Extension):
    """Validation-loop extension (reference: ``extensions.Evaluator``).

    ``evaluate()`` is the method the multi-node evaluator wrapper overrides
    to allreduce the metrics dict (SURVEY §2.4 ``create_multi_node_evaluator``).
    """

    trigger = (1, "epoch")
    priority = PRIORITY_WRITER
    default_name = "validation"

    def __init__(self, iterator, target, converter=concat_examples,
                 device=None, eval_hook=None, eval_func=None):
        if not isinstance(iterator, dict):
            iterator = {"main": iterator}
        self._iterators = iterator
        from ..core.link import Link
        if isinstance(target, Link):
            target = {"main": target}
        self._targets = target
        self.converter = converter
        self.device = device
        self.eval_hook = eval_hook
        self.eval_func = eval_func
        self.name = None

    def get_iterator(self, name="main"):
        return self._iterators[name]

    def get_target(self, name="main"):
        return self._targets[name]

    def __call__(self, trainer=None):
        reporter = reporter_module.Reporter()
        if hasattr(self, "_custom_name"):
            prefix = self._custom_name + "/"
        else:
            prefix = (self.name or self.default_name) + "/"
        for name, target in self._targets.items():
            reporter.add_observer(prefix + name, target)
            reporter.add_observers(prefix + name,
                                   target.namedlinks(skipself=True))
        with reporter:
            result = self.evaluate()
        reporter_module.report(result)
        return result

    def evaluate(self):
        iterator = self._iterators["main"]
        eval_func = self.eval_func or self._targets["main"]
        if self.eval_hook:
            self.eval_hook(self)
        if hasattr(iterator, "reset"):
            iterator.reset()
            it = iterator
        else:
            it = copy.copy(iterator)
        summary = reporter_module.DictSummary()
        sample_counts = {}

        def record(obs_dict, batch):
            summary.add(obs_dict)
            n = len(batch) if hasattr(batch, "__len__") else 1
            for k in obs_dict:
                sample_counts[k] = sample_counts.get(k, 0) + n

        from ..core.link import Link, extract_state
        compiled = isinstance(eval_func, Link) and \
            not getattr(self, "_eval_compile_failed", False)
        eval_state = extract_state(eval_func) if compiled else None
        with using_config("train", False):
            for batch in it:
                in_arrays = self.converter(batch, self.device)
                args = in_arrays if isinstance(in_arrays, tuple) \
                    else (in_arrays,)
                if compiled and not isinstance(in_arrays, dict):
                    try:
                        record(self._compiled_eval(eval_func, eval_state,
                                                   args), batch)
                        continue
                    except Exception:
                        # forwards that aren't jit-traceable (value-
                        # dependent control flow, host-side metrics):
                        # fall back to the reference's eager loop
                        self._eval_compile_failed = True
                        compiled = False
                observation = {}
                with reporter_module.report_scope(observation):
                    if isinstance(in_arrays, dict):
                        eval_func(**in_arrays)
                    else:
                        eval_func(*args)
                record(observation, batch)
        # per-key SAMPLE counts (batch sizes, not batch counts): the
        # multi-node wrapper weights the cross-host average by these, so
        # ragged final batches contribute proportionally to their size
        self._mn_counts = sample_counts
        return summary.compute_mean()

    def _compiled_eval(self, target, state, args):
        """One jitted validation step: forward + captured observations.

        The reference runs evaluation eagerly per batch; compiling keeps
        validation on-device at train-step speeds.  When a multi-node
        communicator is attached (``create_multi_node_evaluator``), the
        step is shard_mapped over its axis with the batch split across
        ranks and per-rank observations pmean'd — evaluation throughput
        scales with the mesh like training does.  Cached per input
        shapes; the trace-time reporter is the prefixed one installed by
        ``__call__``, so observation keys match the eager path.
        """
        import jax
        import numpy as np
        from ..core.link import bind_state
        if not hasattr(self, "_eval_cache"):
            from ..core.optimizer import _LRUCache
            self._eval_cache = _LRUCache()
        key = tuple((np.shape(a), str(getattr(a, "dtype", type(a).__name__)))
                    for a in jax.tree.leaves(args))
        fn = self._eval_cache.get(key)
        if fn is None:
            comm = getattr(self, "_mn_communicator", None)
            axis = getattr(comm, "axis_name", None)
            shardable = axis is not None and all(
                hasattr(a, "shape") and a.ndim > 0
                and a.shape[0] % comm.size == 0
                for a in jax.tree.leaves(args))

            def body(params, pstate, args):
                with bind_state(target, {"params": params,
                                         "state": pstate}):
                    obs = {}
                    with reporter_module.get_current_reporter().scope(obs):
                        with using_config("train", False):
                            target(*args)
                if shardable:
                    from jax import lax
                    obs = jax.tree.map(lambda o: lax.pmean(o, axis), obs)
                return obs

            if shardable:
                from jax import shard_map
                from jax.sharding import PartitionSpec as P
                args_specs = jax.tree.map(lambda _: P(axis), args)
                fn = jax.jit(shard_map(
                    body, mesh=comm.mesh,
                    in_specs=(P(), P(), args_specs), out_specs=P(),
                    check_vma=False))
            else:
                fn = jax.jit(body)
            self._eval_cache[key] = fn
        return fn(state["params"], state["state"], args)


class ExponentialShift(Extension):
    """Multiply an optimizer attribute by ``rate`` on each trigger."""

    trigger = (1, "epoch")

    def __init__(self, attr, rate, init=None, target=None, optimizer=None):
        self._attr = attr
        self._rate = rate
        self._init = init
        self._target = target
        self._optimizer = optimizer
        self._t = 0

    def initialize(self, trainer):
        optimizer = self._optimizer or trainer.updater.get_optimizer("main")
        if self._init is None:
            self._init = getattr(optimizer, self._attr)
        setattr(optimizer, self._attr, self._init * (self._rate ** self._t))

    def __call__(self, trainer):
        self._t += 1
        optimizer = self._optimizer or trainer.updater.get_optimizer("main")
        value = self._init * (self._rate ** self._t)
        if self._target is not None:
            if (self._rate < 1 and value < self._target) or \
               (self._rate > 1 and value > self._target):
                value = self._target
        setattr(optimizer, self._attr, value)

    def serialize(self, serializer):
        self._t = int(serializer("t", self._t))


class LinearShift(Extension):
    trigger = (1, "iteration")

    def __init__(self, attr, value_range, time_range, optimizer=None):
        self._attr = attr
        self._value_range = value_range
        self._time_range = time_range
        self._optimizer = optimizer
        self._t = 0

    def __call__(self, trainer):
        optimizer = self._optimizer or trainer.updater.get_optimizer("main")
        t1, t2 = self._time_range
        v1, v2 = self._value_range
        if self._t <= t1:
            value = v1
        elif self._t >= t2:
            value = v2
        else:
            value = v1 + (v2 - v1) * (self._t - t1) / (t2 - t1)
        setattr(optimizer, self._attr, value)
        self._t += 1

    def serialize(self, serializer):
        self._t = int(serializer("t", self._t))


def observe_lr(optimizer_name="main", observation_key="lr"):
    @make_observe_extension
    def _observe_lr(trainer):
        optimizer = trainer.updater.get_optimizer(optimizer_name)
        reporter_module.report({observation_key: getattr(optimizer, "lr")})

    return _observe_lr


def make_observe_extension(fn):
    fn.trigger = (1, "iteration")
    fn.priority = PRIORITY_WRITER
    return fn


class FailOnNonNumber(Extension):
    """Abort training when any model parameter becomes NaN/Inf."""

    trigger = (1, "iteration")

    def __call__(self, trainer):
        for _, optimizer in trainer.updater.get_all_optimizers().items():
            for p in optimizer.target.params():
                if p.array is not None and not bool(np.all(np.isfinite(np.asarray(p.array)))):
                    raise RuntimeError(
                        "Kill the process since parameters contain NaN/Inf")


class ParameterStatistics(Extension):
    """Report per-link parameter/gradient statistics (reference:
    ``chainer.training.extensions.ParameterStatistics``).

    One compiled reduction over the whole param tree per trigger (not a
    Python loop per parameter): statistics are computed in a single jitted
    call and reported under ``<prefix>/<path>/<data|grad>/<stat>``.
    """

    trigger = (1, "epoch")
    priority = PRIORITY_WRITER
    default_statistics = {
        "mean": lambda x: x.mean(),
        "std": lambda x: x.std(),
        "min": lambda x: x.min(),
        "max": lambda x: x.max(),
    }

    def __init__(self, links, statistics=None, report_params=True,
                 report_grads=True, prefix=None):
        from ..core.link import Link
        if isinstance(links, Link):
            links = [links]
        self._links = links
        self._statistics = statistics or dict(self.default_statistics)
        self._report_params = report_params
        self._report_grads = report_grads
        self._prefix = prefix
        self._compiled = None

    def __call__(self, trainer=None):
        import jax
        params = {}
        grads = {}
        for i, link in enumerate(self._links):
            base = self._prefix + "/" if self._prefix else ""
            name = getattr(link, "name", None) or str(i)
            for path, p in link.namedparams():
                if p.array is not None and self._report_params:
                    params[f"{base}{name}{path}"] = p.array
                if p.grad is not None and self._report_grads:
                    grads[f"{base}{name}{path}"] = p.grad
        if self._compiled is None:
            stats = self._statistics

            @jax.jit
            def compute(params, grads):
                out = {}
                for key, arr in params.items():
                    for sname, fn in stats.items():
                        out[f"{key}/data/{sname}"] = fn(arr)
                for key, arr in grads.items():
                    for sname, fn in stats.items():
                        out[f"{key}/grad/{sname}"] = fn(arr)
                return out

            self._compiled = compute
        observation = self._compiled(params, grads)
        reporter_module.report(observation)
        return observation
