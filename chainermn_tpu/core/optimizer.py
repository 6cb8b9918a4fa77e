"""Optimizers (consumed-Chainer surface: ``chainer.Optimizer`` + optimizers).

Reference anchors: ``chainer/optimizer.py · Optimizer/GradientMethod``,
``chainer/optimizers/ · SGD, MomentumSGD, Adam, ...``,
``chainer/optimizer_hooks/ · WeightDecay, GradientClipping`` (SURVEY.md §2.8).

Architecture (TPU-first): the reference runs a Python loop of per-parameter
CUDA update kernels; here the *whole* step — forward, backward, gradient
transform (where the multi-node subclass inserts its mesh ``psum``), optax
update — is one jit-compiled program per (loss function, input shapes).
Hooks map to optax gradient transformations chained ahead of the base rule,
preserving the reference's apply-hooks-then-update ordering.  The learning
rate is a *traced argument* so schedule extensions (ExponentialShift etc.)
can mutate ``optimizer.lr`` between steps without recompiling.
"""

from __future__ import annotations

from collections import OrderedDict
import warnings

import numpy as np

import jax
import jax.numpy as jnp
import optax

from .link import (Link, bind_state, extract_state,
                   load_param_tree, _persistent_slots)
from .config import config

__all__ = ["Optimizer", "GradientMethod", "SGD", "MomentumSGD", "Adam",
           "AdamW", "RMSprop", "AdaGrad", "AdaDelta", "NesterovAG",
           "WeightDecay", "GradientClipping", "GradientHardClipping",
           "Lasso", "GradientScaling"]


# ---------------------------------------------------------------------------
# Hooks → optax gradient transformations
# ---------------------------------------------------------------------------

class _Hook:
    name = "Hook"
    timing = "pre"

    #: Element-wise hooks (each output element depends only on the same
    #: element of grad/param) may run unchanged on a 1/n chunk of the flat
    #: gradient under ZeRO.  Hooks computing GLOBAL gradient statistics
    #: must instead provide ``to_optax_sharded(axis)`` (see
    #: GradientClipping).  Unmarked hooks are rejected under ZeRO rather
    #: than silently applied chunk-locally.
    chunk_local = False

    def to_optax(self) -> optax.GradientTransformation:
        raise NotImplementedError


class WeightDecay(_Hook):
    """L2 decay added to gradients (reference: ``optimizer_hooks.WeightDecay``)."""

    name = "WeightDecay"
    chunk_local = True

    def __init__(self, rate):
        self.rate = rate

    def to_optax(self):
        return optax.add_decayed_weights(self.rate)


class Lasso(_Hook):
    name = "Lasso"
    chunk_local = True

    def __init__(self, rate):
        self.rate = rate

    def to_optax(self):
        rate = self.rate

        def update_fn(updates, state, params=None):
            upd = jax.tree.map(lambda g, p: g + rate * jnp.sign(p), updates, params)
            return upd, state

        return optax.GradientTransformation(lambda p: optax.EmptyState(), update_fn)


class GradientClipping(_Hook):
    """Clip by global L2 norm (reference: ``optimizer_hooks.GradientClipping``)."""

    name = "GradientClipping"

    def __init__(self, threshold):
        self.threshold = threshold

    def to_optax(self):
        return optax.clip_by_global_norm(self.threshold)

    def to_optax_sharded(self, axis):
        """ZeRO variant: the transform sees only this rank's 1/n chunk of
        the flat gradient, so the GLOBAL norm is the psum of per-chunk
        squared norms — numerically identical to clipping the full
        gradient (padding zeros contribute nothing)."""
        threshold = self.threshold

        def update_fn(updates, state, params=None):
            del params
            sq = sum(jnp.sum(jnp.square(u))
                     for u in jax.tree.leaves(updates))
            gnorm = jnp.sqrt(jax.lax.psum(sq, axis))
            scale = jnp.minimum(1.0, threshold / jnp.maximum(gnorm, 1e-16))
            return jax.tree.map(lambda u: u * scale, updates), state

        return optax.GradientTransformation(lambda p: optax.EmptyState(),
                                            update_fn)


class GradientHardClipping(_Hook):
    name = "GradientHardClipping"
    chunk_local = True

    def __init__(self, lower_bound, upper_bound):
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound

    def to_optax(self):
        lo, hi = self.lower_bound, self.upper_bound

        def update_fn(updates, state, params=None):
            return jax.tree.map(lambda g: jnp.clip(g, lo, hi), updates), state

        return optax.GradientTransformation(lambda p: optax.EmptyState(), update_fn)


class GradientScaling(_Hook):
    name = "GradientScaling"
    chunk_local = True

    def __init__(self, rate):
        self.rate = rate

    def to_optax(self):
        return optax.scale(self.rate)


# ---------------------------------------------------------------------------
# Optimizer base
# ---------------------------------------------------------------------------

def make_loss_and_grad(target, lossfun):
    """Build the traced loss/grad body shared by the single-device and
    multi-node compiled steps.

    Returns ``f(params, pstate, args, kwargs) -> (loss, new_pstate, obs,
    grads)``.  In-forward ``report`` calls are captured into ``obs`` (keys
    prefixed via the reporter active at trace time; standalone use gets a
    fresh reporter with the target registered as ``main`` so keys match
    trainer runs).
    """
    from . import reporter as reporter_module

    def resolve_reporter():
        stack = reporter_module._reporter_stack()
        if stack:
            return stack[-1]
        rep = reporter_module.Reporter()
        rep.add_observer("main", target)
        rep.add_observers("main", target.namedlinks(skipself=True))
        return rep

    def loss_and_grad(params, pstate, rng_key, args, kwargs):
        from . import rng as rng_module

        def loss_on(p):
            with bind_state(target, {"params": p, "state": pstate}) as handle:
                obs = {}
                with resolve_reporter().scope(obs), \
                        rng_module.key_scope(rng_key):
                    loss = lossfun(*args, **kwargs)
                new_pstate = handle.collect()
            if isinstance(loss, tuple):
                loss = loss[0]
            return loss, (new_pstate, obs)

        (loss, (new_pstate, obs)), grads = jax.value_and_grad(
            loss_on, has_aux=True)(params)
        return loss, new_pstate, obs, grads

    return loss_and_grad


def apply_transform_update(tx, grads, opt_state, params, lr, decoupled_wd=0.0):
    """Shared tail of every compiled step: hook-chained transform, then the
    -lr scaling (lr is a traced argument — schedule changes don't recompile).

    ``decoupled_wd`` is applied OUTSIDE the -lr scaling: the reference's
    Adam adds ``eta * weight_decay_rate * param`` to the update un-scaled
    by alpha (reference `chainer/optimizers/adam.py · AdamRule.update_core`),
    so folding it into the lr-scaled updates would make it ~1/lr weaker."""
    updates, new_opt_state = tx.update(grads, opt_state, params)
    updates = jax.tree.map(lambda u, p: -lr * u - decoupled_wd * p,
                           updates, params)
    return optax.apply_updates(params, updates), new_opt_state


def serialize_flat_tree(serializer, tree, count_key, leaf_prefix):
    """Write a pytree as ``count_key`` + one array per flattened leaf."""
    flat, _ = jax.tree.flatten(tree)
    serializer(count_key, len(flat))
    for i, leaf in enumerate(flat):
        serializer(f"{leaf_prefix}{i}", np.asarray(leaf))


def deserialize_flat_tree(serializer, template, count_key, leaf_prefix):
    """Read a pytree written by :func:`serialize_flat_tree` onto
    ``template``'s structure.  Returns ``None`` when the snapshot has no
    ``count_key`` (pre-feature or partial snapshot).  A leaf-count
    mismatch or a leaf missing under a non-strict reader keeps the
    template's value for the affected leaves — but warns loudly, because
    a snapshot saved under a different optimizer/hook configuration
    would otherwise resume with silently mixed optimizer state."""
    try:
        n = serializer(count_key, None)
    except KeyError:
        return None
    if n is None:
        return None
    flat, treedef = jax.tree.flatten(template)
    if int(n) != len(flat):
        warnings.warn(
            f"flat-tree snapshot '{count_key}' holds {int(n)} leaves but the "
            f"current configuration expects {len(flat)}; leaves beyond the "
            "saved count keep their template (fresh) values.  This usually "
            "means the snapshot was saved under a different optimizer/hook "
            "configuration.", stacklevel=2)
    new = []
    missing = []
    for i, leaf in enumerate(flat):
        data = None
        if i < int(n):
            try:
                data = serializer(f"{leaf_prefix}{i}", None)
            except KeyError:
                missing.append(i)
        new.append(jnp.asarray(data) if data is not None else leaf)
    if missing:
        warnings.warn(
            f"flat-tree snapshot '{count_key}' is missing leaves {missing}; "
            "those leaves keep their template (fresh) values.",
            stacklevel=2)
    return jax.tree.unflatten(treedef, new)


def raise_if_donated_state_lost(exc, optimizer):
    """Donation failure containment, shared by every updater path.

    A donated step that fails mid-execution has already consumed the
    parameter/opt-state buffers; retrying ``update()`` on the same
    instance would feed deleted arrays back into XLA with an opaque
    error.  Detect the case and raise a RuntimeError that names the
    actual recovery (rebuild or reload the model — the resilience
    subsystem's consensus resume does exactly that), chaining the
    original failure.  No-op when nothing was donated or the failure
    happened before execution (trace/shape errors leave buffers alive).
    """
    target = getattr(optimizer, "target", None)
    if target is None or not getattr(optimizer, "donate_params", False):
        return
    lost = any(p.array is not None
               and getattr(p.array, "is_deleted", lambda: False)()
               for p in target.params())
    if lost:
        raise RuntimeError(
            "a donated train step failed after consuming the model's "
            "parameter buffers; rebuild or reload the model (snapshot / "
            "consensus resume) before the next update — or set "
            "optimizer.donate_params = False for retry-able interactive "
            "use") from exc


def _operand_specs(operands):
    """ShapeDtypeStruct tree of an operand tuple (idempotent: specs map
    to equal specs) — shapes only, no buffers pinned."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "dtype") and hasattr(a, "shape") else a, operands)


def memory_stats_dict(ma):
    """``CompiledMemoryStats`` → plain dict (JSON-ready), with the
    derived ``peak_hbm_bytes`` figure.  ONE definition — bench rows and
    the hbm_bytes probe both report through it, so the committed budget
    comparisons can never diverge on what "peak" means.  None passes
    through (backend without memory analysis)."""
    if ma is None:
        return None
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_hbm_bytes": ma.argument_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
        + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes,
    }


def aot_memory_analysis(step, operands):
    """``memory_analysis()`` of a compiled step, from shape specs only.

    ``step`` is the jit-wrapped step function; ``operands`` the exact
    argument tuple a dispatch received (or its spec tree).  Lowering
    from ``ShapeDtypeStruct``s pins no buffers, and with the persistent
    XLA cache enabled the AOT compile is a cache hit of the
    dispatch-path executable.  Returns None when the backend implements
    no memory analysis.  Used by bench rows (``peak_hbm_bytes``) and the
    donation test suite (params + opt-state aliased into outputs).
    """
    try:
        return step.lower(*_operand_specs(operands)).compile() \
            .memory_analysis()
    except NotImplementedError:
        return None


class _LRUCache(OrderedDict):
    """Bounded compiled-step cache.

    Keys include ``id(lossfun)``: per-iteration closure lambdas would
    otherwise grow the cache without bound while pinning their captured
    batches.  (Pass data via ``update(lossfun, *args)`` — a fresh closure
    per step forces a retrace by construction.)
    """

    def __init__(self, maxsize=16):
        super().__init__()
        self.maxsize = maxsize

    def get(self, key, default=None):
        if key in self:
            self.move_to_end(key)
            return self[key]
        return default

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        while len(self) > self.maxsize:
            self.popitem(last=False)


class Optimizer:
    """Base optimizer with the reference's lifecycle vocabulary.

    ``setup(link)`` binds a target; ``update(lossfun, *args)`` runs one full
    compiled train step; ``update()`` (no args) consumes gradients already
    stored on ``Parameter.grad`` (the path the eager communicator's
    ``allreduce_grad`` feeds, reference `optimizer.py · GradientMethod.update`).
    """

    # names of hyperparameters passed as traced args (mutable between steps)
    _dynamic_hyper = ("lr",)

    #: Donate parameter buffers to the compiled step (in-place update:
    #: one less params-sized HBM allocation per step, and the headroom
    #: that unlocks per-chip batches beyond 256 on the flagship model).
    #: ON by default: donation is safe through the Link pytree bridge —
    #: every compiled step returns fresh param arrays that ``_write_back``
    #: rebinds into the SAME ``Parameter`` objects before control returns
    #: to user code, and ``Link.copyparams`` copies by value, so code that
    #: goes through Parameters never sees a deleted buffer.  What donation
    #: DOES invalidate is a raw ``jax.Array`` reference captured from
    #: ``p.array`` before an update — hold the ``Parameter``, or
    #: ``np.asarray`` the value, or set ``donate_params = False``.
    #: If a donated step fails MID-EXECUTION (e.g. HBM OOM), the donated
    #: buffers are already consumed: ``update`` raises a RuntimeError
    #: naming the recovery (rebuild/reload the model) instead of leaving
    #: the Link silently holding dead arrays.
    donate_params = True

    def __init__(self):
        self.target: Link | None = None
        self.t = 0
        self.epoch = 0
        self._hooks = OrderedDict()
        self._opt_state = None
        self._tx = None
        self._step_cache = _LRUCache()

    # -- lifecycle ---------------------------------------------------------
    def setup(self, link: Link):
        self.target = link
        self._opt_state = None
        self._step_cache = _LRUCache()
        return self

    def add_hook(self, hook, name=None, timing="pre"):
        if self.target is None:
            raise RuntimeError("call setup() before add_hook()")
        self._hooks[name or hook.name] = hook
        self._tx = None
        self._opt_state = None
        self._step_cache = _LRUCache()

    def remove_hook(self, name):
        del self._hooks[name]
        self._tx = None
        self._opt_state = None
        self._step_cache = _LRUCache()

    def new_epoch(self):
        self.epoch += 1

    # -- optax assembly ----------------------------------------------------
    def _base_transform(self) -> optax.GradientTransformation:
        """Subclass: the update rule *excluding* the -lr scaling."""
        raise NotImplementedError

    def _transform(self, sharded_axis=None):
        """Hook chain ahead of the base rule (single assembly point).

        ``sharded_axis``: mesh axis name when the transform will run on a
        1/n chunk of the flat gradient inside shard_map (ZeRO) — hooks
        needing GLOBAL gradient statistics then use their
        ``to_optax_sharded(axis)`` variant (element-wise hooks are
        chunk-local by construction and keep plain ``to_optax``).
        Sharded chains are not cached: they are built once per compiled
        step by the multi-node wrapper.
        """
        if sharded_axis is None and self._tx is not None:
            return self._tx
        parts = [self._hook_transform(h, sharded_axis)
                 for h in self._hooks.values()]
        parts.append(self._base_transform())
        tx = optax.chain(*parts)
        if sharded_axis is None:
            self._tx = tx
        return tx

    @staticmethod
    def _hook_transform(hook, sharded_axis):
        if sharded_axis is None:
            return hook.to_optax()
        if hasattr(hook, "to_optax_sharded"):
            return hook.to_optax_sharded(sharded_axis)
        if getattr(hook, "chunk_local", False):
            return hook.to_optax()
        raise ValueError(
            f"hook {getattr(hook, 'name', hook)!r} cannot run under "
            f"zero_sharding: it is not marked chunk_local (element-wise) "
            f"and provides no to_optax_sharded(axis) variant — applying "
            f"it to a 1/n gradient chunk would silently change semantics "
            f"if it computes global gradient statistics")

    def _hyper_values(self):
        vals = {name: jnp.asarray(getattr(self, name), jnp.float32)
                for name in self._dynamic_hyper}
        # decoupled (AdamW-style, un-scaled by lr) weight decay; 0 for
        # optimizers without the knob
        vals["decoupled_wd"] = jnp.asarray(
            getattr(self, "weight_decay_rate", 0.0) or 0.0, jnp.float32)
        return vals

    def _next_rng_key(self):
        """Fresh per-step key (traced arg): stochastic layers get a new
        mask every step without recompilation.  Seeded from ``self.seed``
        when set (reproducibility)."""
        if not hasattr(self, "_rng_key") or self._rng_key is None:
            seed = getattr(self, "seed", None)
            if seed is None:
                seed = np.random.randint(0, 2**31 - 1)
            self._rng_key = jax.random.PRNGKey(seed)
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    def _ensure_opt_state(self, params):
        if self._opt_state is None:
            self._opt_state = self._transform().init(params)
        return self._opt_state

    # -- compiled full step ------------------------------------------------
    def _make_step(self, lossfun):
        tx = self._transform()
        loss_and_grad = make_loss_and_grad(self.target, lossfun)

        def step(params, pstate, opt_state, hyper, rng_key, args, kwargs):
            loss, new_pstate, obs, grads = loss_and_grad(
                params, pstate, rng_key, args, kwargs)
            new_params, new_opt_state = apply_transform_update(
                tx, grads, opt_state, params, hyper["lr"],
                hyper.get("decoupled_wd", 0.0))
            return new_params, new_pstate, new_opt_state, loss, grads, obs

        # donate params + opt_state so XLA updates both in place (see the
        # ``donate_params`` class doc for the safety contract; persistent
        # state — arg 1, BN stats — is NOT donated: it is small and the
        # forward reads it eagerly outside the aliasing guarantee)
        donate = (0, 2) if getattr(self, "donate_params", True) else (2,)
        return jax.jit(step, donate_argnums=donate)

    def _stash_step_spec(self, step, operands):
        """Remember the last dispatched step as (jit fn, ShapeDtypeStruct
        tree) — shapes only, no buffers pinned — so tooling can AOT-query
        the exact compiled program (see :func:`aot_memory_analysis`).
        Hot-path discipline: the spec is rebuilt only when the step
        object CHANGES — operand shapes/dtypes are part of the step-cache
        key, so same step ⇒ same specs, and re-dispatches pay one
        identity check instead of a tree-map over the whole
        param/opt-state pytree."""
        last = getattr(self, "_last_step_spec", None)
        if last is not None and last[0] is step:
            return
        self._last_step_spec = (step, _operand_specs(operands))

    def compiled_step_memory_analysis(self):
        """``memory_analysis()`` of the most recently dispatched compiled
        step (None before any update, or when the backend lacks it)."""
        spec = getattr(self, "_last_step_spec", None)
        if spec is None:
            return None
        return aot_memory_analysis(*spec)

    def traced_step(self):
        """The most recently dispatched step re-traced from its shape
        specs (None before any update): ``.jaxpr`` for structure
        censuses, ``.lower()`` for the program text.  No buffers are
        touched."""
        spec = getattr(self, "_last_step_spec", None)
        if spec is None:
            return None
        step, operands = spec
        return step.trace(*operands)

    def _cache_key(self, lossfun, args, kwargs):
        shapes = tuple(
            (np.shape(a), str(getattr(a, "dtype", type(a).__name__)))
            for a in jax.tree.leaves((args, kwargs)))
        return (id(lossfun), shapes, bool(config.train),
                bool(getattr(self, "donate_params", False)))

    def update(self, lossfun=None, *args, **kwargs):
        if self.target is None:
            raise RuntimeError("Optimizer.setup(link) was not called")
        if lossfun is None:
            return self._update_from_grads()
        if any(p.array is None for p in self.target.params()):
            # materialize lazily-initialized params with one eager forward
            # (bind_state restores persistent state, so BN stats are untouched)
            from .link import bind_state
            with bind_state(self.target, extract_state(self.target)):
                lossfun(*args, **kwargs)
        state = extract_state(self.target)
        params, pstate = state["params"], state["state"]
        opt_state = self._ensure_opt_state(params)
        key = self._cache_key(lossfun, args, kwargs)
        step = self._step_cache.get(key)
        if step is None:
            step = self._make_step(lossfun)
            self._step_cache[key] = step
        operands = (params, pstate, opt_state, self._hyper_values(),
                    self._next_rng_key(), args, kwargs)
        self._stash_step_spec(step, operands)
        try:
            new_params, new_pstate, new_opt_state, loss, grads, obs = \
                step(*operands)
        except Exception as e:
            raise_if_donated_state_lost(e, self)
            raise
        self._write_back(new_params, new_pstate, grads)
        self._opt_state = new_opt_state
        self.t += 1
        from . import reporter
        reporter.report(obs)  # keys were prefixed at capture time
        return loss

    def _update_from_grads(self):
        """Apply the update rule to gradients stored on Parameter.grad."""
        params = {}
        grads = {}
        for path, p in self.target.namedparams():
            if p.array is not None and p.grad is not None:
                params[path] = p.array
                grads[path] = p.grad
        if not grads:
            return None
        opt_state = self._ensure_opt_state(params)
        apply = self._step_cache.get("_from_grads")
        if apply is None:
            tx = self._transform()

            @jax.jit
            def apply(params, grads, opt_state, hyper):
                return apply_transform_update(
                    tx, grads, opt_state, params, hyper["lr"],
                    hyper.get("decoupled_wd", 0.0))

            self._step_cache["_from_grads"] = apply
        new_params, self._opt_state = apply(params, grads, opt_state,
                                            self._hyper_values())
        load_param_tree(self.target, new_params)
        self.t += 1
        return None

    def _write_back(self, params, pstate, grads=None):
        load_param_tree(self.target, params)
        slots = {full: (sublink, name)
                 for sublink, name, full in _persistent_slots(self.target)}
        for path, value in pstate.items():
            if path in slots:
                sublink, name = slots[path]
                object.__setattr__(sublink, name, value)
                sublink._persistent[name] = value
        if grads is not None:
            named = dict(self.target.namedparams())
            for path, g in grads.items():
                if path in named:
                    named[path].grad = g

    # -- serialization -----------------------------------------------------
    def serialize(self, serializer):
        # target first: restoring opt_state needs materialized params
        if self.target is not None:
            self.target.serialize(serializer["target"])
        self.t = int(serializer("t", self.t))
        self.epoch = int(serializer("epoch", self.epoch))
        # per-step rng key: resumed stochastic layers (dropout) continue
        # the exact key sequence of the uninterrupted run
        if serializer.is_writer:
            if getattr(self, "_rng_key", None) is not None:
                serializer("rng_key", np.asarray(self._rng_key))
        else:
            try:
                data = serializer("rng_key", None)
            except KeyError:  # snapshots from before keys were saved
                data = None
            if data is not None and np.asarray(data).size:
                self._rng_key = jnp.asarray(np.asarray(data,
                                                       dtype=np.uint32))
        if serializer.is_writer:
            if self._opt_state is not None:
                serialize_flat_tree(serializer, self._opt_state,
                                    "opt_state_len", "opt_state_")
        elif self.target is not None:
            # template for leaf placement: an existing state (e.g. the
            # ZeRO wrapper pre-seeds its flat-sharded template before
            # delegating here) wins over the default per-param tree,
            # which is built only if the snapshot actually carries state
            template = self._opt_state
            if template is None:
                try:
                    has_state = serializer("opt_state_len", None) is not None
                except KeyError:  # snapshot saved before the first update()
                    has_state = False
                if has_state:
                    params = extract_state(self.target)["params"]
                    template = self._transform().init(params)
            if template is not None:
                restored = deserialize_flat_tree(
                    serializer, template, "opt_state_len", "opt_state_")
                if restored is not None:
                    self._opt_state = restored


class GradientMethod(Optimizer):
    """Alias tier matching the reference hierarchy."""


# ---------------------------------------------------------------------------
# Concrete optimizers (reference: chainer/optimizers/*)
# ---------------------------------------------------------------------------

class SGD(GradientMethod):
    def __init__(self, lr=0.01):
        super().__init__()
        self.lr = lr

    def _base_transform(self):
        return optax.identity()


class MomentumSGD(GradientMethod):
    def __init__(self, lr=0.01, momentum=0.9):
        super().__init__()
        self.lr = lr
        self.momentum = momentum

    def _base_transform(self):
        # chainer momentum: v = m*v - lr*g ; p += v  == optax.trace(decay=m)
        return optax.trace(decay=self.momentum)


class NesterovAG(GradientMethod):
    def __init__(self, lr=0.01, momentum=0.9):
        super().__init__()
        self.lr = lr
        self.momentum = momentum

    def _base_transform(self):
        return optax.trace(decay=self.momentum, nesterov=True)


class Adam(GradientMethod):
    """Adam (reference: ``chainer/optimizers/adam.py``).

    ``alpha`` is the step size as in the reference; ``lr`` is the bias-
    corrected effective rate.  ``weight_decay_rate`` gives AdamW behavior.
    """

    _dynamic_hyper = ("lr",)

    def __init__(self, alpha=0.001, beta1=0.9, beta2=0.999, eps=1e-8,
                 weight_decay_rate=0.0, amsgrad=False):
        super().__init__()
        self.alpha = alpha
        self.beta1 = beta1
        self.beta2 = beta2
        self.eps = eps
        self.weight_decay_rate = weight_decay_rate
        self.amsgrad = amsgrad

    @property
    def lr(self):
        # optax.scale_by_adam already applies bias correction, so the
        # traced step multiplies by alpha directly.
        return self.alpha

    @lr.setter
    def lr(self, value):
        self.alpha = value

    def _base_transform(self):
        # weight_decay_rate is NOT part of the transform: it is applied as
        # decoupled decay in apply_transform_update (outside the -lr
        # scaling), matching the reference's `eta * weight_decay_rate *
        # param` term which alpha_t never multiplies.
        return (optax.scale_by_adam(b1=self.beta1, b2=self.beta2,
                                    eps=self.eps, nesterov=False)
                if not self.amsgrad else
                optax.scale_by_amsgrad(b1=self.beta1, b2=self.beta2,
                                       eps=self.eps))


class AdamW(Adam):
    def __init__(self, alpha=0.001, beta1=0.9, beta2=0.999, eps=1e-8,
                 weight_decay_rate=0.01):
        super().__init__(alpha, beta1, beta2, eps, weight_decay_rate)


class RMSprop(GradientMethod):
    def __init__(self, lr=0.01, alpha=0.99, eps=1e-8):
        super().__init__()
        self.lr = lr
        self.alpha = alpha
        self.eps = eps

    def _base_transform(self):
        return optax.scale_by_rms(decay=self.alpha, eps=self.eps)


class AdaGrad(GradientMethod):
    def __init__(self, lr=0.001, eps=1e-8):
        super().__init__()
        self.lr = lr
        self.eps = eps

    def _base_transform(self):
        return optax.scale_by_rss(initial_accumulator_value=0.0, eps=self.eps)


class AdaDelta(GradientMethod):
    def __init__(self, rho=0.95, eps=1e-6):
        super().__init__()
        self.lr = 1.0  # AdaDelta has no lr; scale by 1
        self.rho = rho
        self.eps = eps

    def _base_transform(self):
        return optax.scale_by_adadelta(rho=self.rho, eps=self.eps)
