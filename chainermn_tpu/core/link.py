"""Parameter containers with a define-by-run feel, backed by JAX pytrees.

TPU-native equivalent of the consumed-Chainer surface ``chainer.Link`` /
``chainer.Chain`` / ``chainer.ChainList`` (see SURVEY.md §2.8).  The reference
(`chainer/link.py · Link/Chain/ChainList`) stores ``Parameter`` objects on
mutable objects and mutates them in place from per-parameter update rules.
Here the *user-facing* container keeps that ergonomic shape (attribute
registration inside ``init_scope``, ``namedparams``, ``cleargrads``,
``serialize``) while the *compute* path is functional: ``extract_state`` /
``bind_state`` flatten a Link into a pytree of ``jax.Array`` leaves so that a
whole training step — forward, backward, collective, optimizer update — is one
``jax.jit``-compiled program.  Nothing in the hot loop touches Python object
attributes; the Link is only read/written at step boundaries.
"""

from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Parameter",
    "Link",
    "Chain",
    "ChainList",
    "Sequential",
    "extract_state",
    "bind_state",
    "apply_state",
    "param_tree",
    "grad_tree",
    "set_grads",
    "load_param_tree",
    "abstract_init",
    "cast_params",
]


class Parameter:
    """A trainable array plus its (optional) gradient.

    Mirrors ``chainer.Parameter`` (data/grad pair, lazy initialization when
    constructed from a shape-less initializer).  ``array`` is a ``jax.Array``
    (or numpy array before device placement); ``grad`` is filled by
    the functional autodiff path so that reference-style code
    (``allreduce_grad`` reading ``param.grad``) keeps working.
    """

    def __init__(self, array=None, name: str | None = None):
        self.array = None if array is None else jnp.asarray(array)
        self.grad = None
        self.name = name
        self._initializer = None

    def draw(self, shape, dtype, fn):
        """Set the initial value: ``fn()`` (a host draw of ``shape`` and
        ``dtype``) placed as a ``jax.Array``, or, inside
        :func:`abstract_init`, the shape and dtype alone
        (``jax.ShapeDtypeStruct``): nothing drawn, nothing allocated."""
        if getattr(_thread_local, "abstract", False):
            self.array = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
        else:
            self.array = jnp.asarray(fn())

    @property
    def is_abstract(self):
        return isinstance(self.array, jax.ShapeDtypeStruct)

    # -- chainer-parity conveniences -------------------------------------
    @property
    def data(self):  # chainer exposes .data as an alias of .array
        return self.array

    @data.setter
    def data(self, value):
        self.array = None if value is None else jnp.asarray(value)

    @property
    def shape(self):
        return None if self.array is None else self.array.shape

    @property
    def dtype(self):
        return None if self.array is None else self.array.dtype

    def cleargrad(self):
        self.grad = None

    def zerograd(self):
        if self.array is not None:
            self.grad = jnp.zeros_like(self.array)

    def initialize(self, shape, dtype=jnp.float32, rng: np.random.RandomState | None = None):
        """Materialize a lazily-constructed parameter."""
        if self._initializer is None:
            raise RuntimeError("Parameter has no initializer")
        self.array = jnp.asarray(self._initializer(shape, dtype, rng))

    def __repr__(self):
        return f"Parameter(name={self.name!r}, shape={self.shape}, dtype={self.dtype})"


_thread_local = threading.local()


@contextlib.contextmanager
def abstract_init():
    """Links constructed inside keep their parameters as shapes only.

    A model whose float32 weights do not fit twice (zero-filled by the
    constructor, then made again from a seed) is built here, read for
    its ``namedparams()`` shapes, and filled by :func:`load_param_tree`.
    Links take part by setting their parameters through
    :meth:`Parameter.draw`.  An abstract leaf reaching a compiled
    program is an error of the caller: :func:`extract_state` refuses it.
    """
    prev = getattr(_thread_local, "abstract", False)
    _thread_local.abstract = True
    try:
        yield
    finally:
        _thread_local.abstract = prev


class Link:
    """Base parameter container.

    Parameters and child links assigned as attributes inside ``init_scope``
    are registered (reference: ``chainer/link.py · Link.init_scope``); plain
    attribute assignment outside the scope is untracked, matching the
    reference semantics.  Values registered with ``add_persistent`` (e.g.
    BatchNormalization running statistics) are serialized and threaded through
    jitted programs as non-trainable state.
    """

    def __init__(self, **kwargs):
        object.__setattr__(self, "_params", OrderedDict())
        object.__setattr__(self, "_persistent", OrderedDict())
        object.__setattr__(self, "_children", OrderedDict())
        object.__setattr__(self, "_within_init_scope", False)
        object.__setattr__(self, "name", None)
        with self.init_scope():
            for name, value in kwargs.items():
                setattr(self, name, value)

    # -- registration ----------------------------------------------------
    @contextlib.contextmanager
    def init_scope(self):
        prev = self._within_init_scope
        object.__setattr__(self, "_within_init_scope", True)
        try:
            yield
        finally:
            object.__setattr__(self, "_within_init_scope", prev)

    def __setattr__(self, name, value):
        if getattr(self, "_within_init_scope", False):
            if isinstance(value, Parameter):
                value.name = name
                self._params[name] = value
            elif isinstance(value, Link):
                value.name = name
                self._children[name] = value
        if name in getattr(self, "_persistent", {}):
            self._persistent[name] = value
        object.__setattr__(self, name, value)

    def __delattr__(self, name):
        self._params.pop(name, None)
        self._children.pop(name, None)
        self._persistent.pop(name, None)
        object.__delattr__(self, name)

    def add_param(self, name, array=None):
        param = Parameter(array, name=name)
        self._params[name] = param
        object.__setattr__(self, name, param)
        return param

    def add_persistent(self, name, value):
        self._persistent[name] = value
        object.__setattr__(self, name, value)
        return value

    # -- traversal (chainer vocabulary) ----------------------------------
    def params(self, include_uninit: bool = True):
        for _, p in self.namedparams(include_uninit):
            yield p

    def namedparams(self, include_uninit: bool = True, prefix: str = ""):
        for name, p in self._params.items():
            if include_uninit or p.array is not None:
                yield prefix + "/" + name, p
        for cname, child in self._children.items():
            yield from child.namedparams(include_uninit, prefix + "/" + cname)

    def links(self, skipself: bool = False):
        if not skipself:
            yield self
        for child in self._children.values():
            yield from child.links()

    def namedlinks(self, skipself: bool = False, prefix: str = ""):
        if not skipself:
            yield prefix or "/", self
        for cname, child in self._children.items():
            yield from child.namedlinks(False, prefix + "/" + cname)

    def children(self):
        yield from self._children.values()

    def namedpersistent(self, prefix: str = ""):
        for name in self._persistent:
            yield prefix + "/" + name, getattr(self, name)
        for cname, child in self._children.items():
            yield from child.namedpersistent(prefix + "/" + cname)

    # -- gradient bookkeeping --------------------------------------------
    def cleargrads(self):
        for p in self.params():
            p.cleargrad()

    def zerograds(self):
        for p in self.params():
            p.zerograd()

    def count_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.params() if p.array is not None)

    # -- device placement -------------------------------------------------
    def to_device(self, device=None):
        """Place all arrays on ``device`` (a ``jax.Device``); default device if None."""
        for p in self.params():
            if p.array is not None:
                p.array = jax.device_put(p.array, device)
        for link in self.links():
            for name in link._persistent:
                value = getattr(link, name)
                if isinstance(value, (jnp.ndarray, np.ndarray)) or hasattr(value, "devices"):
                    object.__setattr__(link, name, jax.device_put(jnp.asarray(value), device))
                    link._persistent[name] = getattr(link, name)
        return self

    # chainer-parity aliases; TPU build has no separate CPU/GPU split —
    # everything is a jax.Array whose placement the runtime controls.
    def to_gpu(self, device=None):
        return self.to_device(device)

    def to_cpu(self):
        for p in self.params():
            if p.array is not None:
                p.array = jnp.asarray(np.asarray(p.array))
        return self

    # -- copy -------------------------------------------------------------
    def copyparams(self, link: "Link"):
        """Copy parameter VALUES from ``link`` (reference ``copyparams``
        semantics: ``copydata``, not aliasing).  Copying — rather than
        sharing the ``jax.Array`` objects, as an earlier build did — is
        part of the donation-safety contract: a donated train step on one
        link must never invalidate another link's buffers (see
        ``Optimizer.donate_params``)."""
        src = dict(link.namedparams())
        for path, p in self.namedparams():
            if path in src and src[path].array is not None:
                p.array = jnp.array(src[path].array, copy=True)

    # -- serialization (chainer serializer protocol) ----------------------
    def serialize(self, serializer):
        for name, p in self._params.items():
            data = serializer(name, None if p.array is None else np.asarray(p.array))
            if data is not None and not serializer.is_writer:
                p.array = jnp.asarray(data)
        for name in self._persistent:
            value = getattr(self, name)
            arr = np.asarray(value) if value is not None else None
            data = serializer(name, arr)
            if data is not None and not serializer.is_writer:
                if isinstance(value, (int, float)) or (arr is not None and arr.ndim == 0):
                    restored = data.item() if hasattr(data, "item") and data.ndim == 0 else data
                else:
                    restored = jnp.asarray(data)
                object.__setattr__(self, name, restored)
                self._persistent[name] = restored
        for cname, child in self._children.items():
            child.serialize(serializer[cname])

    # -- call protocol -----------------------------------------------------
    def __call__(self, *args, **kwargs):
        # the link's name on every operation's path (``blocks/3/attn/qkv``
        # in the compiled program's ``op_name`` and the profiler's trace)
        if self.name:
            with jax.named_scope(self.name):
                return self.forward(*args, **kwargs)
        return self.forward(*args, **kwargs)

    def forward(self, *args, **kwargs):
        raise NotImplementedError


class Chain(Link):
    """Link composed of named child links (``chainer.Chain``)."""


class ChainList(Link):
    """Link composed of an ordered list of child links (``chainer.ChainList``)."""

    def __init__(self, *links):
        super().__init__()
        object.__setattr__(self, "_chainlist", [])
        for link in links:
            self.add_link(link)

    def add_link(self, link: Link):
        index = len(self._chainlist)
        name = str(index)
        link.name = name
        self._children[name] = link
        self._chainlist.append(link)
        return link

    def __getitem__(self, index):
        return self._chainlist[index]

    def __len__(self):
        return len(self._chainlist)

    def __iter__(self):
        return iter(self._chainlist)


class Sequential(ChainList):
    """Feed-forward composition of links/callables (``chainer.Sequential``)."""

    def __init__(self, *layers):
        super().__init__()
        object.__setattr__(self, "_layers", [])
        for layer in layers:
            self.append(layer)

    def append(self, layer):
        self._layers.append(layer)
        if isinstance(layer, Link):
            self.add_link(layer)
        return self

    def forward(self, x):
        for layer in self._layers:
            x = layer(x)
        return x


# ---------------------------------------------------------------------------
# Functional bridge: Link <-> pytree state
# ---------------------------------------------------------------------------

def extract_state(link: Link) -> dict:
    """Flatten a link into ``{'params': {path: array}, 'state': {path: array}}``.

    The result is a plain nested dict — a JAX pytree — suitable for jit
    arguments, optax states, checkpointing, and collectives.  Persistent
    python scalars (BN finetune counters) are converted to weak-typed
    arrays ONCE and written back into the link, so every compiled step
    sees the same leaf types (a python-scalar jit argument and its
    written-back Array would otherwise occupy two jit cache entries —
    one full extra XLA compilation per step function).
    """
    params = {path: p.array for path, p in link.namedparams() if p.array is not None}
    abstract = [path for path, a in params.items()
                if isinstance(a, jax.ShapeDtypeStruct)]
    if abstract:
        raise ValueError(
            f"{len(abstract)} parameter(s) are still shapes only "
            f"(abstract_init), e.g. {abstract[0]}: load them first")
    state = {}
    for sublink, name, full in _persistent_slots(link):
        value = getattr(sublink, name)
        if value is None or isinstance(value, (str, bytes)):
            continue
        if not isinstance(value, jax.Array):
            value = jnp.asarray(value)
            # write-through: stabilize the leaf type for later extracts
            object.__setattr__(sublink, name, value)
            sublink._persistent[name] = value
        state[full] = value
    return {"params": params, "state": state}


def param_tree(link: Link) -> dict:
    return {path: p.array for path, p in link.namedparams() if p.array is not None}


def grad_tree(link: Link) -> dict:
    return {path: p.grad for path, p in link.namedparams() if p.grad is not None}


def set_grads(link: Link, grads: dict):
    for path, p in link.namedparams():
        if path in grads:
            p.grad = grads[path]


def load_param_tree(link: Link, params: dict):
    for path, p in link.namedparams():
        if path in params:
            p.array = params[path]


def cast_params(link: Link, dtype):
    """Hold every floating parameter of ``link`` in ``dtype``, one leaf
    at a time: each leaf's old array loses its last reference here as
    its cast replaces it, so the peak is the tree plus one leaf, not two
    trees (the caller must hold no other reference to the old arrays).
    """
    dtype = jnp.dtype(dtype)
    for p in link.params():
        a = p.array
        if a is not None and not p.is_abstract and a.dtype != dtype \
                and jnp.issubdtype(a.dtype, jnp.floating):
            p.array = a.astype(dtype)
            del a


def _persistent_slots(link: Link):
    """Yield (owner_link, attr_name, path) for every persistent array slot."""
    for path, sublink in link.namedlinks():
        for name in sublink._persistent:
            full = (path if path != "/" else "") + "/" + name
            yield sublink, name, full


@contextlib.contextmanager
def bind_state(link: Link, state: dict):
    """Temporarily install pytree arrays into the link (e.g. tracers under jit).

    On exit the original arrays are restored and any *persistent* values the
    forward pass replaced (BN running stats) are gathered into
    ``handle.updated_state``.  This is the bridge that lets define-by-run
    looking model code run inside a traced, purely-functional train step.
    """
    params = state.get("params", state)
    pstate = state.get("state", {})
    saved_params = []
    for path, p in link.namedparams():
        if path in params:
            saved_params.append((p, p.array))
            p.array = params[path]
    saved_persistent = []
    for sublink, name, full in _persistent_slots(link):
        if full in pstate:
            saved_persistent.append((sublink, name, full, getattr(sublink, name)))
            object.__setattr__(sublink, name, pstate[full])
            sublink._persistent[name] = pstate[full]
    # volatile per-call state (stateful LSTM/GRU hidden values): restored
    # on exit so traced calls can't leak tracers into link attributes
    saved_volatile = []
    for sublink in link.links():
        for name in getattr(sublink, "_volatile_attrs", ()):
            saved_volatile.append((sublink, name, getattr(sublink, name)))

    class _Handle:
        updated_state: dict = {}

        def collect(self):
            out = {}
            for sublink, name, full, _ in saved_persistent:
                out[full] = getattr(sublink, name)
            self.updated_state = out
            return out

    handle = _Handle()
    try:
        yield handle
    finally:
        handle.collect()
        for p, arr in saved_params:
            p.array = arr
        for sublink, name, full, orig in saved_persistent:
            object.__setattr__(sublink, name, orig)
            sublink._persistent[name] = orig
        for sublink, name, orig in saved_volatile:
            object.__setattr__(sublink, name, orig)


def apply_state(link: Link, state: dict, *args, **kwargs):
    """Call ``link(*args)`` with ``state`` bound; return (output, new_state).

    ``new_state`` carries forward-mutated persistent values.  Pure function of
    (state, args) — safe to ``jax.jit`` / ``jax.grad``.
    """
    with bind_state(link, state) as handle:
        out = link(*args, **kwargs)
        new_persistent = handle.collect()
    return out, {"params": state.get("params", state), "state": new_persistent}
