"""Shared by the builders: construct a link without its host-side draw,
then load device-made weights into it."""

from __future__ import annotations

import contextlib

import numpy as np


class _NoDraw:
    """Stands in for the ``RandomState`` the links draw from: the shape is
    kept, the values are zeros (every leaf is overwritten afterwards)."""

    def normal(self, loc=0.0, scale=1.0, size=None):
        return np.zeros(size, np.float32)

    uniform = normal


@contextlib.contextmanager
def host_draws_skipped():
    """The links draw initial weights with numpy on the host while they
    are constructed (16 s for GPT-2-medium's 406 M parameters).  The
    benchmark makes its weights on the device from the seed and ``load``
    overwrites every leaf, so the draw is skipped for the constructor's
    duration where the links still draw through ``nn.links._rng``; where
    a later program has renamed it, construction just pays the draw."""
    from chainermn_tpu.nn import links
    saved = getattr(links, "_rng", None)
    if saved is None:
        yield
        return
    links._rng = lambda seed=None: _NoDraw()
    try:
        yield
    finally:
        links._rng = saved


def param_spec(model, init_rule):
    """``(path, shape, rule)`` for every parameter of the link."""
    return tuple((path, tuple(p.shape), init_rule(path, tuple(p.shape)))
                 for path, p in model.namedparams())


def load(model, params):
    """Every leaf of the link takes the device-made array of its path:
    none keeps what its constructor drew (or did not draw)."""
    from chainermn_tpu.core.link import load_param_tree
    missing = [path for path, _ in model.namedparams() if path not in params]
    if missing:
        raise ValueError(f"no seeded weights for {missing[:3]} ...")
    load_param_tree(model, params)
