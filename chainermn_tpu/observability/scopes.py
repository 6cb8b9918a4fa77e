"""Names on the device: the ROLE of each part of a model.

A role is a ``jax.named_scope`` whose name is marked ``~`` (``~attn``,
``~mlp``): it lands in the compiled program as part of every
instruction's ``op_name``, and the profiler stamps it on every device
operation (``tf_op``), so device time can be read by role with no second
compile and no reader of ours (XProf's op profile and trace viewer show
the same path).  The mark keeps a role apart from a link that happens to
carry the same name (``TransformerBlock.attn``); the innermost role on an
operation's path is its role.  A scope is metadata: it changes no
instruction, and it has to be open when the program is TRACED, so there
is no switch.  (Not ``@``: XLA cuts an ``op_name`` at the first one.)
``docs/observability.md``, "Names on the device".
"""

from __future__ import annotations

import jax

#: the vocabulary, which is the contract (``docs/observability.md``)
ROLES = ("embed", "norm", "attn_proj", "cache_write", "attn", "state",
         "mlp", "router", "experts", "head", "loss")
ROLE_MARK = "~"


def role(name):
    """``jax.named_scope`` of one role of :data:`ROLES`; a context
    manager, and a decorator for an entry point that is one role
    whole."""
    if name not in ROLES:
        raise ValueError(f"unknown role {name!r}; the vocabulary is {ROLES}")
    return jax.named_scope(ROLE_MARK + name)
