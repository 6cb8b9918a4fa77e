"""Device time a step of the vocabulary by scope: roles ``embed``, ``head``
and ``loss`` under ``mn_forward_backward``, both directions (the lookup
and its scatter-add gradient, the final norm, the head's three GEMM
fusions, the loss's row reductions).  The head's Adam rides in its
weight-gradient fusion and is counted; Adam over the embedding is a loop
fusion of its own under ``mn_optimizer_update``, where no role reaches,
and is not.  ``train.vocab_ms`` counts the same work by the shapes in an
operation's text.

Read from each operation's ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program's runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there, or carries no
role at all (a commit before PR 38, or an executable kept from then)."""

from benchmark import device_scopes


def read(view):
    return device_scopes.role_ms(view, "step", ("embed", "head", "loss"),
                                 within="mn_forward_backward")
