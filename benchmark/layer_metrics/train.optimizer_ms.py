"""Device time a step of the operations under ``mn_optimizer_update``: what
of the update stands alone (Adam over the embedding, a loop fusion of its
own).  The updates that ride in the epilogue of a weight-gradient GEMM
carry the GEMM's ``tf_op`` and are ``train.bwd_ms``'s.

Read from each operation's ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program's runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there."""

from benchmark import device_scopes


def read(view):
    return device_scopes.ms_a_run(
        view, "step", lambda p: p.phase == "mn_optimizer_update",
        needs_roles=False)
