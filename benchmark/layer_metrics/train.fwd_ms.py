"""Device time a step of the FORWARD pass: the operations under
``mn_forward_backward`` whose path holds no ``transpose(`` (the forward
GEMMs, the flash forward kernel, the norms, the loss).

Read from each operation's ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program's runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there."""

from benchmark import device_scopes


def read(view):
    return device_scopes.ms_a_run(
        view, "step", lambda p: p.phase == "mn_forward_backward"
        and not p.backward, needs_roles=False)
