"""Device time a step of the operations with an EMPTY ``tf_op``: what the
compiler made itself (the weights prefetched by ``copy-start`` /
``copy-done`` and ``slice-start`` / ``slice-done``, layout ``copy``s).
``train.layout_ms`` counts by instruction name instead, so it also takes
``reshape`` and ``transpose`` operations that DO carry a scope: the two
need not agree.

Read from each operation's ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program's runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there."""

from benchmark import device_scopes


def read(view):
    return device_scopes.ms_a_run(view, "step", lambda p: not p.scoped,
                                  needs_roles=False)
