"""Device time a decode step of the operations with an EMPTY ``tf_op``:
what the compiler made itself (whole-pool layout ``copy``s, prefetched
weights).

Read from each operation's ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program's runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there."""

from benchmark import device_scopes


def read(view):
    return device_scopes.ms_a_run(view, "decode", lambda p: not p.scoped,
                                  needs_roles=False)
