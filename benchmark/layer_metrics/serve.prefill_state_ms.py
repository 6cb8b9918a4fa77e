"""Device time a prefill (full and suffix alike) of the gated delta rule:
role ``state``, that is the convolution, the XLA pass AHEAD of
``_gated_delta_chunk_kernel`` (decays, the unit-triangular solve, W, U,
the masked score blocks), the kernel, and the states and convolution
inputs written to their slots.  ``deltanet.prefill_roofline`` reads the
kernel alone.

A model that keeps no recurrent state reads 0: the Laguna cell is listed
because its rehearsal holds every ``serve.`` metric to list it, and reads
0 there (ISSUE 38 called this metric ``deltanet.``; the Olmo cell's
rehearsal admits no new name outside ``serve.``).

Read from each operation's ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program's runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there, or carries no
role at all (a commit before PR 38, or an executable kept from then)."""

from benchmark import device_scopes


def read(view):
    return device_scopes.role_ms(view, "prefill", ("state",))
