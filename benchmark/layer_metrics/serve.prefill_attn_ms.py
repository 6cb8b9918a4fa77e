"""Device time a prefill (full and suffix alike) of attention: roles
``attn`` (the flash kernels of a whole prompt, the blocked softmax of a
suffix over its context) and ``cache_write`` (the prompt"s entries
written to their pages).

Read from each operation"s ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program"s runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there, or carries no
role at all (a commit before PR 38, or an executable kept from then)."""

from benchmark import device_scopes


def read(view):
    return device_scopes.role_ms(view, "prefill", ("attn", "cache_write"))
