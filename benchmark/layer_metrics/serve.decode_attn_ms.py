"""Device time a decode step of attention over the cache: roles ``attn``
(``_paged_decode_kernel`` or the paged gathers with their scores and
values, ``paged_latent_attention``) and ``cache_write`` (the step"s K/V
or latent written to its page).

Read from each operation"s ``tf_op`` (``benchmark/device_scopes.py``): an
operation counts where it lies inside one of the program"s runs that lie
wholly in the traced window, on the first chip; a fusion is booked
whole, by the one ``tf_op`` XLA kept for it; the sum is divided by those
runs.  ``None`` where the program did not run there, or carries no
role at all (a commit before PR 38, or an executable kept from then)."""

from benchmark import device_scopes


def read(view):
    return device_scopes.role_ms(view, "decode", ("attn", "cache_write"))
