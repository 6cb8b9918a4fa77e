"""The one general traffic generator: an open-loop mix of requests from
a data file's parameters and the seed (after bench.py's
``synth_requests``: Poisson arrivals over tenants, each tenant re-sending
its own fixed system prompt ahead of a tail of its own).

The schedule belongs to the mix, the content to the seed.  Tail and
output lengths are the quantiles of a log-uniform distribution between
the mix's bounds, and the gaps between arrivals the quantiles of the
exponential distribution of the mix's rate; the mix's ``schedule_seed``
shuffles each and deals the tenants, so every run of a cell offers the
same arrivals of the same sizes in the same order (as a replayed trace
would), and a tail read from it is that schedule's tail, not a draw of
the arrival process.  ``--seed`` draws every token: the system prompts
and the tails, and with the weights it decides what is generated.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Arrival:
    due: float              # seconds after the window opens
    tenant: int
    prompt: np.ndarray      # int32 token ids: system prompt, then tail
    max_new_tokens: int


def _log_uniform_quantiles(lo, hi, n):
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))) \
        .astype(int)


def generate(mix, vocab_size, seed, seconds):
    """The arrivals due in ``[0, seconds)``, in order of ``due``."""
    plan = np.random.default_rng(mix["schedule_seed"])
    rng = np.random.default_rng(seed)
    n = max(1, int(round(mix["rate"] * seconds)))
    tails = plan.permutation(_log_uniform_quantiles(*mix["tail"], n))
    outputs = plan.permutation(_log_uniform_quantiles(*mix["output"], n))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / mix["rate"]
    due = np.cumsum(plan.permutation(gaps))
    due *= seconds * (n - 0.5) / n / due[-1]
    tenants = plan.permutation(np.arange(n) % mix["tenants"])
    system = [rng.integers(0, vocab_size, mix["prefix_len"], dtype=np.int32)
              for _ in range(mix["tenants"])]
    return [Arrival(float(due[i]), int(tenants[i]),
                    np.concatenate([system[tenants[i]], rng.integers(
                        0, vocab_size, tails[i], dtype=np.int32)]),
                    int(outputs[i])) for i in range(n)]
