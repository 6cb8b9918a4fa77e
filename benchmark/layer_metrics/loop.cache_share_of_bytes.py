"""The cache's part of a looped model's decode step: of the least bytes
a sound step moves (``loop.decode_hbm_roofline``'s arithmetic: the
blocks' parameters once a pass, the head once, every cache layer of the
live contexts read, the new entries written), the share that is cache,
mean over the window's ``serve/decode_window`` spans.  Whether the
``passes``-fold cache or the ``passes``-fold weights sets the step.  A
program without the span's counts gives None."""

import statistics

from benchmark import harness


def read(view):
    roofline = harness.load_module("layer_metrics",
                                   "loop.decode_hbm_roofline")
    shares = []
    for s in roofline.steps(view):
        least, cache = roofline.step_bytes(
            view["run"].config, s.stats["ctx_tokens"], s.stats["passes"],
            s.stats["batch"])
        shares.append(cache / least)
    return 100.0 * statistics.fmean(shares) if shares else None
