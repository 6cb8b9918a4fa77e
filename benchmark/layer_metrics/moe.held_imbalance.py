"""The fullest held expert's copies over the mean over held experts, per
expert layer: ``held_max`` over ``held_copies / held`` of each
``serve/decode_window`` span, the mean over the steps in which any copy
landed here.  ``held`` is the configuration's ``n_routed_experts``, the
experts this chip holds.  A program without the stats gives None."""

import statistics

from benchmark import program_spans


def read(view):
    held = view["run"].config.get("n_routed_experts")
    if not held:
        return None
    ratios = [s.stats["held_max"] * held / s.stats["held_copies"]
              for s in program_spans.named(view, "serve/decode_window")
              if s.stats.get("held_copies")]
    return statistics.fmean(ratios) if ratios else None
