"""Mean share of the page pool in use, ``used_pages`` over
``num_pages`` at the end of each ``serve/step`` that had a batch running
(the spins of an empty engine between arrivals are left out)."""

import statistics

from benchmark import program_spans


def read(view):
    shares = [s.stats["used_pages"] / s.stats["num_pages"]
              for s in program_spans.named(view, "serve/step")
              if s.stats.get("running") and s.stats.get("num_pages")]
    return 100.0 * statistics.fmean(shares) if shares else None
