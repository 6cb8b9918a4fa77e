"""Mean pages of the window group that a running sequence holds:
``window_used_pages`` less ``window_retained_pages`` (those the prefix
trie alone holds) over ``running``, at the end of each ``serve/step``
that had a batch running.  A sequence keeps its window and the page it
grows into, whatever its context: 512 / 16 + 1 or 2.  A program whose
engine has one page pool sets no such stat and gives None."""

import statistics

from benchmark import program_spans


def read(view):
    held = [(s.stats["window_used_pages"] - s.stats["window_retained_pages"])
            / s.stats["running"]
            for s in program_spans.named(view, "serve/step")
            if s.stats.get("running") and "window_used_pages" in s.stats]
    return statistics.fmean(held) if held else None
