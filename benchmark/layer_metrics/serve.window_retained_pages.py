"""Mean pages of the window group that the prefix trie alone holds
(``window_retained_pages`` at the end of each ``serve/step`` that had a
batch running): what a later prefix hit can still read after every
holder's window has moved on, given up first when the pool runs short.
A program whose engine has one page pool gives None."""

import statistics

from benchmark import program_spans


def read(view):
    kept = [s.stats["window_retained_pages"]
            for s in program_spans.named(view, "serve/step")
            if s.stats.get("running") and "window_retained_pages" in s.stats]
    return statistics.fmean(kept) if kept else None
