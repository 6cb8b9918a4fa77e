"""Mean slots of the state group that the prefix trie alone holds
(``state_retained_slots`` at the end of each ``serve/step`` that had a
batch running): the snapshots of state a later prefix hit restores
instead of replaying the prompt, given up first when the pool runs
short.  A program whose engine keeps no state slots gives None."""

import statistics

from benchmark import program_spans


def read(view):
    kept = [s.stats["state_retained_slots"]
            for s in program_spans.named(view, "serve/step")
            if s.stats.get("running") and "state_retained_slots" in s.stats]
    return statistics.fmean(kept) if kept else None
