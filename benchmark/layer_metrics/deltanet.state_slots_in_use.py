"""Mean share of the state group's slots in use, ``state_used_slots``
over ``state_num_slots`` at the end of each ``serve/step`` that had a
batch running: every live sequence's slot and the snapshots on the
prefix trie.  A program whose engine keeps no state slots sets no such
stat and gives None."""

import statistics

from benchmark import program_spans


def read(view):
    shares = [s.stats["state_used_slots"] / s.stats["state_num_slots"]
              for s in program_spans.named(view, "serve/step")
              if s.stats.get("running") and s.stats.get("state_num_slots")]
    return 100.0 * statistics.fmean(shares) if shares else None
