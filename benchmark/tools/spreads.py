"""The spreads a cell's bounds are set from: ``python3 -m
benchmark.tools.spreads <file written by full_sets.sh> ...`` prints, for
each end-to-end metric, each set's median and its spread (the distance
between the first and third quartile of ``statistics.quantiles(values,
n=4)`` as a share of the median), the wider of the two, how far the
second set's median lies from the first's, and each set's range less its
farthest run (how the driver's check reckons a cell's noise against a
bound: PR 41 was refused on it)."""

from __future__ import annotations

import json
import re
import statistics
import sys


def read(path):
    rows = []
    with open(path) as f:
        for text in f:
            m = re.search(r'"set": (\d+), "seed": (\d+).*"line": (\{.*\})\}\s*$',
                          text)
            if m:
                rows.append((int(m.group(1)), int(m.group(2)),
                             json.loads(m.group(3))))
    return rows


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def range_less_farthest(values):
    """The runs' range as a share of their median, the run farthest from
    the median left out."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))
    kept = kept[:-1] if len(kept) > 2 else kept
    return (max(kept) - min(kept)) / med


def main(argv):
    for path in argv:
        rows = read(path)
        print(path, len(rows), "runs; correct:",
              sum(1 for r in rows if r[2]["correct"]), "; failed:",
              sum(r[2]["failed"] for r in rows))
        names = list(rows[0][2]["metrics"])
        for name in names:
            sets = {s: [r[2]["metrics"][name]["value"] for r in rows
                        if r[0] == s] for s in (1, 2)}
            # each side's first run compiles and is recorded apart
            used = {s: v[1:] if name == "setup_s" and s == 1 else v
                    for s, v in sets.items()}
            med = {s: statistics.median(v) for s, v in used.items() if v}
            spr = {s: spread(v) for s, v in used.items() if len(v) >= 2}
            rng = {s: round(range_less_farthest(v), 5)
                   for s, v in used.items() if len(v) >= 2}
            print(f"  {name}: medians {med}, spreads "
                  f"{ {s: round(v, 5) for s, v in spr.items()} }, wider "
                  f"{max(spr.values()):.5f}, second/first median "
                  f"{med.get(2, float('nan')) / med[1] - 1:+.5f}; range less "
                  f"the farthest run {rng}; values "
                  f"{ {s: [round(x, 4) for x in v] for s, v in sets.items()} }")
        print("  memory_peak_bytes", sorted(
            {r[2]["device"]["memory_peak_bytes"] for r in rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
