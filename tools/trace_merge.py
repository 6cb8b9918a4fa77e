#!/usr/bin/env python
"""Merge rank-tagged Chrome-trace JSONL shards into ONE Perfetto file.

Every rank's span tracer exports its own shard
(``trace-rank<N>.jsonl`` — one Chrome trace event per line, ``pid`` =
rank; see ``docs/observability.md``).  This tool joins them:

* events are DEDUPED by (pid, tid, ts, ph, name) — re-exported or
  doubly-collected shards (a rank that exported both at a checkpoint
  and at exit) collapse to one copy, while distinct events are NEVER
  dropped (the lossless-merge property the tier-1 test pins);
* the union is sorted by ``ts`` (ties keep first-seen order, so B
  before E at equal timestamps survives) and validated against the
  committed schema (``observability.validate_events``) — an invalid
  merge is refused with a nonzero exit, never written;
* output is Chrome trace "JSON array" format (``[...]``), which
  Perfetto / ``chrome://tracing`` load directly.

Usage::

    python tools/trace_merge.py -o merged.json result/trace-rank*.jsonl

Library surface: :func:`merge_events` / :func:`merge_files` (used by
the tier-1 tests).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chainermn_tpu.observability import (read_jsonl, repair_balance,
                                         validate_events)


def _dedupe_key(ev):
    return (ev.get("pid"), ev.get("tid"), ev.get("ts"), ev.get("ph"),
            ev.get("name"))


def merge_events(shards):
    """Merge per-rank event lists: dedupe ACROSS shards by
    (rank, tid, ts, ph, name, intra-shard occurrence), ts-sort (stable
    — intra-shard order breaks ties), validate.  Returns the merged
    event list; raises ``ValueError`` on a schema-invalid result.

    The occurrence counter matters: two DISTINCT events inside one
    shard may legitimately share the full key (back-to-back
    sub-microsecond spans of the same name on one lane) — deduping
    them would orphan an E and turn a valid shard into a refused
    merge.  Only the cross-shard duplicates (the same ring exported
    twice) collapse."""
    seen = set()
    merged = []
    for shard in shards:
        occurrence = {}
        for ev in shard:
            key = _dedupe_key(ev)
            n = occurrence.get(key, 0)
            occurrence[key] = n + 1
            if (key, n) in seen:
                continue
            seen.add((key, n))
            merged.append(ev)
    # metadata events (ph == M) lead, then ts order; Python's stable
    # sort keeps each shard's B-before-E ordering at equal ts
    merged.sort(key=lambda e: (0 if e.get("ph") == "M" else 1,
                               e.get("ts", 0)))
    # checkpoint + exit exports of the SAME ring: the first export
    # closed still-open spans with a synthetic E, the second carries
    # the real E at a later ts — after the cross-shard dedupe the
    # extra E is an orphan.  The shared repair pass drops it (and
    # closes any B left open), so the merge of a run's own shards can
    # never be refused; validation then guards only genuinely
    # malformed input.
    try:
        merged = repair_balance(merged)
    except (KeyError, TypeError) as e:
        raise ValueError(f"malformed event in shard: {e!r}") from e
    validate_events(merged)
    return merged


def merge_files(paths, out_path=None):
    """Merge JSONL shard files; optionally write the Perfetto-loadable
    JSON array.  Returns the merged event list."""
    merged = merge_events([read_jsonl(p) for p in paths])
    if out_path is not None:
        with open(out_path, "w") as f:
            f.write("[\n")
            f.write(",\n".join(json.dumps(ev) for ev in merged))
            f.write("\n]\n")
    return merged


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("shards", nargs="+",
                    help="rank-tagged JSONL trace shards")
    ap.add_argument("-o", "--out", required=True,
                    help="merged Perfetto-loadable JSON array")
    args = ap.parse_args(argv)
    try:
        merged = merge_files(args.shards, args.out)
    except ValueError as e:
        print(f"trace_merge: REFUSED (schema-invalid merge): {e}",
              file=sys.stderr)
        return 1
    ranks = sorted({ev.get("pid") for ev in merged
                    if ev.get("ph") != "M"})
    print(f"trace_merge: {len(merged)} events from "
          f"{len(args.shards)} shard(s), ranks {ranks} -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
