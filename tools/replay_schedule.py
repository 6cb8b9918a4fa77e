#!/usr/bin/env python3
"""Replay a serving cell's own schedule on the host with given step times.

ROADMAP asks every serving claim for this before it spends a chip call
(PR 43's method): below its knee a cell's ``serve_tokens_per_s`` is the
offered load less the requests in flight at the close, so a shorter
cycle shows only as whole requests that finish inside the window, and
whether it can is a matter of the schedule, which is fixed by the cell's
traffic file (``benchmark.traffic_gen.generate``: the arrivals, their
tenants and their sizes do not depend on ``--seed``).

The model is one server that does one thing at a time: an arrival that
is due and finds a free lane is admitted by a prefill, a suffix prefill
where a request of its tenant is still in flight (a live holder of the
prefix) and a full one where none is, and the prefill gives the request
its first token; otherwise a decode step gives every lane a token and
costs ``decode_ms + decode_ms_per_lane x lanes``.  A request is finished
when its last token lands.  Counted: the tokens of the requests finished
inside the window (what ``benchmark/drivers/serve.py`` counts for a cell
below its knee) and the tokens stamped inside it (what it counts for a
traffic file with ``"count": "tokens"``).

    python tools/replay_schedule.py kimi-k2.6-serve-agent \\
        --decode-ms 11.0 --suffix-ms 41 --full-ms 120

No device, no JAX: a number from this is a count of a schedule, never a
measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def replay(mix, lanes, seconds, decode_ms, suffix_ms, full_ms,
           decode_ms_per_lane=0.0):
    """``{"due", "finished", "tokens_of_finished", "tokens_stamped",
    "in_flight_at_close"}`` of ``mix``'s schedule over ``seconds``."""
    from benchmark.traffic_gen import generate
    arrivals = generate(mix, 2, 0, seconds)
    now, nxt = 0.0, 0
    running = []                # [tenant, tokens still owed, budget]
    finished = tokens_of_finished = stamped = 0

    def land(request):
        """One token of ``request`` at ``now``; True when it was the last."""
        nonlocal finished, tokens_of_finished, stamped
        request[1] -= 1
        if now <= seconds:
            stamped += 1
        if request[1] == 0 and now <= seconds:
            finished += 1
            tokens_of_finished += request[2]
        return request[1] == 0

    while now <= seconds and (nxt < len(arrivals) or running):
        if nxt < len(arrivals) and arrivals[nxt].due <= now \
                and len(running) < lanes:
            a = arrivals[nxt]
            nxt += 1
            held = any(r[0] == a.tenant for r in running)
            now += (suffix_ms if held else full_ms) / 1e3
            request = [a.tenant, a.max_new_tokens, a.max_new_tokens]
            if not land(request):
                running.append(request)
        elif running:
            now += (decode_ms + decode_ms_per_lane * len(running)) / 1e3
            running = [r for r in running if not land(r)]
        else:
            now = arrivals[nxt].due         # idle until the next arrival
    return {"due": len(arrivals), "finished": finished,
            "tokens_of_finished": tokens_of_finished,
            "tokens_stamped": stamped,
            "in_flight_at_close": len(running)}


def replay_cell(cell, seconds, **step_times):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell + ".json")) as f:
        traffic = json.load(f)
    return replay(traffic["mix"], traffic["engine"]["max_batch"], seconds,
                  **step_times)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell", help="a serving cell of BENCHMARK.json")
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--decode-ms", type=float, required=True)
    p.add_argument("--decode-ms-per-lane", type=float, default=0.0)
    p.add_argument("--suffix-ms", type=float, required=True)
    p.add_argument("--full-ms", type=float, required=True)
    args = p.parse_args(argv)
    out = replay_cell(args.cell, args.seconds, decode_ms=args.decode_ms,
                      decode_ms_per_lane=args.decode_ms_per_lane,
                      suffix_ms=args.suffix_ms, full_ms=args.full_ms)
    out["tokens_per_s_by_requests"] = out["tokens_of_finished"] / args.seconds
    out["tokens_per_s_by_stamps"] = out["tokens_stamped"] / args.seconds
    print(json.dumps(out))


if __name__ == "__main__":
    main()
