"""Driver ``serve``: a language model behind ``ServingEngine`` under an
open loop of arrivals at a rate fixed in the cell.

One thread submits each request when it is due and steps the engine, as
bench.py's serving loop does.  Requests are timed from when they were
due; the run prints how late the generator ran.  After the window the
requests in flight are finished (their first-token times and gaps count),
and a sample of the finished requests, drawn from the seed with the
longest in it, is held against the plain reference.

**Two ways to count a window's tokens**, chosen by the cell's traffic
file (``drive`` says why there are two):

- no ``count`` key: the tokens of the requests that FINISHED before the
  close, over the time at which the step that crossed the close
  returned.  Below a knee that is the offered load; the cells accepted
  before PR 42 keep it, so their history in the ledger stays comparable.
- ``"count": "tokens"``: every token whose own stamp lies inside the
  window, of every request submitted in it, finished or in flight, over
  the window's length on the clock.  A cell above its knee takes this
  one: there finishes crowd at every instant, and a count by whole
  requests swings by requests (3.5 % over six runs of the Laguna peak
  file where the stamps spread 0.9 %: PERF.md section 6, PR 42).

``"trace_after_s": <seconds>`` moves a TRACED run's window off the ramp:
the run drives ``trace_after_s + trace_seconds`` of schedule in one go
and opens the profiler once ``trace_after_s`` have passed, so every
per-layer reader (device trace, program spans and counters) reads the
plateau and not an engine filling up from empty.  A plain run ignores
the key.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from .. import harness, tracing, traffic_gen, weights
from ..models import _init


def _buckets(lengths, lo, hi):
    """The engine's power-of-two buckets (from ``lo``, capped at ``hi``)
    that these lengths fall into."""
    out = set()
    for n in lengths:
        b = lo
        while b < n and b < hi:
            b *= 2
        out.add(min(b, hi))
    return sorted(out)


class Program:
    """The system under test: one engine, its programs warm."""

    def __init__(self, run):
        from chainermn_tpu.serving import ServingEngine
        mix, eng = run.traffic["mix"], run.traffic["engine"]
        self.run, self.mix = run, mix
        builder = harness.load_module("models", run.config["builder"])
        self.model = builder.build(run.config, max_len=eng["max_context"])
        self.spec = _init.param_spec(self.model, builder.init_rule)
        _init.load(self.model, weights.make_params(self.spec, run.seed))
        self.engine = ServingEngine(
            self.model, num_pages=eng["num_pages"],
            page_size=eng["page_size"], max_batch=eng["max_batch"],
            max_context=eng["max_context"], max_queue=eng["max_queue"])
        self.vocab = run.config["vocab_size"]
        self._ids = 0

    def request(self, prompt, max_new, tenant="warm", arrival=None):
        from chainermn_tpu.serving import Request
        self._ids += 1
        return Request(prompt, max_new, tenant=f"t{tenant}",
                       arrival_time=time.monotonic() if arrival is None
                       else arrival, request_id=self._ids)

    def busy(self):
        e = self.engine
        return bool(e.running or e.prefilling or e.scheduler.pending())

    def drain(self):
        while self.busy():
            self.engine.step()

    def trace_counts(self):
        e = self.engine
        return (e.prefill_traces, e.prefix_prefill_traces, e.decode_traces,
                e.fork_traces, e.chunk_traces, e.spec_traces,
                e.transfer_traces)

    def warm_up(self):
        """Drive, through ``submit`` and ``step`` alone, every program
        the mix's lengths can reach and no other: the prefill bucket of
        each prompt length, the prefix-hit suffix prefill of each tail
        length, and the decode program at every batch bucket.  The page
        fork needs a live prompt that ends inside a page right behind
        the shared pages, which tails of a page or more never give."""
        mix, eng = self.mix, self.run.traffic["engine"]
        rng = np.random.default_rng(0)
        S = mix["prefix_len"]
        lo, hi = mix["tail"]
        if lo < eng["page_size"] or S % eng["page_size"]:
            raise harness.BenchmarkError(
                "a mix with tails shorter than a page, or a system prompt "
                "that ends inside one, can reach the engine's page fork, "
                "which this warm-up does not drive")

        def toks(n):
            return rng.integers(0, self.vocab, n, dtype=np.int32)

        for b in _buckets(range(S + lo, S + hi + 1), 16,
                          eng["max_context"]):
            self.engine.submit(self.request(toks(min(b, S + hi)), 1))
            self.drain()
        system = toks(S)
        holder_tail = toks(hi)
        lanes = eng["max_batch"]
        # a holder that stays live while the rest of the warm-up shares
        # its prefix
        self.engine.submit(self.request(
            np.concatenate([system, holder_tail]), 4 * lanes))
        self.engine.step()
        for b in _buckets(range(lo, hi + 1), 16, eng["max_context"]):
            self.engine.submit(self.request(
                np.concatenate([system, toks(max(lo, min(b, hi)))]), 1))
            self.engine.step()
        # decode at every batch bucket: lanes join one group at a time
        live, group = 1, 1
        while live < lanes:
            for _ in range(group):
                self.engine.submit(self.request(
                    np.concatenate([system, toks(lo)]), 2 * lanes))
            self.engine.step()
            live += group
            group = live
        self.drain()


def reference_gap(run, spec, sample, precision="float32",
                  control=None):
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample: one reference pass over each
    prompt with its served tokens.  With ``control`` (a precision), the
    token judged at each position is the one the control puts first."""
    ref = harness.load_module("reference", run.config["reference"])
    params = weights.make_params(spec, run.seed)
    T = run.traffic["engine"]["max_context"]
    worst, n_tokens = 0.0, 0
    for prompt, tokens in sample:
        full = np.zeros(T, np.int32)
        n = len(prompt) + len(tokens)
        full[:len(prompt)] = prompt
        full[len(prompt):n] = tokens
        rows = slice(len(prompt) - 1, n - 1)
        logits = np.asarray(ref.sequence_logits(run.config, params, full,
                                                precision=precision))[rows]
        judged = np.asarray(tokens)
        if control is not None:
            judged = np.asarray(ref.sequence_logits(
                run.config, params, full, precision=control))[rows] \
                .argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(len(judged)), judged]
        worst = max(worst, float(gaps.max()))
        n_tokens += len(judged)
    return worst, n_tokens


def pick_sample(finished, seed, k):
    """``k`` finished requests drawn from the seed, the longest first."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: r.prompt.size + len(r.tokens))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    chosen = [longest] + [rest[i] for i in order[:k - 1]]
    return [(np.asarray(r.prompt), list(r.tokens)) for r in chosen]


def tokens_stamped(requests, lo, hi):
    """The tokens of ``requests`` whose stamp lies in ``[lo, hi]`` (the
    engine's ``time.monotonic()``, as the window's edges are)."""
    return sum(1 for r in requests for t in r.token_times if lo <= t <= hi)


def drive(prog, arrivals, seconds, tracer=None, count=None,
          trace_after_s=None):
    """The window: submit each arrival when it is due, step the engine,
    close at ``seconds`` once every arrival is in, then finish what is
    in flight.  Returns what was measured; the rate is
    ``tokens_in_window / counted_s``.

    ``count`` says what ``tokens_in_window`` is.  ``None``: the tokens of
    the requests that finished before the close, all or nothing, and
    ``counted_s`` the time at which the step that crossed the close
    returned.  ``"tokens"``: the stamps ``t`` of ``token_times`` with
    ``t <= base + seconds``, over every request submitted, finished or
    in flight, and ``counted_s`` is ``seconds``: the window closes on
    the clock, and a step that crosses the close adds nothing.  There
    are two because below a knee both are the offered load, and the
    cells that count by requests keep their ledger history comparable
    (moving them would raise each by the tokens of the 5-13 requests in
    flight at its close, 2-3 %); above a knee a request finishes at
    every instant, the close cannot be put on a plateau of the schedule,
    and only the stamps mean anything: their quantum is one decode step.
    Either way the result carries both counts (``tokens_of_finished``,
    ``tokens_stamped``).

    With ``trace_after_s`` the tracer is entered inside the loop, once
    that many seconds have passed, and the window that is counted and
    traced opens when the profiler has started (``opened_s``) and closes
    ``seconds - trace_after_s`` later: the ramp is driven, not read."""
    import jax
    engine = prog.engine
    n_warm = len(engine.completed)
    steps_warm, hits_warm = engine.decode_steps, engine.prefix_hits
    requests, late_ms, refused = [], [], 0
    queued_steps = steps = 0
    late_open = tracer is not None and trace_after_s is not None
    with contextlib.ExitStack() as stack:
        if tracer is not None and not late_open:
            stack.enter_context(tracer)
        base = time.monotonic()
        opened, close = (None, float("inf")) if late_open else (0.0, seconds)
        i = 0
        while True:
            t = time.monotonic() - base
            if opened is None and t >= trace_after_s:
                stack.enter_context(tracer)
                t = opened = time.monotonic() - base
                close = opened + seconds - trace_after_s
            while i < len(arrivals) and arrivals[i].due <= t:
                a = arrivals[i]
                req = prog.request(a.prompt, a.max_new_tokens, a.tenant,
                                   arrival=base + a.due)
                req.due = base + a.due
                try:
                    engine.submit(req)
                    requests.append(req)
                except Exception as e:   # refused: counts as failed
                    refused += 1
                    harness.say({"refused": repr(e)})
                late_ms.append((time.monotonic() - base - a.due) * 1e3)
                i += 1
            if t >= close and i == len(arrivals):
                break
            with jax.profiler.TraceAnnotation("bench/engine_step"):
                st = engine.step()
            steps += 1
            queued_steps += engine.scheduler.pending() > 0
            if st["decoded"] == 0 and st["admitted"] == 0:
                with jax.profiler.TraceAnnotation("bench/wait_arrival"):
                    time.sleep(0.0005)
        window_s = time.monotonic() - base
    done = list(engine.completed[n_warm:])
    of_finished = sum(len(r.token_times) for r in done)
    stamped = tokens_stamped(requests, base + opened, base + close)
    by_stamps = count == "tokens"
    out = {"window_s": window_s, "due": len(arrivals),
           "finished_in_window": len(done),
           "in_flight_at_close": len(requests) - len(done),
           "queued_at_close": engine.scheduler.pending(),
           "queued_share_of_steps": queued_steps / max(steps, 1),
           "decode_steps_at_close": engine.decode_steps - steps_warm,
           "prefix_hits_at_close": engine.prefix_hits - hits_warm,
           "count": "tokens" if by_stamps else "requests",
           "opened_s": opened, "tokens_of_finished": of_finished,
           "tokens_stamped": stamped,
           "tokens_in_window": stamped if by_stamps else of_finished,
           "counted_s": close - opened if by_stamps else window_s}
    prog.drain()
    finished = list(engine.completed[n_warm:])
    ttft_ms, gap_ms, missing = [], [], 0
    for r in requests:
        if r.first_token_time is None:
            missing += 1
            ttft_ms.append((base + window_s - r.due) * 1e3)
            continue
        ttft_ms.append((r.first_token_time - r.due) * 1e3)
        gap_ms.extend(np.diff(r.token_times) * 1e3)
    out.update(requests=requests, finished=finished, late_ms=late_ms,
               ttft_ms=ttft_ms, gap_ms=gap_ms, missing=missing,
               failed=refused + len(requests) - len(finished),
               gap_p50_ms_by_slice=gap_medians(requests, base, close))
    return out


def gap_medians(requests, base, close, slice_s=5.0):
    """The median gap between a request's consecutive tokens, for each
    ``slice_s`` of the window by the later token's stamp: one engine cycle
    (a decode step and the host between two) where the lanes are full.  A
    run whose host gap changes level part way shows it here without a
    profiler (the level is worth 3-8 % of a saturated cell's rate)."""
    slices = [[] for _ in range(int(np.ceil(close / slice_s)))]
    for r in requests:
        t = np.asarray(r.token_times) - base
        for at, gap in zip(t[1:], np.diff(t)):
            if at < close:
                slices[int(at // slice_s)].append(gap * 1e3)
    return [float(np.median(s)) if s else None for s in slices]


def measure(run, prog, watch, checks):
    """The window through a warm engine and every check but the
    reference's: (what ``drive`` measured, set-up seconds, the tracer)."""
    engine = prog.engine
    seconds = min(run.seconds, run.traffic["trace_seconds"]) \
        if run.trace else run.seconds
    after = run.traffic.get("trace_after_s") if run.trace else None
    if after is not None:
        seconds += after
    arrivals = traffic_gen.generate(run.traffic["mix"], prog.vocab,
                                    run.seed, seconds)
    setup_s = time.perf_counter() - run.t0
    mark, traces_warm = watch.snapshot(), prog.trace_counts()
    tracer = tracing.Tracing(tracing.trace_dir(run)) if run.trace else None
    w = drive(prog, arrivals, seconds, tracer,
              count=run.traffic.get("count"), trace_after_s=after)
    compiled, traced = watch.since(mark)
    harness.say({**{k: v for k, v in w.items() if not isinstance(v, list)},
                 "decode_steps": engine.decode_steps,
                 "prefix_hits": engine.prefix_hits,
                 "evictions": engine.evictions, "forks": engine.forks,
                 "setup_s": setup_s, **watch.summary()})
    harness.say({"serve_gap_p50_ms_by_5s_slice": w["gap_p50_ms_by_slice"]})
    harness.say(harness.timing_summary("generator_late_ms", w["late_ms"]))
    harness.say(harness.timing_summary("serve_ttft_ms", w["ttft_ms"]))
    harness.say(harness.timing_summary("serve_gap_ms", w["gap_ms"]))
    checks.require("no_compile_in_window",
                   compiled == 0 and traced == 0
                   and prog.trace_counts() == traces_warm,
                   {"compiled_or_loaded": compiled, "traced": traced})
    checks.require("token_counts",
                   all(len(r.tokens) == r.max_new_tokens
                       for r in w["finished"]), len(w["finished"]))
    checks.require("all_due_finished",
                   w["failed"] == 0 and w["missing"] == 0,
                   {"failed": w["failed"], "no_first_token": w["missing"]})
    return w, setup_s, tracer


def run(run):
    import jax

    if run.traffic.get("count") not in (None, "tokens"):
        raise harness.BenchmarkError(
            "a traffic file's count is \"tokens\" or absent, not "
            f"{run.traffic['count']!r}")
    checks = harness.Checks()
    with harness.watch_compiles() as watch:
        prog = Program(run)
        prog.warm_up()
        w, setup_s, tracer = measure(run, prog, watch, checks)
    device = harness.device_block(run.devices, run.rehearsal)
    metrics = {
        "serve_tokens_per_s": w["tokens_in_window"] / w["counted_s"],
        "setup_s": setup_s,
    }

    # -- the reference, once the engine and its pools are freed
    sample = pick_sample(w["finished"], run.seed,
                         run.traffic["check_requests"])
    spec, attempted, failed = prog.spec, w["due"], w["failed"]
    del prog, w
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    gap, n_tokens = reference_gap(run, spec, sample)
    checks.limit("served_logit_gap", gap,
                 run.traffic["limits"]["served_logit_gap"])
    harness.say({"reference_s": time.perf_counter() - t_ref,
                 "requests_compared": len(sample),
                 "tokens_compared": n_tokens})
    checks.say()
    return {"correct": checks.ok, "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device,
            "tracing": tracer, "checks": checks.rows, "sample": sample,
            "spec": spec}
