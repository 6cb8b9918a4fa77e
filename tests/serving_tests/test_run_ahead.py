"""One decode run in flight (PR 46): ``ServingEngine.step`` dispatches
decode run ``n + 1``, its tokens taken on the device from run ``n``'s
``nxt``, before it fetches run ``n``.

The reference is the synchronous order the engine keeps for the states
that rule the run-ahead out (dispatch, fetch, record: the parent's
``_step``), reached here by answering ``_may_run_ahead`` with False on
the instance.  Every request's tokens have to be the same under both,
for arrivals mid-stream over tenants that share prefixes, finishes by
``max_new_tokens`` and by ``eos_id``, an eviction of lanes in flight,
and ``spec_k``, chunked prefill and the disaggregated ship, which land
first; the spans say the order on the ring; ``step`` keeps its contract
under a pinned clock; the small gather is warmed with the buckets and is
no program a traffic file's needle reads.
"""

import numpy as np
import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import Request, ServingEngine
from chainermn_tpu.serving.engine import _bucket, next_tokens_program

VOCAB = 89


@pytest.fixture(scope="module")
def model():
    return TransformerLM(n_vocab=VOCAB, d_model=32, n_heads=2, n_layers=1,
                         max_len=128, seed=3)


def _engine(model, sync=False, **kw):
    kw = dict(dict(num_pages=64, page_size=8, max_batch=4,
                   max_context=96), **kw)
    eng = ServingEngine(model, **kw)
    if sync:
        eng._may_run_ahead = lambda: False    # the parent's order
    return eng


def _drive(eng, requests, max_steps=400):
    """Submit everything (arrival times gate admission), step a pinned
    clock of one second a call until the engine is empty."""
    for r in requests:
        eng.submit(r)
    t = 0.0
    while (eng.running or eng.prefilling or eng.scheduler.pending()) \
            and t < max_steps:
        eng.step(now=t)
        assert eng.allocator.check()
        t += 1.0
    assert not (eng.running or eng.prefilling or eng.scheduler.pending())
    assert eng._flight is None          # an idle engine holds no run


def _tokens(eng):
    """``{request id: prompt + tokens}``: whole across the fold of an
    eviction, which moves tokens into the prompt."""
    return {r.request_id: [int(x) for x in r.prompt] + list(r.tokens)
            for r in eng.completed}


def _mixed(seed=0, n=9, tenants=3, max_new=(3, 14)):
    """Arrivals spread over the first calls, ``tenants`` system prompts
    of two pages each shared within a tenant: prefix hits mid-stream."""
    rng = np.random.RandomState(seed)
    systems = [rng.randint(0, VOCAB, 16) for _ in range(tenants)]
    out = []
    for i in range(n):
        tail = rng.randint(0, VOCAB, rng.randint(8, 20))
        prompt = np.concatenate([systems[i % tenants], tail]).astype(np.int32)
        out.append(Request(prompt, int(rng.randint(*max_new)),
                           tenant=f"t{i % tenants}", arrival_time=1.5 * i,
                           request_id=100 + i))
    return out


def _both(model, make, **kw):
    """The same requests through the engine and through its synchronous
    order: both engines, drained."""
    out = []
    for sync in (False, True):
        eng = _engine(model, sync=sync, **kw)
        _drive(eng, make())
        out.append(eng)
    return out


# -- (i)-(iii): the tokens are the synchronous order's -----------------------

@pytest.mark.parametrize("case", ["arrivals", "single_lane", "eviction"])
def test_tokens_equal_the_synchronous_order(model, case):
    kw, make = {
        # tenants sharing prefixes arrive while others decode; lanes
        # retire from the middle, buckets grow and shrink
        "arrivals": ({}, lambda: _mixed(seed=1)),
        # one lane a time: every run but a request's last goes ahead
        "single_lane": ({"max_batch": 1}, lambda: _mixed(seed=2, n=4)),
        # a pool that runs dry mid-decode: the victim is in flight
        "eviction": ({"num_pages": 14, "prefix_cache": False},
                     lambda: _mixed(seed=3, n=6, max_new=(20, 30))),
    }[case]
    ahead, sync = _both(model, make, **kw)
    assert _tokens(ahead) == _tokens(sync)
    assert len(ahead.completed) == len(make())
    assert sync.decode_steps_ahead == 0
    assert ahead.decode_steps_ahead > 0
    # a stamp a token (an eviction folds tokens into the prompt and
    # keeps their stamps)
    stamps = {r.request_id: len(r.token_times) for r in sync.completed}
    assert {r.request_id: len(r.token_times)
            for r in ahead.completed} == stamps
    if case == "arrivals":
        assert ahead.prefix_hits == sync.prefix_hits > 0
    if case == "eviction":
        assert ahead.evictions > 0 and sync.evictions > 0
        assert any(r.preemptions for r in ahead.completed)


def test_a_finish_by_max_new_tokens_is_left_out_of_the_run_ahead(model):
    """The host knows a lane's last token is in flight: the run behind
    it does not carry the lane, so no decode run is spare and the runs
    dispatched are the synchronous order's."""
    def make():
        return [Request(np.arange(1, 12, dtype=np.int32) + i, new,
                        request_id=i) for i, new in enumerate((2, 5, 9))]
    ahead, sync = _both(model, make)
    assert _tokens(ahead) == _tokens(sync)
    assert ahead.decode_steps == sync.decode_steps == 8
    assert [len(r.tokens) for r in sorted(
        ahead.completed, key=lambda r: r.request_id)] == [2, 5, 9]


def test_a_finish_by_eos_drops_the_spare_token_and_keeps_the_pool_sound(
        model):
    """``eos_id`` is found when its run lands, a run late: the lane rode
    the run behind it spare.  That token is recorded nowhere, the
    request ends on its ``eos_id``, and the pages it gave back, taken by
    the next admission while the spare run may still write its one
    position, serve that admission the synchronous order's tokens (every
    program takes the pools the run before it returned)."""
    free = _engine(model, sync=True)
    _drive(free, _mixed(seed=4, n=6, tenants=2, max_new=(10, 16)))
    # a token some request produces in mid-stream ends it there
    victim = max(free.completed, key=lambda r: len(r.tokens))
    eos = victim.tokens[len(victim.tokens) // 2]

    def make():
        return _mixed(seed=4, n=6, tenants=2, max_new=(10, 16))
    ahead, sync = _both(model, make, eos_id=eos, num_pages=20,
                                  max_batch=2)
    assert _tokens(ahead) == _tokens(sync)
    cut = [r for r in ahead.completed if len(r.tokens) < r.max_new_tokens]
    assert cut, "no request ended on eos_id"
    for r in cut:
        assert r.tokens[-1] == eos and eos not in r.tokens[:-1]
        assert len(r.token_times) == len(r.tokens)
    # a spare run was dispatched (the eos was seen a run late) and its
    # token dropped: more runs than the synchronous order, same tokens
    assert ahead.decode_steps > sync.decode_steps
    assert ahead.allocator.check() and ahead._flight is None


# -- (iv): what the engine's state rules out lands first ---------------------

@pytest.mark.parametrize("case", ["spec_k", "chunked", "disagg"])
def test_the_states_that_rule_it_out_take_the_synchronous_order(model,
                                                                case):
    kw = {"spec_k": {"spec_k": 3},
          "chunked": {"chunk_tokens": 16},
          "disagg": {"disagg": True, "prefix_cache": False}}[case]

    def make():
        return _mixed(seed=5, n=6, tenants=2)
    eng = _engine(model, **kw)
    _drive(eng, make())
    plain = _engine(model, sync=True,
                    prefix_cache=kw.get("prefix_cache", True))
    _drive(plain, make())
    assert _tokens(eng) == _tokens(plain)
    if case == "chunked":
        # prompts of 24-35 tokens stream in by chunks of 16: no run goes
        # ahead while one is mid-chunk, and runs do once none is
        assert eng.chunked_admissions > 0
        assert 0 < eng.decode_steps_ahead < eng.decode_steps
    else:
        assert eng.decode_steps_ahead == 0


def test_no_run_goes_ahead_while_a_prompt_is_mid_chunk(model):
    eng = _engine(model, chunk_tokens=16, chunk_budget=16)
    eng.submit(Request(np.arange(1, 13, dtype=np.int32), 30, request_id=1))
    eng.step(now=0.0)
    eng.step(now=1.0)
    assert eng._flight is not None              # a run is ahead
    eng.submit(Request(np.arange(2, 62, dtype=np.int32), 4,
                       arrival_time=2.0, request_id=2))
    before = eng.decode_steps_ahead
    mid_chunk_calls = 0
    while True:
        eng.step(now=2.0)
        if not eng.prefilling:
            break
        # a call that leaves a prompt mid-chunk has landed what was in
        # flight and dispatched nothing behind it
        mid_chunk_calls += 1
        assert eng._flight is None
        assert eng.decode_steps_ahead == before
    assert mid_chunk_calls == 3             # 60 tokens by chunks of 16
    # the call that ran the last chunk goes ahead again
    assert eng.decode_steps_ahead == before + 1
    assert eng._flight is not None


# -- the contract of step() ---------------------------------------------------

def test_step_records_the_tokens_of_its_own_decode_step_by_its_return(
        model):
    """Call ``k`` returns with the tokens of decode step ``k`` recorded
    and stamped with that call's clock, as the synchronous order does:
    the first call dispatches two runs and lands one, every later call
    dispatches one and lands one."""
    eng = _engine(model)
    req = Request(np.arange(1, 10, dtype=np.int32), 6, request_id=1)
    eng.submit(req)
    for k in range(5):
        st = eng.step(now=10.0 + k)
        # the prefill's token and one a call
        assert len(req.tokens) == k + 2
        assert req.token_times[-1] == 10.0 + k
        assert st["decoded"] == 1
        # runs dispatched: one more than landed, until the last token
        # is in flight (a finish by max_new_tokens is known ahead)
        assert eng.decode_steps == min(k + 2, 5)
    assert req.finish_time == 14.0 and eng._flight is None
    assert eng.decode_steps_ahead == 4


def test_a_request_admitted_in_a_call_joins_the_run_that_call_dispatches(
        model):
    eng = _engine(model)
    a = Request(np.arange(1, 10, dtype=np.int32), 12, request_id=1)
    eng.submit(a)
    eng.step(now=0.0)
    b = Request(np.arange(3, 14, dtype=np.int32), 12, arrival_time=1.0,
                request_id=2)
    eng.submit(b)
    eng.step(now=1.0)           # admits b behind the run in flight
    assert len(b.tokens) == 1 and [r.request_id for r in
                                   eng._flight.lanes] == [1, 2]
    eng.step(now=2.0)           # lands the run b joined
    assert len(b.tokens) == 2 and len(a.tokens) == 4


def test_the_ahead_share_is_one_in_a_steady_batch_and_zero_under_spec_k(
        model):
    eng = _engine(model)
    _drive(eng, [Request(np.arange(1, 9, dtype=np.int32) + i, 12,
                         request_id=i) for i in range(3)])
    # every run but the first went behind one in flight
    assert eng.decode_steps == 11
    assert eng.decode_steps_ahead == eng.decode_steps - 1
    spec = _engine(model, spec_k=3)
    _drive(spec, [Request(np.arange(1, 9, dtype=np.int32) + i, 12,
                          request_id=i) for i in range(3)])
    assert spec.decode_steps > 0 and spec.decode_steps_ahead == 0


def test_drop_in_flight_forgets_the_run_and_a_reroute_recomputes_it(model):
    """A fleet that reroutes a dead replica's sequences drops the run in
    flight: its tokens are recomputed from those recorded."""
    from chainermn_tpu.serving.fleet import LocalReplica
    whole = _engine(model, sync=True)
    _drive(whole, [Request(np.arange(1, 10, dtype=np.int32), 8,
                           request_id=1)])
    eng = _engine(model)
    replica = LocalReplica(0, eng)
    replica.submit(Request(np.arange(1, 10, dtype=np.int32), 8,
                           request_id=1))
    for k in range(3):
        replica.step(now=float(k))
    assert eng._flight is not None
    (moved,) = replica.drain_for_reroute(now=3.0)
    assert eng._flight is None and not eng.running
    other = _engine(model)
    _drive(other, [moved])
    assert _tokens(other) == _tokens(whole)


# -- the spans ----------------------------------------------------------------

@pytest.fixture
def ring():
    prev = obs.set_mode("events")
    obs.reset_tracer()
    obs.reset_registry()
    yield
    obs.set_mode(prev)
    obs.reset_tracer()
    obs.reset_registry()


def _windows(events):
    """The ``serve/decode_window`` spans of the ring in order, each
    ``(tags, [names of the spans opened inside it])``."""
    out, inside = [], None
    for e in sorted(events, key=lambda e: e["ts"]):
        if e["name"] == "serve/decode_window":
            if e["ph"] == "B":
                inside = (e.get("args", {}), [])
                out.append(inside)
            elif e["ph"] == "E":
                inside = None
        elif inside is not None and e["ph"] == "B":
            inside[1].append(e["name"])
    return out


def test_a_window_dispatches_the_next_run_before_it_fetches_the_last(
        model, ring):
    """On the ring: the call that finds nothing in flight builds and
    dispatches run 0 outside any window (nothing lands there), then
    opens one that dispatches run 1 and fetches run 0; every later
    window dispatches run ``n + 1`` BEFORE the fetch of run ``n``; the
    batch's last run is fetched in a window that dispatches nothing.
    Every window lands a run and carries that run's tags, as the host
    knew them at its dispatch, so one span's counts are one run's."""
    eng = _engine(model)
    eng.submit(Request(np.arange(1, 10, dtype=np.int32), 5, request_id=1))
    eng.submit(Request(np.arange(2, 13, dtype=np.int32), 5, request_id=2))
    step = 0
    while eng.running or eng.scheduler.pending():
        eng.step(now=float(step))
        step += 1
    events = obs.tracer().events()
    windows = _windows(events)
    build_dispatch = ["serve/decode_build", "serve/decode_dispatch"]
    assert [w[0]["step"] for w in windows] == [0, 1, 2, 3]
    assert [w[0]["ahead"] for w in windows] == [0, 1, 1, 1]
    for _, inside in windows[:-1]:
        assert inside == build_dispatch + ["serve/decode_fetch"]
    assert windows[-1][1] == ["serve/decode_fetch"]
    # two prompts of 9 and 11 tokens, each run a token more a lane
    assert [w[0]["ctx_tokens"] for w in windows] == [22, 24, 26, 28]
    assert all(w[0]["batch"] == w[0]["bucket"] == 2 for w in windows)
    # four runs, each fetched and recorded once
    assert eng.decode_steps == 4
    for name in ("serve/decode_build", "serve/decode_dispatch",
                 "serve/decode_fetch", "serve/record"):
        assert len([e for e in events
                    if e["name"] == name and e["ph"] == "B"]) == 4


def test_the_synchronous_order_fetches_the_run_its_window_dispatched(
        model, ring):
    eng = _engine(model, spec_k=0, disagg=True, prefix_cache=False)
    eng.submit(Request(np.arange(1, 10, dtype=np.int32), 4, request_id=1))
    step = 0
    while eng.running or eng.scheduler.pending():
        eng.step(now=float(step))
        step += 1
    windows = _windows(obs.tracer().events())
    assert [w[0]["ahead"] for w in windows] == [0, 0, 0]
    for _, inside in windows:
        assert inside == ["serve/decode_build", "serve/decode_dispatch",
                          "serve/decode_fetch"]


# -- the small program --------------------------------------------------------

def test_next_tokens_program_takes_rows_and_host_tokens():
    """One program for every pair of buckets: the run before's ``nxt``
    in its bucket's place (here the bucket of 4, behind those of 1 and
    2: its rows count from 3), the tokens cut to every bucket."""
    import jax.numpy as jnp
    prevs = tuple(jnp.asarray(np.array(v, np.int32)) for v in
                  ([91], [92, 93], [11, 12, 13, 14], [0] * 8))
    sel = jnp.asarray(np.array([3 + 2, -1 - 40, 3 + 0, -1, -1 - 7, 3 + 3,
                                -1, -1], np.int32))
    want = [13, 40, 11, 0, 7, 14, 0, 0]
    outs = next_tokens_program(prevs, sel)
    assert [np.asarray(o).tolist() for o in outs] == \
        [want[:1], want[:2], want[:4], want]


@pytest.mark.parametrize("tp", [1, 2])
def test_the_gather_is_warmed_with_the_buckets_and_named_apart(model, tp):
    """ONE trace for every pair of batch buckets, by the first decode
    run (``warmup()`` or the first step); none afterwards, as lanes
    join and leave, and the decode program traced once a bucket
    whichever way a run takes its tokens: under ``tp`` a run leaves
    ``nxt`` committed to the mesh, and the host's tokens are placed the
    same (placed as the host's arrays are, every bucket would lower and
    compile twice).  Its module is no decode or prefill program to a
    traffic file's needles."""
    eng = _engine(model, max_batch=8, tp=tp)
    eng.warmup()
    assert eng.ahead_traces == 1 and len(eng.batch_buckets) == 4
    frozen = (eng.ahead_traces, eng.decode_traces, eng.prefill_traces,
              eng.prefix_prefill_traces)
    assert frozen[1] == len(eng.batch_buckets)
    _drive(eng, _mixed(seed=6, n=10))
    assert eng.decode_steps_ahead > 0
    assert (eng.ahead_traces, eng.decode_traces, eng.prefill_traces,
            eng.prefix_prefill_traces) == frozen
    cold = _engine(model, max_batch=8)
    cold.submit(Request(np.arange(1, 10, dtype=np.int32), 3, request_id=1))
    cold.step(now=0.0)
    assert cold.ahead_traces == 1
    import jax.numpy as jnp
    name = eng._next_tokens_fn.lower(
        (jnp.zeros(2, jnp.int32),), jnp.zeros(4, jnp.int32)) \
        .compiler_ir("stablehlo").operation.attributes["sym_name"]
    for needle in ("_decode", "_prefill", "_prefix_prefill"):
        assert needle not in str(name), name


_COMPILE = "/jax/core/compile/backend_compile_duration"


def _compiles_of_a_benchmark_drive(model, sync):
    """Drive a fresh engine through ``submit`` / ``step`` alone, as
    ``benchmark/drivers/serve.py: Program.warm_up`` drives a cell's (the
    prefill bucket of each prompt length and drain, a holder, the suffix
    prefill of each tail length behind it, lanes joining a group a call
    until every batch bucket has run, drain), then through 200 calls of
    joins and leaves.  Returns the ``backend_compile`` events JAX fired
    in the warm-up and in the 200 calls, and the batch buckets those
    calls dispatched at.  Every jit cache of the process is dropped
    first, so an eager helper another test compiled is counted here
    too (PR 45's draft of the gather made its idle operands with
    ``jnp.zeros``: a broadcast a bucket)."""
    import jax
    from jax import monitoring
    jax.clear_caches()
    fired = []

    def listen(name, _secs, **_kw):
        if name == _COMPILE:
            fired.append(name)

    monitoring.register_event_duration_secs_listener(listen)
    try:
        eng = _engine(model, sync=sync, max_batch=8, num_pages=96)
        rng = np.random.RandomState(7)
        S, lo, hi, lanes = 16, 8, 24, 8
        ids = iter(range(10 ** 6))

        def request(prompt, new, at=0.0):
            return Request(np.asarray(prompt, np.int32), new,
                           arrival_time=at, request_id=next(ids))

        def toks(n):
            return rng.randint(0, VOCAB, n)

        def drain(now):
            while eng.running or eng.prefilling or eng.scheduler.pending():
                eng.step(now=now)

        for b in (32, 64):                  # prompts of S + lo .. S + hi
            eng.submit(request(toks(min(b, S + hi)), 1))
            drain(0.0)
        system = toks(S)
        eng.submit(request(np.concatenate([system, toks(hi)]), 4 * lanes))
        eng.step(now=0.0)
        for b in (16, 32):                  # tails of lo .. hi
            eng.submit(request(np.concatenate([system, toks(min(b, hi))]),
                               1))
            eng.step(now=0.0)
        live = group = 1
        while live < lanes:
            for _ in range(group):
                eng.submit(request(np.concatenate([system, toks(lo)]),
                                   2 * lanes))
            eng.step(now=0.0)
            live += group
            group = live
        drain(0.0)
        warm = len(fired)
        buckets = set()
        for k in range(200):
            now = 1.0 + k
            # bursts and lulls: the batch climbs to 8 lanes and falls
            # back to none
            for _ in range(rng.randint(0, 3) if k % 50 < 30 else 0):
                eng.submit(request(
                    np.concatenate([system, toks(rng.randint(lo, hi + 1))]),
                    int(rng.randint(1, 14)), at=now))
            eng.step(now=now)
            if eng._flight is not None:
                buckets.add(eng._flight.nxt.shape[0])
            elif eng.running:
                buckets.add(_bucket(len(eng.running), eng.batch_buckets,
                                    "batch"))
        drain(201.0)
        assert eng.allocator.check()
        return warm, len(fired) - warm, buckets, eng
    finally:
        monitoring.unregister_event_duration_listener(listen)


def test_the_run_ahead_compiles_one_program_more_and_none_in_the_window(
        model):
    """The acceptance count of PR 46 on the CPU: driven as a benchmark
    cell drives it, an engine that keeps a run in flight fires exactly
    ONE ``backend_compile`` event more than the synchronous order (the
    gather, whatever pairs of buckets follow each other), all of it in
    the warm-up; the 200 calls of joins and leaves over every batch
    bucket compile nothing."""
    sync_warm, sync_window, sync_buckets, _ = \
        _compiles_of_a_benchmark_drive(model, sync=True)
    warm, window, buckets, eng = \
        _compiles_of_a_benchmark_drive(model, sync=False)
    assert sync_window == 0 and window == 0
    assert warm == sync_warm + 1
    assert buckets == set(eng.batch_buckets) == {1, 2, 4, 8}
    assert sync_buckets == buckets
    # lanes moved between runs (the gather) and kept their rows (the
    # pass-through), and the gather was traced once
    assert eng.ahead_traces == 1
    assert 0 < eng.decode_steps_ahead < eng.decode_steps
