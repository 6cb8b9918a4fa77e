"""CPython (3.11 and later) keeps Python frames in chunks of 16 KiB and
pays an ``mmap`` and a ``munmap`` for every call whose frame is the
first of a new chunk: a loop that sits on a chunk's edge pays them a
call.  ``utils.compat.call_with_frame_room`` gives a call one chunk of
1 MiB to run in, and the serving engine makes the first call of every
program at every shape through it (that call traces and lowers: PERF.md
section 6, PR 46, has what the edge cost a large program on the chip).

What is counted here is minor page faults (``ru_minflt``): a chunk
newly mapped is touched, a chunk kept is not.  No time is asserted.
"""

import resource
import sys

import numpy as np
import pytest

from chainermn_tpu.utils.compat import call_with_frame_room

CALLS = 2000


def _faults():
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def _leaf(a, b=0, c=0, d=0, e=0, f=0, g=0, h=0):
    # a frame no smaller than ``_at_depth``'s: as the caller's depth
    # grows a frame at a time, the room left under ``_loop`` cannot
    # step over the sizes at which this frame alone finds none
    return a


def _loop(n):
    before = _faults()
    for _ in range(n):
        _leaf(1)
    return _faults() - before


def _at_depth(d, room):
    if d:
        return _at_depth(d - 1, room)
    return call_with_frame_room(_loop, CALLS) if room else _loop(CALLS)


def test_it_calls_with_the_arguments_and_hands_back_what_it_gets():
    assert call_with_frame_room(lambda a, b=2, *c, **d: (a, b, c, d),
                                1, 3, 4, k=5) == (1, 3, (4,), {"k": 5})
    assert call_with_frame_room(list) == []
    with pytest.raises(KeyError, match="gone"):
        call_with_frame_room({}.__getitem__, "gone")


@pytest.mark.skipif(sys.version_info < (3, 11) or
                    sys.implementation.name != "cpython",
                    reason="the frame stack in chunks is CPython 3.11's")
def test_a_loop_on_a_chunks_edge_faults_a_call_and_none_with_room():
    """Over 400 caller depths the plain loop meets an edge at least
    three times (a chunk holds about 120 of these frames) and there
    faults about once a call; with room it faults at no depth, and a recursion
    600 frames deep inside the room meets no edge either (five chunks
    of the plain stack)."""
    plain = [_at_depth(d, room=False) for d in range(400)]
    roomy = [_at_depth(d, room=True) for d in range(400)]
    assert sorted(plain)[-3] > CALLS // 2, sorted(plain)[-5:]
    assert max(roomy) < CALLS // 100, sorted(roomy)[-5:]

    def deep(d):
        return deep(d - 1) + 1 if d else _loop(CALLS)

    inside = [call_with_frame_room(deep, d) - d for d in range(0, 600, 7)]
    assert max(inside) < CALLS // 100, sorted(inside)[-5:]


def test_the_engine_gives_room_to_the_first_call_of_a_program_at_a_shape(
        monkeypatch):
    """Driven through ``submit`` and ``step`` alone (as the benchmark
    drives a cell), every trace of a model-owned program happens inside
    a call made with room, one such call a program and shape, and a
    warm engine makes none."""
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.serving import Request, ServingEngine
    from chainermn_tpu.serving import engine as engine_module
    roomy = []       # the traces counted when a call with room began
    inside = [0]

    def counted(fn, *args, **kwargs):
        inside[0] += 1
        try:
            return call_with_frame_room(fn, *args, **kwargs)
        finally:
            inside[0] -= 1
            roomy.append(traces(eng))

    def traces(e):
        return (e.prefill_traces + e.prefix_prefill_traces
                + e.decode_traces)

    monkeypatch.setattr(engine_module, "call_with_frame_room", counted)
    model = TransformerLM(n_vocab=64, d_model=32, n_heads=2, n_layers=1,
                          max_len=128, seed=3)
    eng = ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                        max_context=96)
    outside = []     # traces that happened with no room around them
    for name in ("prefill_traces", "prefix_prefill_traces",
                 "decode_traces"):
        assert getattr(eng, name) == 0

    def drive(seed, n):
        rng = np.random.RandomState(seed)
        system = rng.randint(0, 64, 16)
        for i in range(n):
            eng.submit(Request(
                np.concatenate([system, rng.randint(0, 64, 8 + i)])
                .astype(np.int32), 3 + i % 5, arrival_time=float(i),
                request_id=seed * 100 + i))
        t = 0.0
        while eng.running or eng.scheduler.pending():
            before = traces(eng), len(roomy)
            eng.step(now=t)
            if traces(eng) > before[0] and len(roomy) == before[1]:
                outside.append(t)
            t += 1.0

    drive(1, 8)
    assert not outside and inside[0] == 0
    cold = len(roomy)
    # one call with room a (program, shape): each traced exactly once
    assert cold == traces(eng) > 4
    assert sorted(roomy) == roomy and len(set(roomy)) == cold
    drive(2, 8)
    assert len(roomy) == cold and not outside
