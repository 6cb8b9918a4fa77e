"""``tools/replay_schedule.py``: a serving cell's own schedule replayed
on the host with given step times.  Held to what the chip counted in
``kimi-k2.6-serve-agent`` (ledger, PR 46: 699.02-699.11 tokens/s, 268 of
288 requests and 27 969 tokens in 40 s) at the ledger's step times, with
and without PR 46's 3.4 ms host gap in the cycle, which the chip showed
to move nothing."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(ROOT, "tools", "replay_schedule.py")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("replay_schedule", TOOL)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("decode_ms, suffix_ms, full_ms, finished, tokens", [
    (11.0, 41, 120, 268, 27969),        # the ledger's step times
    (14.4, 41, 120, 268, 27969),        # with the host gap PR 46 took out
    (7.75, 36, 60, 275, 28915),         # ISSUE 47's prediction
])
def test_kimis_schedule_at_the_ledgers_step_times(
        tool, decode_ms, suffix_ms, full_ms, finished, tokens):
    out = tool.replay_cell("kimi-k2.6-serve-agent", 40.0,
                           decode_ms=decode_ms, suffix_ms=suffix_ms,
                           full_ms=full_ms)
    assert out["due"] == 288
    assert (out["finished"], out["tokens_of_finished"]) == (finished, tokens)
    assert out["finished"] + out["in_flight_at_close"] <= out["due"]
    # a stamp is a token inside the window, finished or not
    assert out["tokens_stamped"] >= out["tokens_of_finished"]


def test_a_server_too_slow_for_the_load_leaves_a_queue(tool):
    out = tool.replay_cell("kimi-k2.6-serve-agent", 40.0, decode_ms=60.0,
                           suffix_ms=41, full_ms=120)
    assert out["finished"] < 230 and out["in_flight_at_close"] > 60
    assert out["tokens_of_finished"] < 0.75 * 27969


def test_the_command_prints_both_counts():
    done = subprocess.run(
        [sys.executable, TOOL, "kimi-k2.6-serve-agent", "--decode-ms", "11",
         "--suffix-ms", "41", "--full-ms", "120"],
        capture_output=True, text=True, timeout=120, check=True)
    out = json.loads(done.stdout.splitlines()[-1])
    assert out["finished"] == 268
    assert out["tokens_per_s_by_requests"] == pytest.approx(699.225)
    assert out["tokens_per_s_by_stamps"] > out["tokens_per_s_by_requests"]
