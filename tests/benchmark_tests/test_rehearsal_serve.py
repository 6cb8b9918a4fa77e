"""The serve driver end to end on the CPU at tiny shapes: the plain
reference against the engine's served tokens, the control (the reference
in float8) called not correct, and a token altered where it is produced
called not correct.  The limit here is for these tiny shapes, between
what the sound engine and the control read at them."""

from benchmark.drivers import serve

from . import _tiny

SERVE_LIMIT = {"served_logit_gap": 0.02}


def _check(rows, name):
    return next(r for r in rows if r["check"] == name)


CELL = "gpt2m-serve-chat"


def test_serving_cell_runs_and_agrees_with_its_reference():
    run = _tiny.tiny_run(CELL, seconds=2.0, limits=SERVE_LIMIT)
    line = _tiny.result(run)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 40
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_serving_control_in_float8_is_not_correct():
    run = _tiny.tiny_run(CELL, seconds=2.0, limits=SERVE_LIMIT)
    result = serve.run(run)
    assert result["correct"]
    sound = _check(result["checks"], "served_logit_gap")["value"]
    gap, n = serve.reference_gap(run, result["spec"], result["sample"],
                                 control="fp8")
    assert n >= 40
    assert gap > SERVE_LIMIT["served_logit_gap"] > sound


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from chainermn_tpu.serving import ServingEngine
    record = ServingEngine._record_token

    def altered(self, req, tok, now):
        record(self, req, (int(tok) + 1) % 256, now)
    monkeypatch.setattr(ServingEngine, "_record_token", altered)
    run = _tiny.tiny_run(CELL, seconds=2.0, limits=SERVE_LIMIT)
    line = _tiny.result(run)
    assert line["correct"] is False
