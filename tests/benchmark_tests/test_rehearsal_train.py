"""The drivers end to end on the CPU at tiny shapes (the harness's own
rehearsal: result lines say ``platform: cpu``): each plain reference
against the system, the control (the reference in float8) called not
correct, and the timed path broken underneath called not correct.

The limits here are for these tiny shapes, set between what the sound
program and the control read at them (the cells' own limits, read on the
chip at the cells' sizes, are in their traffic files).
"""

import gc

import pytest

from benchmark import harness
from benchmark.drivers import train

from . import _tiny

LIMITS = {
    "gpt2m-train-1chip": {"loss_gap": 0.004, "grad_norm_gap": 0.008,
                          "grad_norm_median_gap": 0.002,
                          "delta_norm_gap": 0.7,
                          "delta_norm_median_gap": 0.0012},
    "resnet50-train-1chip": {"loss_gap": 0.012, "grad_norm_gap": 0.5,
                             "grad_norm_median_gap": 0.02,
                             "grad_diff_gap": 0.06,
                             "delta_norm_gap": 0.7,
                             "delta_norm_median_gap": 0.03},
}
TRAIN_CELLS = sorted(LIMITS)


def _check(rows, name):
    return next(r for r in rows if r["check"] == name)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_training_cell_runs_and_agrees_with_its_reference(cell):
    run = _tiny.tiny_run(cell, limits=LIMITS[cell])
    line = _tiny.result(run)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"train_samples_per_s_per_chip",
                                    "setup_s"}
    assert line["metrics"]["train_samples_per_s_per_chip"]["value"] > 0


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_training_control_in_float8_is_not_correct(cell):
    run = _tiny.tiny_run(cell, limits=LIMITS[cell])
    prog = train.Program(run)
    spec, data, batch = prog.spec, prog.data, prog.global_batch
    del prog
    gc.collect()
    want = train.reference_steps(run, spec, data, batch)
    control = train.reference_steps(run, spec, data, batch, precision="fp8")
    checks = harness.Checks()
    train.compare(checks, control, want, run.traffic["limits"])
    assert not checks.ok
    # and it is the lower precision that fails, not the step itself
    assert _check(checks.rows, "delta_norm_gap")["ok"]
    if "grad_diff_gap" in LIMITS[cell]:
        # the classifier's gradient, whole: the number that tells the
        # precisions apart where the norms of a BN network's leaves do not
        assert not _check(checks.rows, "grad_diff_gap")["ok"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from chainermn_tpu.core import optimizer as core_optimizer
    monkeypatch.setattr(
        core_optimizer, "apply_transform_update",
        lambda tx, grads, opt_state, params, lr, wd=0.0: (params, opt_state))
    run = _tiny.tiny_run("gpt2m-train-1chip",
                         limits=LIMITS["gpt2m-train-1chip"])
    line = _tiny.result(run)
    assert line["correct"] is False


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    run = _tiny.tiny_run("gpt2m-train-1chip",
                         limits=LIMITS["gpt2m-train-1chip"])
    whole = train.Program.__init__

    def half_batch(self, run):
        whole(self, run)
        convert = self.updater.converter

        def drop_half(batch, device=None):
            n = len(batch) // 2
            return convert(batch[:n] + batch[:n], device)
        self.updater.converter = drop_half
    monkeypatch.setattr(train.Program, "__init__", half_batch)
    line = _tiny.result(run)
    assert line["correct"] is False


def test_four_devices_agree_with_the_merged_batch_reference():
    run = _tiny.tiny_run("gpt2m-train-1chip", n_devices=4,
                         limits=LIMITS["gpt2m-train-1chip"])
    line = _tiny.result(run)
    assert line["correct"] is True and line["device"]["count"] == 4


def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown():
    run = _tiny.tiny_run("gpt2m-train-1chip", trace=True,
                         limits=LIMITS["gpt2m-train-1chip"])
    line = _tiny.result(run)
    assert line["correct"] is True
    assert "train.dispatch_ms" in line["metrics"]
    assert "train_samples_per_s_per_chip" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
