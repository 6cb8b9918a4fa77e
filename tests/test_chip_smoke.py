"""CPU rehearsal of ``chip_smoke.py``: every phase at a tiny width
through the same functions the chip runs (Pallas in interpret mode), the
refusal to run without a TPU, and the shape of the last line."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_LM = dict(n_vocab=128, d_model=32, n_heads=2, n_layers=2, seq_len=32,
               per_chip_batch=2)
TINY_RESNET = dict(block_counts=(1, 1, 1, 1), n_classes=10, image_size=32,
                   per_chip_batch=2)
TINY_SERVE = dict(num_pages=32, page_size=8, max_batch=4, max_context=64,
                  n_requests=4, prompt_lens=(8, 40), max_new_tokens=8)


@pytest.fixture
def flash_interpret(monkeypatch):
    monkeypatch.setenv("CHAINERMN_TPU_FLASH_INTERPRET", "1")


def test_train_transformer_phase(flash_interpret):
    out = chip_smoke.train_transformer(TINY_LM, steps=3)
    assert len(out["losses"]) == 3 and out["losses"][-1] < out["losses"][0]
    assert out["kernels"] == ["_flash_bwd_fused_kernel", "_flash_kernel_lse"]


def test_train_transformer_phase_fails_off_the_pallas_path():
    """Without the kernels in the step (the XLA attention of a plain CPU
    run) the phase's own check raises: a fallback cannot pass."""
    with pytest.raises(AssertionError, match="_flash_kernel_lse"):
        chip_smoke.train_transformer(TINY_LM, steps=2)


def test_train_resnet_phase():
    out = chip_smoke.train_resnet(TINY_RESNET, steps=3)
    assert len(out["losses"]) == 3 and len(out["step_s"]) == 2


def test_serve_phase(flash_interpret):
    out = chip_smoke.serve(TINY_LM, TINY_SERVE)
    assert out["completed"] == 4 and out["tokens"] == 32
    assert out["window_retraces"] == 0
    assert out["logit_max_abs_err"] <= chip_smoke.SERVE_LOGIT_ATOL


def test_data_parallel_phase_on_four_virtual_devices(flash_interpret):
    """``--chips 4`` rehearsed: four virtual CPU devices, per-device
    batch 1 against the one-device run on the merged batch."""
    out = chip_smoke.data_parallel(jax.devices()[:4],
                                   dict(TINY_LM, per_chip_batch=1))
    assert out["n_devices"] == 4
    assert out["max_rel_diff"] <= chip_smoke.DP_LOSS_RTOL


def test_data_parallel_phase_detects_a_diverging_trajectory(
        flash_interpret):
    with pytest.raises(AssertionError, match="relative difference"):
        chip_smoke.data_parallel(jax.devices()[:4],
                                 dict(TINY_LM, per_chip_batch=1),
                                 loss_rtol=0.0)


def test_last_line_shape_from_a_faked_device():
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = chip_smoke.result_line([dev])
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(chip_smoke.result_line([dev] * 4))["device"][
        "count"] == 4


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["default", "chips4"])
def test_without_a_tpu_the_script_fails_and_prints_no_result(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
