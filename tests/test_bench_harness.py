"""The bench harness is one process that fails when it should.

``bench.py`` prints one JSON line per result and exits 0; a failing
mode, a missing TPU (without the explicit ``JAX_PLATFORMS=cpu``
rehearsal) or an unknown device kind is an error, never a fallback row.
"""

import json
import os
import subprocess
import sys
import types

import pytest

import bench

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(env_overrides, drop=(), timeout=300):
    env = dict(os.environ, **env_overrides)
    for name in drop:
        env.pop(name, None)
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                          env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=ROOT)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return proc, lines


@pytest.mark.parametrize("model,expected", [
    (None, ("resnet50_imagenet_train_throughput", "images/sec/chip")),
    ("resnet50", ("resnet50_imagenet_train_throughput", "images/sec/chip")),
    ("transformer", ("transformer_lm_train_throughput", "tokens/sec/chip")),
    ("longcontext", ("longcontext_flash_feasibility", "tokens_context")),
    ("serving", ("serving_engine_throughput", "tokens/sec")),
    ("moe", ("moe_lm_train_throughput", "tokens/sec/chip")),
])
def test_err_metric(monkeypatch, model, expected):
    if model is None:
        monkeypatch.delenv("BENCH_MODEL", raising=False)
    else:
        monkeypatch.setenv("BENCH_MODEL", model)
    assert bench._err_metric() == expected


def _device(kind):
    return types.SimpleNamespace(device_kind=kind, platform="tpu")


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197.0), ("TPU v5e", 197.0), ("TPU v5p", 459.0),
    ("TPU v4", 275.0), ("TPU v6e", 918.0), ("cpu", None)])
def test_peak_tflops_table(kind, peak):
    assert bench._peak_tflops([_device(kind)]) == peak


def test_unknown_device_kind_raises(monkeypatch):
    """An MFU over an assumed peak is not a measurement: a device that
    is not in the table is an error, and no env knob overrides it."""
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "123")
    with pytest.raises(ValueError, match="TPU v9"):
        bench._peak_tflops([_device("TPU v9 hyper")])


def test_failing_mode_prints_error_line_and_exits_nonzero():
    """No except that returns 0: the mode's ValueError becomes a
    ``value: null`` line under the mode's metric and exit code 1."""
    proc, lines = _run_bench({"JAX_PLATFORMS": "cpu",
                              "BENCH_MODEL": "transformer",
                              "BENCH_D_MODEL": "100", "BENCH_HEADS": "3"})
    assert proc.returncode == 1
    assert lines[-1]["metric"] == "transformer_lm_train_throughput"
    assert lines[-1]["value"] is None
    assert "not divisible" in lines[-1]["error"]
    assert "cpu_fallback" not in proc.stdout


def test_unknown_mode_exits_nonzero():
    proc, lines = _run_bench({"JAX_PLATFORMS": "cpu",
                              "BENCH_MODEL": "resnet5O"})
    assert proc.returncode == 1
    assert lines[-1]["value"] is None and "resnet5O" in lines[-1]["error"]


def test_no_tpu_and_no_explicit_cpu_exits_nonzero_before_timing():
    """Without ``JAX_PLATFORMS=cpu`` a machine without a TPU is a
    failure, not a CPU row."""
    proc, lines = _run_bench({"BENCH_MODEL": "transformer"},
                             drop=("JAX_PLATFORMS",))
    assert proc.returncode != 0
    assert all(ln["value"] is None for ln in lines)
    assert "compile_s" not in proc.stdout


def test_cpu_rehearsal_row_is_labelled_and_exits_zero():
    """The explicit CPU rehearsal still works end to end, names the
    device it ran on, and carries no MFU."""
    proc, lines = _run_bench({
        "JAX_PLATFORMS": "cpu", "BENCH_MODEL": "transformer",
        "BENCH_BS": "1", "BENCH_SEQ": "32", "BENCH_D_MODEL": "32",
        "BENCH_LAYERS": "1", "BENCH_VOCAB": "64", "BENCH_STEPS": "2"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = lines[-1]
    assert row["platform"] == "cpu" and row["device_kind"] == "cpu"
    assert row["n_steps"] == 2 and row["value"] > 0
    assert "mfu" not in row and "stale" not in row


def test_bench_is_one_process():
    """No supervisor, no re-exec, no detach registry, no state files:
    the module imports nothing that could start or signal a process and
    names no path under /tmp."""
    with open(os.path.join(ROOT, "bench.py")) as f:
        src = f.read()
    for word in ("subprocess", "signal", "fcntl", "os.exec", "/tmp/",
                 "bench_last_good", "BENCH_PEAK_TFLOPS",
                 "BENCH_XLA_CACHE_DIR"):
        assert word not in src, word
    assert not os.path.exists(os.path.join(ROOT, "bench_last_good.json"))


def test_longcontext_cpu_smoke_end_to_end():
    """Full run of the longcontext mode on CPU (interpret mode, clamped
    T): per-T flash rows + xla contrast row + summary line with the
    largest completed T as the value."""
    proc, lines = _run_bench({
        "JAX_PLATFORMS": "cpu", "BENCH_MODEL": "longcontext",
        "BENCH_LC_SEQS": "64", "BENCH_LC_XLA_T": "64"})
    assert proc.returncode == 0 and lines, proc.stderr[-2000:]
    by_metric = {ln["metric"]: ln for ln in lines}
    assert by_metric["longcontext_flash_row"]["T"] == 64
    assert by_metric["longcontext_flash_row"]["interpreted"] is True
    assert by_metric["longcontext_flash_row"]["bwd_mode"] == "fused"
    assert "longcontext_xla_contrast" in by_metric
    summary = lines[-1]
    assert summary["metric"] == "longcontext_flash_feasibility"
    assert summary["value"] == 64
    assert summary["rows"] and summary["xla_contrast"]["T"] == 64


# -- bench_scaling's gloo launcher (multi-process, slow) ---------------------

def _run_gloo_harness(extra_args, timeout):
    """Shared launcher for the bench_scaling gloo tests: own session so
    a timeout reaps the gloo worker GRANDCHILDREN too (not just the
    bench_scaling parent), stdout parsed into JSON rows."""
    import signal
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "bench_scaling.py"),
         *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.communicate()
        raise
    assert proc.returncode == 0, stderr[-2000:]
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


@pytest.mark.slow
def test_gloo_scaling_harness_two_process(tmp_path):
    """bench_scaling --gloo-procs mechanics: the real cross-process
    compiled-DP measurement keeps working — rows parse, per-hop summary
    present."""
    rows = _run_gloo_harness(
        ["--gloo-procs", "1,2", "--per-chip-bs", "8", "--steps", "5",
         "--gloo-hidden", "32"], timeout=420)
    by_procs = {r["processes"]: r for r in rows if "step_ms" in r}
    assert set(by_procs) == {1, 2}
    assert all(r["step_ms"] > 0 for r in by_procs.values())
    summary = [r for r in rows if "per_hop_overhead_raw_ms" in r]
    assert summary and summary[0]["processes"] == 2
    assert "overhead_vs_serialized_compute_ms" in summary[0]
    assert all(r["zero_sharding"] is False for r in by_procs.values())


@pytest.mark.slow
def test_gloo_scaling_harness_zero_mode(tmp_path):
    """--gloo-zero mechanics: the ZeRO-1 cross-process curve (psum_scatter
    + all_gather data plane) keeps producing parseable rows."""
    rows = _run_gloo_harness(
        ["--gloo-procs", "1", "--per-chip-bs", "8", "--steps", "5",
         "--gloo-hidden", "32", "--gloo-zero"], timeout=300)
    assert rows and rows[0]["zero_sharding"] is True
    assert rows[0]["step_ms"] > 0
