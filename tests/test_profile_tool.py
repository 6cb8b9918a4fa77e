"""The xplane.pb walker in tools/profile_tpu_step.py must be known-good
BEFORE chip time depends on it (VERDICT r3 Weak #2): capture a real
2-step CPU trace in-suite and assert the summary yields nonempty op
rows.  Exercises jax.profiler.trace output end-to-end through the
hand-rolled protobuf varint walker — parser bitrot fails here, not on
the one chance at the chip.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import profile_tpu_step  # noqa: E402


def test_summarize_parses_real_trace(tmp_path, capsys):
    @jax.jit
    def step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256), jnp.float32)
    float(step(x))  # compile outside the trace window
    out_dir = str(tmp_path / "trace")
    with jax.profiler.trace(out_dir):
        for _ in range(2):
            loss = step(x)
        float(loss)

    profile_tpu_step.summarize(out_dir)
    out = capsys.readouterr().out
    assert "plane:" in out, f"no plane found in summary output:\n{out}"
    # at least one per-op row:  "<ms> ms  <pct>%  <op name>"
    rows = re.findall(r"^\s+[\d.]+ ms\s+[\d.]+%\s+\S+", out, re.M)
    assert rows, f"no op rows parsed from trace:\n{out}"


def test_empty_device_planes_fall_back_to_the_host_plane(tmp_path, capsys):
    """A process that has loaded libtpu (a compile-only client is
    enough) writes TPU planes with no events into a CPU capture: the
    summary must still read the host plane."""
    import glob

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256), jnp.float32)
    float(step(x))
    out_dir = str(tmp_path / "trace")
    with jax.profiler.trace(out_dir):
        float(step(x))
    path, = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    name = b"/device:TPU:0"
    plane = b"\x12" + bytes([len(name)]) + name    # XPlane.name(2)
    with open(path, "ab") as f:                     # XSpace.planes(1)
        f.write(b"\x0a" + bytes([len(plane)]) + plane)

    profile_tpu_step.summarize(out_dir)
    assert "plane: /host:CPU" in capsys.readouterr().out


def test_summarize_empty_dir_reports_cleanly(tmp_path, capsys):
    profile_tpu_step.summarize(str(tmp_path))
    out = capsys.readouterr().out
    assert "no xplane.pb" in out


def test_compare_diffs_two_real_traces(tmp_path, capsys):
    """--compare is the queue's NCHW-vs-NHWC instrument: capture two
    traces of different programs and assert per-op delta rows print
    (ops matched by name, missing side = 0)."""

    @jax.jit
    def step_a(x):
        return jnp.tanh(x @ x).sum()

    @jax.jit
    def step_b(x):
        return jnp.exp(jnp.sin(x @ x)).sum()  # different op mix

    x = jnp.ones((256, 256), jnp.float32)
    float(step_a(x)), float(step_b(x))  # compile outside the windows
    dirs = []
    for name, step in [("a", step_a), ("b", step_b)]:
        d = str(tmp_path / name)
        with jax.profiler.trace(d):
            for _ in range(2):
                loss = step(x)
            float(loss)
        dirs.append(d)

    profile_tpu_step.compare(*dirs)
    out = capsys.readouterr().out
    assert "total delta (B-A):" in out
    rows = re.findall(r"^\s*[\d.]+\s+[\d.]+\s+[+-][\d.]+\s+\S+", out, re.M)
    assert rows, f"no delta rows:\n{out}"


def test_compare_missing_trace_reports_cleanly(tmp_path, capsys):
    profile_tpu_step.compare(str(tmp_path / "nope"), str(tmp_path / "x"))
    out = capsys.readouterr().out
    assert "EMPTY" in out
