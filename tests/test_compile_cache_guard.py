"""One function decides where JAX's persistent compile cache lives
(``utils.compat.configure_persistent_cache``): with
``JAX_COMPILATION_CACHE_DIR`` set no directory is set in code at all;
unset, it is ``<checkout>/.jax_cache`` — resolved from the package's own
location, so it cannot depend on the pid or the cwd (the path is part of
the cache key: a directory that moves never hits).
"""

import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax
from chainermn_tpu.utils.compat import configure_persistent_cache
print("RETURNED", configure_persistent_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
"""

# an entry SCRIPT at its smallest (one epoch of the MNIST example, one
# batch): the cache directory it ends up with is whatever the shared
# function decided
_EXAMPLE_PROBE = """
import sys, runpy
import jax
sys.argv = ["train_mnist.py", "--platform", "cpu", "--epoch", "1",
            "--batchsize", "6000", "--unit", "8", "--out", {out!r}]
try:
    runpy.run_path({path!r}, run_name="__main__")
finally:
    print("CONFIG", jax.config.jax_compilation_cache_dir)
"""


def _run(code, cwd, env_dir):
    # one CPU device, unoptimized compiles: the probes check a path
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_backend_optimization_level=0",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return dict(re.findall(r"^(RETURNED|CONFIG) (.*)$", proc.stdout,
                           re.M))


_EXAMPLE = "example"


@pytest.mark.parametrize("code,from_root,env_set", [
    (_PROBE, True, True),      # the variable wins, code sets nothing
    (_PROBE, True, False),     # unset: <checkout>/.jax_cache
    (_PROBE, False, False),    # another pid, another cwd: the same path
    (_EXAMPLE, False, False),  # an example goes through the function
    (_EXAMPLE, False, True),
], ids=["env_set", "unset", "unset_other_cwd", "example_unset",
        "example_env_set"])
def test_cache_directory_rule(code, from_root, env_set, tmp_path):
    placed = str(tmp_path / "placed") if env_set else None
    expected = placed or os.path.join(ROOT, ".jax_cache")
    if code is _EXAMPLE:
        code = _EXAMPLE_PROBE.format(
            path=os.path.join(ROOT, "examples", "train_mnist.py"),
            out=str(tmp_path / "result"))
    got = _run(code, ROOT if from_root else str(tmp_path), placed)
    # CONFIG is what JAX ends up with: from the environment when the
    # variable is set, from the one setter otherwise
    assert got["CONFIG"] == expected
    assert got.get("RETURNED", expected) == expected


def test_env_set_makes_no_config_update(monkeypatch):
    """In process: with the variable set the function never calls
    ``jax.config.update`` for the directory."""
    import jax
    from chainermn_tpu.utils import compat
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compat.configure_persistent_cache() == "/some/dir"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compat.configure_persistent_cache()
    assert calls == [("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))]


def test_one_setter_in_the_tree():
    """``grep -rn compilation_cache_dir --include=*.py`` finds one
    setter, and the retired knobs are gone (so is the last script that
    named one)."""
    setters, retired = [], []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out", "build")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            if os.path.abspath(path) == os.path.abspath(__file__):
                continue
            with open(path) as f:
                src = f.read()
            if re.search(r"update\(\s*[\"']jax_compilation_cache_dir", src):
                setters.append(os.path.relpath(path, ROOT))
            if re.search(r"CHAINERMN_TPU_XLA_CACHE_DIR|BENCH_XLA_CACHE_DIR",
                         src):
                retired.append(os.path.relpath(path, ROOT))
    assert setters == [os.path.join("chainermn_tpu", "utils", "compat.py")]
    assert retired == []
