"""On a tpu backend the flash dispatch hides nothing: interpret mode is
refused, and a shape the kernels cannot take warns once, by shape, with
the path it took.  The backend is faked; the non-kernel paths are plain
jnp, so they run here."""

import importlib
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_WARNED_FALLBACK", set())


def _qkv(T, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.normal(0, 1, (1, 2, T, 16)).astype(np.float32))
            for _ in range(3)]


@pytest.mark.parametrize("entry", ["attention", "attention_with_lse"])
def test_interpret_env_on_tpu_backend_is_an_error(tpu_backend, monkeypatch,
                                                  entry):
    monkeypatch.setenv("CHAINERMN_TPU_FLASH_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="FLASH_INTERPRET"):
        getattr(fa, entry)(*_qkv(128), causal=True)


@pytest.mark.parametrize("entry,path", [
    ("attention", "XLA attention"),
    ("attention_with_lse", "blockwise jnp attention")])
def test_irregular_T_on_tpu_backend_warns_once_naming_the_shape(
        tpu_backend, entry, path):
    q, k, v = _qkv(200)  # 200 % min(128, 200) != 0: no tile divides it
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        first = getattr(fa, entry)(q, k, v, causal=True)
        getattr(fa, entry)(q, k, v, causal=True)
    msgs = [str(w.message) for w in caught
            if str(w.message).startswith("flash attention:")]
    assert len(msgs) == 1, msgs
    assert "[1, 2, 200, 16]" in msgs[0] and path in msgs[0]
    # the dispatch is kept: the fallback still answers
    out = first[0] if isinstance(first, tuple) else first
    ref = fa.xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_off_the_chip_the_fallback_stays_silent():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fa.attention(*_qkv(200), causal=True)
    assert not [w for w in caught
                if str(w.message).startswith("flash attention:")]
