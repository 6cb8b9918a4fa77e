"""``benchmark/device_scopes.py`` and the readers of ISSUE 38's
per-layer metrics: how a ``tf_op`` is read (role, direction, phase), how
operations are booked to a program's runs, each reader on made-up
operations, then on the two traces recorded on a TPU v5e:
``tiny_train.xplane.pb`` (before the roles: the phases and the unscoped
remainder read, every role reader silent) and
``tiny_serve_scoped.xplane.pb`` (recorded with the roles)."""

import json
import os

import pytest

from benchmark import device_scopes, harness, trace_reduce, xplane_wire
from benchmark.device_scopes import parse
from benchmark.trace_reduce import Event, Trace
from benchmark.xplane_wire import Op

from . import _tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tiny_train.xplane.pb")
SCOPED = os.path.join(DATA, "tiny_serve_scoped.xplane.pb")

TRAIN = ["train.fwd_ms", "train.bwd_ms", "train.optimizer_ms",
         "train.attn_ms", "train.mlp_ms", "train.head_ms",
         "train.unscoped_ms"]
SERVE = ["serve.decode_attn_ms", "serve.decode_proj_ms",
         "serve.decode_ffn_ms", "serve.decode_head_ms",
         "serve.decode_unscoped_ms", "serve.prefill_attn_ms",
         "serve.prefill_ffn_ms"]
STATE = ["serve.decode_state_ms", "serve.prefill_state_ms"]
SERVING_CELLS = ["gpt2m-serve-chat", "kimi-k2.6-serve-agent",
                 "laguna-s-2.1-serve-repo", "olmo-hybrid-7b-serve-docs"]


def reader(name):
    return harness.load_module("layer_metrics", name)


# -- a path ------------------------------------------------

@pytest.mark.parametrize("tf_op,scoped,role,backward", [
    ("jit(rank_step)/mn_forward_backward/jvp(blocks)/3/~mlp/tanh:",
     True, "mlp", False),
    ("jit(rank_step)/mn_forward_backward/transpose(jvp(blocks))/3/~mlp/mul",
     True, "mlp", True),
    # ``transpose`` as the primitive is a forward operation
    ("jit(rank_step)/mn_forward_backward/jvp()/transpose", True, None, False),
    # a link called ``attn`` is no role; neither is a role's bare word
    ("jit(_decode)/blocks/3/attn/qkv/dot_general", True, None, False),
    ("jit(_decode)/blocks/3/mlp/~attn_proj/attn/qkv/dot_general:",
     True, "attn_proj", False),
    # the last piece is the primitive and never a role
    ("jit(_decode)/blocks/3/~attn", True, None, False),
    # the innermost of two roles wins, inside a transform's brackets too
    ("jit(_decode)/blocks/0/~attn_proj/attn/~attn/pallas_call",
     True, "attn", False),
    ("jit(f)/mn_forward_backward/transpose(jvp(~head))/ln_f/~norm/mul",
     True, "norm", True),
    ("jit(f)/transpose(jvp(~loss))/~loss/jit(_where)/select_n",
     True, "loss", True),
    # a mark on a word outside the vocabulary is no role
    ("jit(f)/~attention/dot_general", True, None, False),
    # nothing of the program's: empty, or an argument's name
    ("", False, None, False),
    ("state['params']['/blocks/0/attn/qkv/W']", False, None, False),
    ("operands[0]", False, None, False),
])
def test_what_a_tf_op_says(tf_op, scoped, role, backward):
    path = parse(tf_op)
    assert (path.scoped, path.role, path.backward) == (scoped, role,
                                                       backward)


def test_phase_and_link_path():
    p = parse("jit(rank_step)/mn_forward_backward/transpose(jvp(blocks))/3/"
              "~attn_proj/attn/qkv/dot_general:")
    assert p.phase == "mn_forward_backward" and p.direction == "bwd"
    assert p.where == "blocks/3/~attn_proj/attn/qkv"
    p = parse("jit(rank_step)/mn_optimizer_update/mul")
    assert (p.phase, p.role, p.where, p.direction) == (
        "mn_optimizer_update", None, "", "fwd")
    p = parse("jit(_decode)/blocks/1/~attn/jit(_flash)/pallas_call")
    assert p.phase is None and p.where == "blocks/1/~attn"


# -- made-up operations ------------------------------------

def _op(tf_op, start, dur, name="%fusion.1 = f32[8] fusion(%x)", category=""):
    return Op(name, start, dur, tf_op, category)


class _Run:
    traffic = {"programs": {"step": ["rank_step"], "decode": ["_decode"],
                            "prefill": ["_prefill", "_prefix_prefill"]}}
    config = {}


def _view(ops, modules, lo=0.0, hi=1.0):
    trace = Trace({"/device:TPU:0": modules}, {"/device:TPU:0": []}, [])
    view = {"trace": trace, "lo": lo, "hi": hi, "run": _Run}
    view["device_scopes"] = device_scopes.book(
        ops, modules, _Run.traffic["programs"], lo, hi)
    return view


FB = "jit(rank_step)/mn_forward_backward/"


def test_training_readers_on_made_up_operations():
    ops = [
        _op(FB + "jvp(~embed)/embed/gather", 0.000, 0.001),
        _op(FB + "jvp(blocks)/0/~attn_proj/attn/qkv/dot_general", 0.001,
            0.004),
        _op(FB + "jvp(blocks)/0/~attn_proj/attn/~attn/pallas_call", 0.005,
            0.002),
        _op(FB + "jvp(blocks)/0/~mlp/fc1/dot_general", 0.007, 0.008),
        _op(FB + "jvp(~loss)/~loss/reduce_sum", 0.015, 0.001),
        _op(FB + "transpose(jvp(~head))/head/dot_general", 0.016, 0.016),
        _op(FB + "transpose(jvp(blocks))/0/~mlp/fc1/dot_general", 0.032,
            0.032),
        _op("jit(rank_step)/mn_optimizer_update/mul", 0.064, 0.003),
        _op("", 0.067, 0.002, category="copy-done"),
        _op("params['/head/W']", 0.069, 0.001, category="data formatting"),
        # a loop holds others and is not one itself
        _op(FB + "jvp(blocks)/0/~mlp/while", 0.0, 0.07,
            name="%while.3 = (f32[8]) while(%t)"),
        # the second run: one operation
        _op(FB + "jvp(blocks)/0/~mlp/fc1/dot_general", 0.101, 0.010),
        # in a run that ends outside the window, and in no run at all
        _op(FB + "jvp(blocks)/0/~mlp/fc1/dot_general", 0.95, 0.01),
        _op(FB + "jvp(blocks)/0/~mlp/fc1/dot_general", 0.50, 0.01),
    ]
    mods = [Event("jit_rank_step(1)", 0.0, 0.071),
            Event("jit_rank_step(1)", 0.1, 0.02),
            Event("jit_rank_step(1)", 0.94, 0.10),
            Event("jit_other(2)", 0.49, 0.05)]
    view = _view(ops, mods)
    step = view["device_scopes"]["step"]
    assert step.runs == 2 and len(step.ops) == 11
    assert step.run_s == pytest.approx(0.091)
    want = {"train.fwd_ms": (1 + 4 + 2 + 8 + 1 + 10) / 2,
            "train.bwd_ms": (16 + 32) / 2,
            "train.optimizer_ms": 3 / 2,
            "train.unscoped_ms": (2 + 1) / 2,
            "train.attn_ms": (4 + 2) / 2,
            "train.mlp_ms": (8 + 32 + 10) / 2,
            "train.head_ms": (1 + 1 + 16) / 2}
    for name, ms in want.items():
        assert reader(name).read(view) == pytest.approx(ms), name
    # the identity: phases and the unscoped remainder are all of it
    assert sum(want[n] for n in ("train.fwd_ms", "train.bwd_ms",
                                 "train.optimizer_ms",
                                 "train.unscoped_ms")) \
        == pytest.approx(step.op_s * 1e3 / 2)
    line = device_scopes.summary(step)
    assert line["runs"] == 2 and line["op_ms"] == pytest.approx(40.0)
    assert line["by_role"]["mlp.bwd"] == pytest.approx(16.0)
    assert line["by_role"]["attn.fwd"] == pytest.approx(1.0)
    assert line["scoped_no_role"] == {"mn_optimizer_update": 1.5}
    assert line["unscoped"] == {"copy-done": 1.0,
                                "data formatting (an argument's)": 0.5}
    assert line["no_role_share_of_scoped"] == pytest.approx(3 / 77, abs=1e-4)
    assert next(iter(line["heaviest_paths"])) == "blocks/*/~mlp/fc1 [bwd]"


def test_serving_readers_on_made_up_operations():
    D, P = "jit(_decode)/", "jit(_prefix_prefill)/"
    ops = [
        _op(D + "~embed/embed/gather", 0.000, 0.001),
        _op(D + "blocks/0/~norm/ln1/mul", 0.001, 0.001),
        _op(D + "blocks/0/~attn_proj/qkv/dot_general", 0.002, 0.002),
        _op(D + "blocks/0/~cache_write/~cache_write/scatter", 0.004, 0.001),
        _op(D + "blocks/0/~attn/~attn/dot_general", 0.005, 0.004),
        _op(D + "blocks/0/~state/~state/mul", 0.009, 0.002),
        _op(D + "blocks/0/~mlp/fc1/dot_general", 0.011, 0.003),
        _op(D + "blocks/1/~experts/~router/top_k", 0.014, 0.001),
        _op(D + "blocks/1/~experts/~experts/dot_general", 0.015, 0.002),
        _op(D + "~head/head/dot_general", 0.017, 0.002),
        _op(D + "~head/argmax", 0.019, 0.001),
        _op("", 0.020, 0.005, category="data formatting"),
        _op(P + "blocks/0/~attn/~attn/dot_general", 0.100, 0.010),
        _op(P + "blocks/0/~cache_write/~cache_write/scatter", 0.110, 0.002),
        _op(P + "blocks/0/~mlp/fc1/dot_general", 0.112, 0.006),
        _op(P + "blocks/0/~state/~state/pallas_call", 0.118, 0.004),
        _op("jit(_prefill)/blocks/0/~experts/~experts/dot_general", 0.200,
            0.020),
    ]
    mods = [Event("jit__decode(1)", 0.0, 0.026),
            Event("jit__prefix_prefill(2)", 0.1, 0.03),
            Event("jit__prefill(3)", 0.2, 0.03)]
    view = _view(ops, mods)
    assert view["device_scopes"]["prefill"].runs == 2
    want = {"serve.decode_attn_ms": 4 + 1, "serve.decode_proj_ms": 2 + 1,
            "serve.decode_ffn_ms": 3 + 1 + 2, "serve.decode_head_ms": 1 + 3,
            "serve.decode_unscoped_ms": 5, "serve.decode_state_ms": 2,
            "serve.prefill_attn_ms": (10 + 2) / 2,
            "serve.prefill_ffn_ms": (6 + 20) / 2,
            "serve.prefill_state_ms": 4 / 2}
    for name, ms in want.items():
        assert reader(name).read(view) == pytest.approx(ms), name
    decode = view["device_scopes"]["decode"]
    assert sum(want[n] for n in want if ".decode_" in n) \
        == pytest.approx(decode.op_s * 1e3)


@pytest.mark.parametrize("name", TRAIN + SERVE + STATE)
def test_nothing_to_read_gives_none(name):
    # no device, no run of the program in the window
    empty = {"trace": Trace({}, {}, []), "lo": 0.0, "hi": 1.0, "run": _Run,
             "result": {}}
    assert reader(name).read(empty) is None
    view = _view([_op("jit(_other)/mul", 0.0, 0.01)],
                 [Event("jit_other(2)", 0.0, 0.05)])
    assert reader(name).read(view) is None


@pytest.mark.parametrize("name", [n for n in TRAIN + SERVE + STATE
                                  if n not in ("train.fwd_ms",
                                               "train.bwd_ms",
                                               "train.optimizer_ms",
                                               "train.unscoped_ms",
                                               "serve.decode_unscoped_ms")])
def test_a_program_without_roles_silences_the_role_readers(name):
    """The parent's programs, or an executable a compile cache kept from
    before the roles: ``None``, not zero."""
    ops = [_op(FB + "jvp()/dot_general", 0, 0.01),
           _op("jit(_decode)/dot_general", 0.1, 0.01),
           _op("jit(_prefill)/dot_general", 0.2, 0.01)]
    mods = [Event("jit_rank_step(1)", 0.0, 0.02),
            Event("jit__decode(1)", 0.1, 0.02),
            Event("jit__prefill(1)", 0.2, 0.02)]
    view = _view(ops, mods)
    assert reader(name).read(view) is None
    line = device_scopes.summary(view["device_scopes"]["decode"])
    assert line["no_role_share_of_scoped"] == 1.0


# -- the manifest ------------------------------------------------

@pytest.mark.parametrize("name", TRAIN + SERVE + STATE)
def test_manifest_entry(name):
    entry = next(m for m in harness.load_manifest()["per_layer"]
                 if m["name"] == name)
    train = name.startswith("train.")
    assert _tiny.without_variants(entry) == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": "step program" if train else (
            "kernels" if name == "serve.prefill_state_ms"
            else "serving programs"),
        "moves": "train_samples_per_s_per_chip" if train
        else "serve_tokens_per_s",
        "workloads": ["gpt2m-train-1chip"] if train else (
            SERVING_CELLS[2:] if name in STATE else SERVING_CELLS)}
    assert reader(name).read.__doc__ is None and reader(name).__doc__


# -- the recorded traces ------------------------------------------------

def _recorded_view(path):
    trace = trace_reduce.load(path)
    with open(path + ".json") as f:
        kept = json.load(f)

    class Run:
        traffic = kept["traffic"]
        config = kept["config"]
    lo, hi = trace_reduce.window(trace, "bench/window")
    view = {"trace": trace, "lo": lo, "hi": hi, "run": Run}
    view["device_scopes"] = device_scopes.book(
        xplane_wire.device_ops(path), trace.modules[trace.devices[0]],
        Run.traffic["programs"], lo, hi)
    return view, kept


def test_the_recorded_training_trace_reads_by_phase_and_no_role():
    view, _ = _recorded_view(RECORDED)
    step = view["device_scopes"]["step"]
    assert step.runs == 10 and not step.has_roles
    phases = {n: reader(n).read(view) for n in (
        "train.fwd_ms", "train.bwd_ms", "train.optimizer_ms",
        "train.unscoped_ms")}
    # 802.5 us under mn_forward_backward over the ten steps
    assert (phases["train.fwd_ms"] + phases["train.bwd_ms"]) * 10 \
        == pytest.approx(0.8025, abs=2e-4)
    assert phases["train.optimizer_ms"] * 10 == pytest.approx(0.1289,
                                                              abs=1e-4)
    # the 126.3 us with no ``tf_op`` at all, and what is named after an
    # argument (a parameter's prefetch or cast: 12.5 us)
    bare = sum(op.dur for op, _ in step.ops if not op.tf_op) * 1e3
    assert bare == pytest.approx(0.1263, abs=2e-4)
    assert phases["train.unscoped_ms"] * 10 == pytest.approx(
        bare + 0.0125, abs=2e-4)
    assert 0 < phases["train.fwd_ms"] < phases["train.bwd_ms"]
    # with mn_allreduce_grad they are the step's operations
    rest = device_scopes.ms_a_run(
        view, "step", lambda p: p.scoped and p.phase in (
            None, "mn_allreduce_grad"), needs_roles=False)
    assert sum(phases.values()) + rest == pytest.approx(step.op_s * 1e2)
    assert step.op_s < step.run_s
    for name in ("train.attn_ms", "train.mlp_ms", "train.head_ms"):
        assert reader(name).read(view) is None
    assert device_scopes.summary(step)["no_role_share_of_scoped"] == 1.0


@pytest.mark.skipif(not os.path.exists(SCOPED),
                    reason="no recorded serving trace")
def test_the_recorded_serving_trace_reads_by_role():
    """A tiny ``TransformerLM`` engine on the chip, recorded WITH the
    roles (``benchmark/tools/record_tiny_serve.py``): every reader gives
    what the run on the chip printed for it (the result line is beside
    the trace), and the roles and the unscoped remainder are all of each
    program's device time."""
    view, kept = _recorded_view(SCOPED)
    line = kept["line"]["metrics"]
    programs = view["device_scopes"]
    assert programs["decode"].runs > 10 and programs["prefill"].runs >= 2
    mine = [n for n in SERVE if n in line]
    assert mine == SERVE
    for name in mine:
        assert reader(name).read(view) == pytest.approx(
            line[name]["value"], rel=1e-6), name
    # a model with no recurrent state: the role is there to read, as 0
    for name in STATE:
        assert reader(name).read(view) == 0.0
    for key in ("decode", "prefill"):
        p = programs[key]
        assert p.has_roles and all(path.role or not path.scoped
                                   for _, path in p.ops)
        summary = device_scopes.summary(p)
        assert summary["no_role_share_of_scoped"] == 0.0
        assert sum(summary["by_role"].values()) \
            + sum(summary["unscoped"].values()) \
            == pytest.approx(summary["op_ms"], abs=2e-3)
        assert {"attn.fwd", "cache_write.fwd", "attn_proj.fwd", "mlp.fwd",
                "norm.fwd", "embed.fwd", "head.fwd"} \
            <= set(summary["by_role"])
        assert any(k.startswith("blocks/*/") for k in
                   summary["heaviest_paths"])
    decode = programs["decode"]
    parts = [reader(n).read(view) for n in SERVE if ".decode_" in n]
    assert sum(parts) == pytest.approx(decode.op_s * 1e3 / decode.runs)
    # the step's own duration, as ``serve.decode_step_ms`` reads it, is
    # a little more: the gaps between operations inside a run
    assert decode.op_s <= decode.run_s
