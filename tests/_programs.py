"""The compiled text of the programs the role tests read: the engine's
three serving programs of a tiny model, driven through ``submit`` and
``step`` until each has run once, and the training step of a tiny LM.
Compiled on the CPU, each program once."""

import re

import numpy as np


def serving_texts(prog):
    """``{"_prefill" | "_prefix_prefill" | "_decode": compiled text}`` of
    a ``benchmark.drivers.serve.Program``'s engine: two requests that
    share the mix's prefix, the second admitted while the first decodes,
    so that it is a prefix hit."""
    engine, texts = prog.engine, {}
    for name in ("_prefill", "_prefix_prefill", "_decode"):
        jitted, compiled = getattr(engine, name + "_fn"), {}

        def call(*args, _jitted=jitted, _compiled=compiled, _name=name):
            key = tuple((a.shape, str(a.dtype)) for a in args[1:])
            if key not in _compiled:
                _compiled[key] = _jitted.lower(*args).compile()
                texts.setdefault(_name, _compiled[key].as_text())
            return _compiled[key](*args)
        setattr(engine, name + "_fn", call)
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, prog.vocab, size=prog.mix["prefix_len"])
    for tail in (8, 12):
        prompt = np.concatenate([prefix, rng.randint(1, prog.vocab, tail)])
        engine.submit(prog.request(prompt.astype(np.int32), 4))
        engine.step()
        engine.step()
    prog.drain()
    return texts


def step_text(model, comm, x):
    """The compiled text of the multi-node optimizer's step program
    (``jit_rank_step``) for ``model(x, x)`` over ``comm``, Adam."""
    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import Adam
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(Adam(alpha=0.01), comm).setup(model)
    opt.update(model, x, x)
    return opt.actual_optimizer.traced_step().lower().compile().as_text()


def op_names(text):
    """The ``op_name`` of every instruction of the program proper (a
    parameter's is its argument's name; a reducer's body has the bare
    primitive)."""
    return [n for n in re.findall(r'op_name="([^"]*)"', text)
            if n.startswith("jit(")]
