"""Device time by the program's own names, read from the run's
``.xplane.pb``.

The program opens ``jax.named_scope``s when it is traced: the three
phases of a training step (``mn_forward_backward``,
``mn_allreduce_grad``, ``mn_optimizer_update``), a link's name wherever
a link is called (``blocks/3/attn/qkv``) and, marked ``~``, the ROLE of
each part of a model (``~attn``, ``~mlp``, ...: the vocabulary below,
``chainermn_tpu.observability.ROLES``; ``docs/observability.md``).  XLA
keeps the path as each instruction's ``op_name`` and the profiler stamps
it on every device operation as ``tf_op`` (``xplane_wire.py``).  This
module reads it, as ``program_spans.py`` reads the host's spans: parsed
once a run, kept in the ``view``, for the readers under
``layer_metrics/``.

How an operation is booked:

* to a program's RUN, by lying inside an ``XLA Modules`` event whose
  name holds one of the program's needles
  (``traffic["programs"][...]``) and which lies wholly in the traced
  window; operations that only hold others (loops, calls) are left out;
* to a ROLE, by the innermost ``~role`` on its path.  The path is split
  on ``/``, ``(`` and ``)``; its last piece is the primitive and never a
  role; a link that happens to be called ``attn`` is not a role;
* BACKWARD where the text ``transpose(`` occurs in the path (a piece
  ``transpose`` alone is a primitive's name: ``jvp()/transpose`` is a
  forward operation);
* whole.  A fusion has ONE ``tf_op``, that of the operation XLA kept as
  its root: a weight-gradient GEMM with Adam's update in its epilogue is
  a backward GEMM here, and a GEMM whose epilogue holds the next norm's
  row sum is the GEMM's.
* UNSCOPED where ``tf_op`` is empty, or is no path of the program's
  (an argument's name, ``state['params']['/head/W']``, on a copy or a
  cast of it): what the compiler made itself (prefetch ``copy-start`` /
  ``copy-done``, layout ``copy``s).

A program without roles (every commit before PR 38, or an executable
that a compile cache kept from then: the cache's key leaves ``op_name``
out) gives ``None`` from every role reader; the printed line says what
share of the scoped time carries no role, so that such a run reads as
100 % there and not as zeros.  All times are seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import time

from . import harness, trace_reduce, xplane_wire

ROLES = ("embed", "norm", "attn_proj", "cache_write", "attn", "state",
         "mlp", "router", "experts", "head", "loss")
MARK = "~"
PHASES = ("mn_forward_backward", "mn_allreduce_grad", "mn_optimizer_update")
BACKWARD = "transpose("

_SPLIT = re.compile(r"[/()]")
_PROGRAM = re.compile(r"\w+\(")     # ``jit(rank_step)/...``, ``pmap(...)/...``
_LAYER = re.compile(r"/\d+(?=/|$)")   # ``blocks/3/...``: alike layers as one
_JIT = re.compile(r"jit\([^()]*\)")     # a jitted function's own name
_WRAP = re.compile(r"\w+\(|\)")         # ``transpose(jvp(`` ... ``))``


@dataclasses.dataclass(frozen=True)
class Path:
    """What an operation's ``tf_op`` says of it."""
    scoped: bool         # the operation carries a path at all
    role: str | None     # the innermost ``~role`` of the vocabulary
    backward: bool
    phase: str | None    # the training step's phase, if any
    where: str           # the link path, for the printed line

    @property
    def direction(self):
        return "bwd" if self.backward else "fwd"


_UNSCOPED = Path(False, None, False, None, "")


def parse(tf_op):
    """The :class:`Path` of one ``tf_op``."""
    if not _PROGRAM.match(tf_op):
        return _UNSCOPED
    pieces = [p for p in _SPLIT.split(tf_op) if p]
    scopes = pieces[:-1]            # the last piece is the primitive
    role = None
    for p in scopes:
        if p.startswith(MARK) and p[1:] in ROLES:
            role = p[1:]
    phase = next((p for p in scopes if p in PHASES), None)
    named = _WRAP.sub("", _JIT.sub("", tf_op)).split("/")[:-1]
    where = "/".join(p for p in named if p and p not in PHASES)
    return Path(True, role, BACKWARD in tf_op, phase, where)


@dataclasses.dataclass
class Program:
    """The operations of one program's runs inside the window."""
    runs: int = 0
    run_s: float = 0.0          # the runs' own durations, summed
    ops: list = dataclasses.field(default_factory=list)  # [(Op, Path)]

    @property
    def op_s(self):
        return sum(op.dur for op, _ in self.ops)

    @property
    def has_roles(self):
        return any(p.role for _, p in self.ops)

    def seconds(self, keep):
        return sum(op.dur for op, p in self.ops if keep(p))


def book(ops, modules, programs, lo, hi):
    """``{program: Program}``: each operation (containers left out) to
    the run it lies in.  ``ops`` are ``xplane_wire.Op``s, ``modules``
    the first chip's ``XLA Modules`` events, ``programs`` the traffic
    file's ``{program: needles}``."""
    out = {key: Program() for key in programs}
    runs = sorted((m.start, m.end, key) for key, needles in programs.items()
                  for m in modules if trace_reduce.is_match(m, needles)
                  and m.start >= lo and m.end <= hi)
    for s, e, key in runs:
        out[key].runs += 1
        out[key].run_s += e - s
    starts = [r[0] for r in runs]
    parsed = {}
    for op in ops:
        i = bisect.bisect_right(starts, op.start + 1e-12) - 1
        if i < 0 or op.end > runs[i][1] + 1e-9 \
                or trace_reduce._is_container(op):
            continue
        path = parsed.get(op.tf_op)
        if path is None:
            path = parsed[op.tf_op] = parse(op.tf_op)
        out[runs[i][2]].ops.append((op, path))
    return out


def programs(view):
    """``{program: Program}`` of the run, parsed once and kept in the
    view.  The first call also prints ``device_ms_by_scope``, on an
    earlier output line.  A result without a trace directory, or a trace
    without a device plane, gives programs with no run."""
    if "device_scopes" not in view:
        needles = view["run"].traffic.get("programs", {})
        trace = view["trace"]
        trace_dir = getattr(view["result"].get("tracing"), "dir", None) \
            if "result" in view else None
        ops, took = [], 0.0
        if trace_dir and trace.devices:
            t0 = time.perf_counter()
            ops = xplane_wire.device_ops(
                trace_reduce.find_xplane(trace_dir), trace.devices[0])
            took = time.perf_counter() - t0
        modules = trace.modules[trace.devices[0]] if trace.devices else []
        view["device_scopes"] = book(ops, modules, needles,
                                     view["lo"], view["hi"])
        if ops:
            harness.say({"device_ms_by_scope": {
                key: summary(p) for key, p in view["device_scopes"].items()
                if p.runs}, "parse_s": took, "operations": len(ops)})
    return view["device_scopes"]


def ms_a_run(view, program, keep, needs_roles=True):
    """Device time of the program's operations that ``keep`` (a
    predicate over :class:`Path`) takes, in ms a run; ``None`` where the
    program did not run in the window, or, for a reader of roles, where
    no operation of it carries one."""
    p = programs(view).get(program)
    if p is None or not p.runs or not p.ops:
        return None
    if needs_roles and not p.has_roles:
        return None
    return p.seconds(keep) * 1e3 / p.runs


def role_ms(view, program, roles, within=None):
    """ms a run under the roles named, both directions; ``within`` keeps
    it to one phase of the training step."""
    return ms_a_run(view, program, lambda p: p.role in roles
                    and (within is None or p.phase == within))


# -- the printed line ------------------------------------------------

def _ms(seconds, runs):
    return round(seconds * 1e3 / runs, 4)


def summary(p, heaviest=15):
    """One program's entry of ``device_ms_by_scope``: ms a run by role
    and direction, the scoped time with no role by its phase (or its
    outermost name), the unscoped remainder by ``hlo_category``, the
    heaviest link paths (alike layers summed: ``blocks/*/~mlp/fc1``),
    and the share of the scoped time that carries no role."""
    by_role, no_role, unscoped, paths = {}, {}, {}, {}
    for op, path in p.ops:
        if not path.scoped:
            key = (op.category or "uncategorised") \
                + (" (an argument's)" if op.tf_op else "")
            unscoped[key] = unscoped.get(key, 0.0) + op.dur
            continue
        if path.role:
            key = f"{path.role}.{path.direction}"
            by_role[key] = by_role.get(key, 0.0) + op.dur
        else:
            key = path.phase or path.where.split("/", 1)[0] or "program"
            no_role[key] = no_role.get(key, 0.0) + op.dur
        key = f"{_LAYER.sub('/*', path.where) or '-'} [{path.direction}]"
        paths[key] = paths.get(key, 0.0) + op.dur
    scoped = sum(by_role.values()) + sum(no_role.values())

    def rows(d, n=None):
        return {k: _ms(v, p.runs) for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:n]}

    return {"runs": p.runs, "run_ms": _ms(p.run_s, p.runs),
            "op_ms": _ms(p.op_s, p.runs),
            "by_role": rows(by_role), "scoped_no_role": rows(no_role),
            "unscoped": rows(unscoped),
            "no_role_share_of_scoped":
                round(sum(no_role.values()) / scoped, 4) if scoped else None,
            "heaviest_paths": rows(paths, heaviest)}
