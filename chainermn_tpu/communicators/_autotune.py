"""Self-tuning exchange planner (ISSUE 19, ROADMAP item 2).

Every topology knob the comm stack grew — ``bucket_mb``, ``stripe_ratio``,
the per-hop dtype ladder — used to be a static constructor argument the
operator guessed per topology.  This module closes the loop the way
HiCCL composes collectives from a machine description and FlexLink
picks its multi-path split from measured link bandwidths:

1. **measure** — :func:`measure_fabric` runs a seconds-scale startup
   micro-bench (one ``psum`` per mesh hop: a large probe for bandwidth,
   a tiny probe for launch latency) over the REAL fabric; the optional
   online mode (:func:`measurements_from_trace`) instead reads the
   ISSUE 14 span tracer's ``train/grad_exchange*`` spans, whose
   payload-bytes attributes make bandwidth = Σbytes/Σduration directly
   readable off a trace.
2. **agree** — :func:`agree_exchange_plan` all-gathers the per-rank
   measurements over the object channel, reduces them DETERMINISTICALLY
   (sorted median, fixed tie-break, 6-significant-digit rounding — no
   rank-local floating-point divergence), derives the plan locally, and
   broadcasts rank 0's plan so every rank executes the identical
   exchange even if a rank's derivation somehow diverged (divergence is
   counted and warned, never silently absorbed).
3. **plan** — :func:`derive_exchange_plan` is a PURE function of the
   agreed measurements + the (collectively identical) topology summary:
   ``bucket_mb`` from the slowest measured hop's bandwidth×latency
   (:func:`~._memory_utility.derived_bucket_bytes`), ``stripe_ratio``
   from docs/performance.md §10's finish-together split
   (:func:`~._memory_utility.derived_stripe_ratio`), and a bfloat16 DCN
   crossing when the slow hop is < half the fast hop's bandwidth.
   Unmeasurable hops (axis size 1, missing latency) fall back to the
   documented defaults WITH a derivation note — the plan always says
   why it chose what it chose.

The derived plan only fills knobs the caller did NOT hand-set
(explicit constructor argument or env var — provenance recorded at
construction, carried across clones and elastic rebuilds): hand knobs
always win, which is what makes the golden-trajectory gate exact — an
``autotune=`` run whose derived plan matches the hand knobs compiles
the identical program.  The agreed plan is recorded as an artifact
(``CHAINERMN_TPU_AUTOTUNE_DIR``) mirroring ``tools/autotune_plan.json``,
whose committed numeric fields stay null until a run on real hardware
stamps them.
"""

from __future__ import annotations

import hashlib
import json
import time
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ._memory_utility import (DEFAULT_BUCKET_MB, DEFAULT_STRIPE_RATIO,
                              derived_bucket_bytes, derived_stripe_ratio,
                              exchanged_bytes)

__all__ = ["measure_fabric", "measurements_from_trace",
           "reduce_measurements", "derive_exchange_plan",
           "agree_exchange_plan", "retune_communicator",
           "topology_summary", "plan_fingerprint", "record_plan",
           "PLAN_VERSION"]

#: plan schema version — bumped when the derivation rules change, so a
#: recorded artifact can never be replayed against different rules
PLAN_VERSION = 1

#: per-collective launch-overhead budget the bucket rule amortizes
OVERHEAD_FRAC = 0.125


def _round6(x):
    """Canonical 6-significant-digit rounding — every number that
    enters the plan passes through here, so two ranks deriving from the
    same agreed measurements produce byte-identical JSON."""
    return float(f"{float(x):.6g}")


# -- measurement ------------------------------------------------------------
def _hop_list(comm):
    """``(hop_name, mesh_axis, axis_size)`` per fabric hop: ``ici`` +
    ``dcn`` on a hierarchical communicator, the single ``world`` hop on
    a flat one."""
    if comm.hierarchy is not None:
        return [("ici", comm.ici_axis, comm.ici_size),
                ("dcn", comm.dcn_axis, comm.dcn_size)]
    return [("world", comm.axis_name, comm.size)]


def measure_fabric(comm, probe_mb=1.0, iters=4):
    """Startup micro-bench: per mesh hop, one replicated ``psum`` timed
    at two sizes — a ``probe_mb`` buffer for bandwidth (wire bytes per
    call = :func:`~._memory_utility.exchanged_bytes` of a psum over the
    hop) and an 8-element buffer for launch latency (min over iters).

    Seconds-scale by construction: 2 compiles + ``2×iters`` executions
    per hop.  A size-1 hop is UNMEASURABLE (nothing crosses a wire) and
    reports ``{"size": 1, "gbps": None, "lat_us": None}`` — the planner
    falls back for it explicitly.  Collective: every rank must enter
    (the probes are real collectives over the shared mesh).
    """
    from .. import observability
    from jax import shard_map
    measurement = {"source": "startup", "probe_mb": _round6(probe_mb),
                   "iters": int(iters), "hops": {}}
    with observability.span("autotune/measure",
                            tags={"mode": "startup",
                                  "probe_mb": float(probe_mb)}):
        for hop, axis, axis_size in _hop_list(comm):
            if axis_size <= 1:
                measurement["hops"][hop] = {"size": 1, "gbps": None,
                                            "lat_us": None}
                continue
            inv = 1.0 / float(axis_size)

            def probe(x, _axis=axis, _inv=inv):
                # /size keeps the replicated value stable across iters
                return lax.psum(x, _axis) * _inv

            mapped = jax.jit(shard_map(
                probe, mesh=comm.mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False))
            n_big = max(1, int(float(probe_mb) * (1 << 20)) // 4)
            big = jnp.ones((n_big,), jnp.float32)
            mapped(big).block_until_ready()          # compile + warm
            t0 = time.perf_counter()
            out = big
            for _ in range(int(iters)):
                out = mapped(out)
            out.block_until_ready()
            elapsed = max(time.perf_counter() - t0, 1e-9)
            wire = exchanged_bytes(n_big * 4, axis_size, "psum")
            gbps = wire * int(iters) / elapsed / 1e9

            small = jnp.ones((8,), jnp.float32)
            mapped(small).block_until_ready()
            lat_s = float("inf")
            for _ in range(int(iters)):
                t0 = time.perf_counter()
                mapped(small).block_until_ready()
                lat_s = min(lat_s, time.perf_counter() - t0)
            measurement["hops"][hop] = {"size": int(axis_size),
                                        "gbps": float(gbps),
                                        "lat_us": float(lat_s * 1e6)}
    return measurement


def measurements_from_trace(events, payload_key="payload_bytes"):
    """Online mode: bandwidth read directly off the ISSUE 14 tracer's
    ``train/grad_exchange*`` spans.  B/E pairs are matched LIFO per
    ``(pid, tid, name)`` track; each pair contributes its
    ``args.payload_bytes`` (the ISSUE 19 small-fix attribute) over its
    duration, grouped by the span's ``args.hop`` tag when present
    (``world`` otherwise).  Spans without a payload attribute are
    skipped — timing alone is not a bandwidth sample.

    No latency field comes out of a trace (a full-exchange span bounds
    launch overhead only loosely), so plans derived from online
    measurements keep the committed ``bucket_mb`` fallback unless a
    startup micro-bench also ran.
    """
    open_spans = {}
    totals = {}     # hop -> [bytes, seconds, samples]
    for ev in events or []:
        name = ev.get("name", "")
        if not name.startswith("train/grad_exchange"):
            continue
        key = (ev.get("pid"), ev.get("tid"), name)
        if ev.get("ph") == "B":
            open_spans.setdefault(key, []).append(ev)
        elif ev.get("ph") == "E" and open_spans.get(key):
            b = open_spans[key].pop()
            args = b.get("args") or {}
            payload = args.get(payload_key)
            if payload is None:
                continue
            dur_s = max(ev.get("ts", 0) - b.get("ts", 0), 0) * 1e-6
            if dur_s <= 0:
                continue
            hop = args.get("hop", "world")
            acc = totals.setdefault(hop, [0.0, 0.0, 0])
            acc[0] += float(payload)
            acc[1] += dur_s
            acc[2] += 1
    hops = {}
    for hop, (nbytes, secs, samples) in sorted(totals.items()):
        hops[hop] = {"size": None,
                     "gbps": nbytes / secs / 1e9 if secs > 0 else None,
                     "lat_us": None, "samples": samples}
    return {"source": "online", "hops": hops}


# -- deterministic agreement -------------------------------------------------
def reduce_measurements(gathered):
    """Reduce the all-gathered per-rank measurements to ONE agreed set:
    per hop and field, the sorted median with a FIXED tie-break
    (element ``(n-1)//2``), rounded to 6 significant digits.  A pure,
    order-insensitive function of the gathered list — every rank holds
    the same list after the allgather, so every rank computes the same
    agreed measurements (the determinism the plan fingerprint gates).
    """
    gathered = [g for g in gathered if g]
    if not gathered:
        raise ValueError("no fabric measurements to reduce")
    base = gathered[0]
    out = {"source": base.get("source", "startup"), "ranks": len(gathered)}
    for k in ("probe_mb", "iters"):
        if base.get(k) is not None:
            out[k] = base[k]
    hop_names = sorted({h for g in gathered for h in (g.get("hops") or {})})
    hops = {}
    for h in hop_names:
        entries = [g["hops"][h] for g in gathered
                   if h in (g.get("hops") or {})]
        agg = {}
        for field in ("size", "gbps", "lat_us"):
            vals = sorted(float(e[field]) for e in entries
                          if e.get(field) is not None)
            if not vals:
                agg[field] = None
            else:
                v = vals[(len(vals) - 1) // 2]
                agg[field] = int(v) if field == "size" else _round6(v)
        hops[h] = agg
    out["hops"] = hops
    return out


def topology_summary(comm):
    """The collectively-identical topology facts the planner keys off —
    every field is a pure function of the communicator's construction
    arguments, which are themselves collective."""
    axis = comm.axis_name
    label = "x".join(axis) if isinstance(axis, (tuple, list)) else str(axis)
    summary = {"axis": label,
               "kind": "hierarchical" if comm.hierarchy is not None
               else "flat",
               "size": int(comm.size),
               "exchange": comm.exchange}
    if comm.hierarchy is not None:
        summary["inter"], summary["intra"] = (int(s)
                                              for s in comm._hier_sizes)
    return summary


def plan_fingerprint(plan):
    """16-hex-char sha256 of the plan's canonical JSON (sorted keys,
    no whitespace, ``fingerprint`` excluded) — the identity the
    cross-rank determinism gate and the plan gauge carry."""
    body = {k: v for k, v in plan.items() if k != "fingerprint"}
    blob = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


def derive_exchange_plan(measurements, topology):
    """PURE planner: agreed measurements + topology summary → the
    ``{bucket_mb, stripe_ratio, grad_dtype}`` plan.  Deterministic and
    byte-identical across ranks (every number passes through 6-digit
    canonical rounding; the fingerprint is over canonical JSON).

    Derivation rules, each with an explicit fallback note when a hop is
    unmeasurable:

    * ``bucket_mb`` — from the SLOWEST measured hop's (bandwidth,
      latency) via :func:`~._memory_utility.derived_bucket_bytes` (the
      slow hop's launch overhead is the one worth amortizing); ``None``
      (= keep the committed default) when no hop has both fields.
    * ``stripe_ratio`` — hierarchical topologies only:
      :func:`~._memory_utility.derived_stripe_ratio` (§10's
      ``r* = B_dcn/(B_ici+B_dcn)``) when BOTH hops measured, else the
      documented :data:`~._memory_utility.DEFAULT_STRIPE_RATIO`
      fallback; ``None`` on flat topologies (one fabric — nothing to
      stripe).
    * ``grad_dtype`` — ``{"ici": None, "dcn": "bfloat16"}`` when the
      measured DCN bandwidth is under half the ICI bandwidth (the slow
      crossing is worth halving; ICI stays lossless by design), else
      ``None``.
    """
    notes = []
    hops = dict(measurements.get("hops") or {})
    measured = {h: v for h, v in hops.items()
                if (v or {}).get("gbps") is not None}

    bucket_mb = None
    if measured:
        slowest = min(sorted(measured), key=lambda h: measured[h]["gbps"])
        lat = measured[slowest].get("lat_us")
        if lat is not None:
            bucket_mb = _round6(
                derived_bucket_bytes(measured[slowest]["gbps"], lat,
                                     overhead_frac=OVERHEAD_FRAC)
                / (1 << 20))
            notes.append(f"bucket_mb from slowest measured hop "
                         f"'{slowest}' (bandwidth x latency / "
                         f"{OVERHEAD_FRAC})")
        else:
            notes.append(f"hop '{slowest}' has bandwidth but no latency "
                         f"sample (online trace): bucket_mb keeps the "
                         f"committed default {DEFAULT_BUCKET_MB} MB")
    else:
        notes.append(f"no measurable hop: bucket_mb keeps the committed "
                     f"default {DEFAULT_BUCKET_MB} MB")

    stripe_ratio = None
    grad_dtype = None
    if topology.get("kind") == "hierarchical":
        gi = (hops.get("ici") or {}).get("gbps")
        gd = (hops.get("dcn") or {}).get("gbps")
        if gi is not None and gd is not None:
            stripe_ratio = _round6(derived_stripe_ratio(gi, gd))
            notes.append("stripe_ratio = r* = B_dcn / (B_ici + B_dcn) "
                         "(docs/performance.md S10 finish-together split)")
            if gd < 0.5 * gi:
                grad_dtype = {"ici": None, "dcn": "bfloat16"}
                notes.append("B_dcn < B_ici/2: bfloat16 DCN crossing "
                             "(ICI stays lossless by design)")
        else:
            missing = "+".join(h for h in ("ici", "dcn")
                               if (hops.get(h) or {}).get("gbps") is None)
            stripe_ratio = _round6(DEFAULT_STRIPE_RATIO)
            notes.append(f"{missing} unmeasured: stripe_ratio falls back "
                         f"to DEFAULT_STRIPE_RATIO "
                         f"({DEFAULT_STRIPE_RATIO})")

    plan = {
        "version": PLAN_VERSION,
        "axis": topology.get("axis"),
        "topology": dict(topology),
        "bucket_mb": bucket_mb,
        "stripe_ratio": stripe_ratio,
        "grad_dtype": grad_dtype,
        "measurements": measurements,
        "derivation": {
            "formula": "r* = B_dcn / (B_ici + B_dcn)",
            "bucket_rule": f"bytes = bandwidth x latency / "
                           f"{OVERHEAD_FRAC}, clamped [1, 32] MB",
            "fallbacks": {"stripe_ratio": DEFAULT_STRIPE_RATIO,
                          "bucket_mb": DEFAULT_BUCKET_MB},
            "notes": notes,
        },
    }
    plan["fingerprint"] = plan_fingerprint(plan)
    return plan


def agree_exchange_plan(comm, measurement):
    """Allgather the per-rank measurements, reduce deterministically,
    derive locally, then take RANK 0's plan by broadcast — the agreed
    plan every rank applies.  The local derivation *should* already be
    byte-identical (pure function of agreed inputs — the tier-1
    determinism gate); if a rank's fingerprint still diverges the
    broadcast wins, a warning fires, and the divergence counter bumps —
    never a silent split-brain exchange."""
    from .. import observability
    with observability.span("autotune/agree"):
        gathered = comm.allgather_obj(measurement)
        reduced = reduce_measurements(gathered)
        with observability.span("autotune/derive"):
            local = derive_exchange_plan(reduced, topology_summary(comm))
        plan = comm.bcast_obj(local, root=0)
    if plan.get("fingerprint") != local.get("fingerprint"):
        from ..observability import registry
        registry().counter(
            "chainermn_tpu_autotune_plan_divergence_total",
            help="ranks whose locally derived plan differed from the "
                 "broadcast rank-0 plan (should be 0: the planner is a "
                 "pure function of agreed measurements)").inc(
            axis=str(plan.get("axis")))
        warnings.warn(
            f"autotune plan derivation diverged from rank 0 "
            f"(local {local.get('fingerprint')} != broadcast "
            f"{plan.get('fingerprint')}); executing rank 0's plan",
            RuntimeWarning, stacklevel=2)
    from ..observability import registry
    registry().gauge(
        "chainermn_tpu_autotune_plan_fingerprint",
        help="numeric prefix of the agreed exchange plan's fingerprint "
             "(identical on every rank of a healthy job)").set(
        float(int(plan["fingerprint"][:12], 16)),
        axis=str(plan.get("axis")))
    observability.instant(
        "autotune/plan",
        tags={"fingerprint": plan["fingerprint"],
              "bucket_mb": plan.get("bucket_mb"),
              "stripe_ratio": plan.get("stripe_ratio")})
    if comm.rank == 0:
        record_plan(plan)
    return plan


def record_plan(plan, path=None):
    """Write the agreed plan as a JSON artifact.  Default location:
    ``$CHAINERMN_TPU_AUTOTUNE_DIR/autotune_plan_<axis>.json`` (one file
    per mesh axis — an elastic resize's epoch-suffixed axis gets a
    FRESH artifact, the per-epoch trail the re-tune tests pin); no env
    var, no write.  Returns the path written, or ``None``."""
    import os
    if path is None:
        out_dir = os.environ.get("CHAINERMN_TPU_AUTOTUNE_DIR", "").strip()
        if not out_dir:
            return None
        safe_axis = "".join(c if c.isalnum() or c in "-_" else "_"
                            for c in str(plan.get("axis", "world")))
        path = os.path.join(out_dir, f"autotune_plan_{safe_axis}.json")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(plan, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def retune_communicator(comm, mode="startup", events=None):
    """measure → agree → apply: returns the communicator to actually
    train with (a retuned clone, or ``comm`` itself with the plan
    attached when the derived plan changes nothing the caller left
    free).  ``mode="online"`` derives from tracer events (``events`` or
    the live tracer ring) instead of running the startup micro-bench.
    Collective under multi-process execution — every rank must call
    with the same arguments, like communicator construction itself."""
    if mode in (True, "startup"):
        measurement = measure_fabric(comm)
    elif mode == "online":
        if events is None:
            from .. import observability
            events = observability.tracer().events()
        measurement = measurements_from_trace(events)
    else:
        raise ValueError(
            f"autotune mode must be 'startup' (True) or 'online', "
            f"got {mode!r}")
    plan = agree_exchange_plan(comm, measurement)
    return comm.retuned(plan)
