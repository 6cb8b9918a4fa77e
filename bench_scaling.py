"""Scaling-efficiency harness (north-star metric #2, BASELINE.md).

Measures data-parallel ResNet train-step throughput at 1..N chips and the
raw gradient-allreduce bandwidth, reporting scaling efficiency
(throughput_n / (n × throughput_1)).  On a real pod the mesh covers
physical chips and the collective rides ICI; without one, run with
``--simulate-devices 8 --platform cpu`` for the methodology curve
(framework-overhead scaling only — SURVEY §7 step 7 notes v4-32 numbers
are for the real-pod stage).

Output: one JSON line per device count + a summary line.
"""

import argparse
import json
import os
import time

import numpy as np


def measure_step_throughput(n_devices, per_chip_bs, image_size, steps,
                            model_kind="resnet18"):
    import jax
    import jax.numpy as jnp

    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import MomentumSGD
    from chainermn_tpu.models import Classifier, ResNet18, ResNet50

    devices = jax.devices()[:n_devices]
    comm = ct.create_communicator("jax_ici", devices=devices,
                                  axis_name=f"bench{n_devices}",
                                  allreduce_grad_dtype="bfloat16")
    model_cls = ResNet50 if model_kind == "resnet50" else ResNet18
    model = Classifier(model_cls(n_classes=1000,
                                 compute_dtype=jnp.bfloat16, seed=0))
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.1, momentum=0.9), comm).setup(model)

    gbs = per_chip_bs * n_devices
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (gbs, 3, image_size, image_size))
                    .astype(np.float32))
    t = jnp.asarray(rng.randint(0, 1000, gbs).astype(np.int32))
    for _ in range(2):
        loss = opt.update(model, x, t)
    jax.block_until_ready(loss)
    start = time.perf_counter()
    for _ in range(steps):
        loss = opt.update(model, x, t)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - start
    return steps * gbs / dt


def measure_allreduce_bandwidth(n_devices, n_floats, iters=20):
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()[:n_devices]
    mesh = Mesh(np.asarray(devices), ("ar",))
    x = jnp.ones((n_devices, n_floats), jnp.float32)

    fn = jax.jit(shard_map(lambda x: lax.psum(x, "ar"), mesh=mesh,
                           in_specs=P("ar"), out_specs=P("ar"),
                           check_vma=False))
    jax.block_until_ready(fn(x))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    dt = time.perf_counter() - start
    # ring allreduce moves 2(n-1)/n × payload per chip
    bytes_moved = 4 * n_floats * 2 * (n_devices - 1) / max(n_devices, 1)
    return iters * bytes_moved / dt / 1e9  # GB/s per chip


def project_efficiency(step_ms, n_chips, grad_mb=51.1, ici_gbps=100.0,
                       overlap_fraction=0.8, host_overhead_ms=0.5):
    """Analytic DP scaling-efficiency projection for an n-chip pod
    (BENCH_NOTES.md "Scaling-efficiency projection" — the defensible
    basis for the v4-32 north-star claim while only one chip exists).

    Model: per-step time on n chips =
        step_ms + exposed_allreduce
    where ``step_ms`` is the measured single-chip wall-clock step (host
    bookkeeping included — bench.py times ``opt.update`` end to end, so
    host overhead is already inside it), exposed_allreduce =
    (1 - overlap_fraction) × t_ring_allreduce, and
    t_ring_allreduce = 2(n-1)/n × grad_bytes / ici_bandwidth.

    * ``grad_mb`` — ResNet-50 has 25.557M params; bf16-compressed gradient
      payload = 51.1 MB (the flagship ``allreduce_grad_dtype="bfloat16"``
      configuration).
    * ``ici_gbps`` — per-chip algorithmic ring bandwidth along one torus
      axis.  v4's ICI is ~100 GB/s bidirectional per axis; this is the
      conservative single-axis figure (XLA can also use multiple axes).
    * ``overlap_fraction`` — XLA overlaps the gradient all-reduce with the
      remaining backward pass inside the single compiled step; 0.8 is
      conservative (the last layer's gradients cannot overlap).
    * ``host_overhead_ms`` — extra per-step host cost that appears ONLY
      in the multi-chip regime (e.g. multi-controller bookkeeping); the
      single-chip host cost is already inside the measured ``step_ms``,
      so it must not be double-counted here.  Default 0.5 ms is the
      round-1 measured bookkeeping figure used as a conservative adder.
    """
    t_ar_ms = 2 * (n_chips - 1) / n_chips * grad_mb * 1e6 / (ici_gbps * 1e9) * 1e3
    exposed = (1.0 - overlap_fraction) * t_ar_ms
    t_n = step_ms + host_overhead_ms + exposed
    t_1 = step_ms
    return t_1 / t_n


def _gloo_worker(pid, nprocs, port, per_rank_bs, hidden, steps,
                 zero=False, exchange="flat"):
    """One process of the REAL cross-process compiled DP step (the same
    path as ``tests/multiprocess_tests/_worker.py · run_dp_step``): gloo
    CPU backend, 1 device per process, the whole DP step one shard_mapped
    jit whose gradient pmean crosses actual process boundaries.  With
    ``zero`` the optimizer state is ZeRO-1 sharded: the gradient
    traffic becomes psum_scatter + all_gather instead of one pmean —
    the curve then measures the reduce-scatter refactoring's transport
    cost across real process boundaries.  Times the steady-state step;
    rank 0 prints the row."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from chainermn_tpu.communicators._communication_utility import (
        initialize_distributed)
    assert initialize_distributed(f"localhost:{port}",
                                  num_processes=nprocs, process_id=pid)
    import jax.numpy as jnp

    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import MomentumSGD
    from chainermn_tpu.models import MLP, Classifier

    # exchange selects the gradient-exchange structure under test (the
    # ISSUE 5 exposed-comm A/B: bucketed vs flat across REAL process
    # boundaries; ISSUE 6 adds the hierarchical two-level legs — with
    # one device per process the split infers to dcn=nprocs × ici=1, so
    # the DCN hop is the one crossing the real process boundary);
    # reduce_scatter routes through the optimizer-level step variant,
    # zero keeps the ZeRO-1 contract
    comm_name, bc, opt_exchange = ct.communicators.exchange_knobs(exchange)
    # the striped legs (ISSUE 11) must run a NONZERO ratio or the curve
    # would silently measure the strict hierarchical schedule under the
    # striped name; the launcher exports CHAINERMN_TPU_STRIPE_RATIO for
    # the ratio sweep
    stripe = None
    if exchange in ("striped", "striped_rs"):
        from chainermn_tpu.communicators._memory_utility import (
            DEFAULT_STRIPE_RATIO)
        stripe = float(os.environ.get("CHAINERMN_TPU_STRIPE_RATIO", "")
                       or DEFAULT_STRIPE_RATIO)
    comm = ct.create_communicator(comm_name, batch_collectives=bc,
                                  stripe_ratio=stripe)
    assert comm.size == nprocs == jax.device_count()
    model = Classifier(MLP(n_units=hidden, n_out=10, seed=0))
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.01, momentum=0.9), comm, zero_sharding=zero,
        exchange=opt_exchange).setup(model)

    gbs = per_rank_bs * nprocs
    rng = np.random.RandomState(0)
    x = np.asarray(rng.normal(0, 1, (gbs, 64)).astype(np.float32))
    t = np.asarray(rng.randint(0, 10, gbs).astype(np.int32))

    for _ in range(3):  # trace+compile, then steady-state warmup
        loss = opt.update(model, x, t)
    float(loss)

    n_buckets = None
    if exchange == "bucketed":
        # post-warmup: params materialize lazily on the first update
        n_buckets = len(comm.grad_buckets_for(model))
    if nprocs > 1:
        comm._host_channel().barrier()
    start = time.perf_counter()
    for _ in range(steps):
        loss = opt.update(model, x, t)
    float(loss)  # the collective step is lock-step across processes
    dt = time.perf_counter() - start
    if pid == 0:
        n_params = sum(int(np.prod(p.array.shape))
                       for p in model.params())
        row = {
            "processes": nprocs, "per_rank_bs": per_rank_bs,
            "zero_sharding": bool(zero),
            "exchange": exchange,
            "topology": comm.topology,
            "ici_size": comm.ici_size, "dcn_size": comm.dcn_size,
            "grad_payload_mb": round(n_params * 4 / 1e6, 2),
            "step_ms": round(dt / steps * 1e3, 3),
            "examples_per_sec": round(steps * gbs / dt, 1)}
        if exchange == "bucketed":
            # the degenerate single-bucket datum (payload fits the
            # bound) must be tellable apart downstream
            row["bucket_mb"] = comm.bucket_mb
            row["n_buckets"] = n_buckets
        if comm.striped:
            # the ratio sweep's independent variable travels with the
            # row — three curves at {0.25, 0.5, 0.75} are only
            # comparable if each datum names its split
            row["stripe_ratio"] = comm.stripe_ratio
        print(json.dumps(row), flush=True)


def _run_gloo_curve(proc_counts, per_rank_bs, hidden, steps, zero=False,
                    reps=1, exchange="flat", stripe_ratio=None):
    """Launch each P-process measurement and report per-hop overhead:
    step_ms(P) - step_ms(1) is the cost the framework adds per step when
    the SAME compiled program's gradient mean must cross P real process
    boundaries (gloo over localhost — an upper bound on framework
    overhead; ICI on a pod is faster than loopback gloo).

    ``reps`` > 1 repeats each P-process measurement and reports
    mean/min/max step_ms per row (VERDICT r4 Weak #2: on a 1-core box
    the multi-process rows carry scheduler time-slicing noise — the
    spread quantifies it instead of a single draw hiding it)."""
    import re
    import socket
    import subprocess
    import sys
    # 1 device per process is the measurement's contract: a leaked
    # simulated-mesh flag (tests/conftest.py exports
    # --xla_force_host_platform_device_count into the environment) would
    # give every worker N devices and break the topology assert
    env = dict(os.environ)
    if "XLA_FLAGS" in env:
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+\s*", "",
            env["XLA_FLAGS"])
    if stripe_ratio is not None:
        # the ratio sweep's per-invocation knob: workers read it at
        # communicator construction (ISSUE 11)
        env["CHAINERMN_TPU_STRIPE_RATIO"] = str(stripe_ratio)
    if 1 not in proc_counts:
        # the per-hop summary is defined relative to the 1-process step;
        # computing it against rows[0] at some other count would publish
        # silently mislabeled overhead numbers
        raise SystemExit("--gloo-procs must include 1 (the baseline for "
                         "the per-hop overhead summary)")
    rows = []
    for nprocs in proc_counts:
      rep_rows = []
      for _rep in range(max(1, reps)):
        # bind-then-close port choice has a TOCTOU window (another
        # process can grab it before the coordinator re-binds): retry
        # the whole P-process measurement on rendezvous failure
        for attempt in range(3):
            with socket.socket() as s:
                s.bind(("localhost", 0))
                port = s.getsockname()[1]
            procs = [subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--gloo-worker", str(pid), str(nprocs), str(port),
                 str(per_rank_bs), str(hidden), str(steps),
                 str(int(zero)), exchange],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
                for pid in range(nprocs)]
            timed_out = False
            outs = [None] * nprocs
            deadline = time.monotonic() + 600
            for i, p in enumerate(procs):
                try:
                    outs[i] = p.communicate(timeout=max(
                        1.0, deadline - time.monotonic()))[0]
                except subprocess.TimeoutExpired:
                    # rendezvous hang manifestation: a stolen port that
                    # accepts connections but never speaks the
                    # coordinator protocol blocks workers inside
                    # initialize_distributed
                    timed_out = True
            # a wedged worker (dead peer in the gloo barrier) must not
            # outlive the measurement: kill stragglers, but KEEP their
            # output — the final-attempt assertion needs diagnostics
            for i, p in enumerate(procs):
                if p.poll() is None:
                    p.kill()
                try:
                    rem = p.communicate()[0]
                except Exception:
                    rem = None
                if outs[i] is None:
                    outs[i] = rem
            outs = [o or "" for o in outs]
            if not timed_out and all(p.returncode == 0 for p in procs):
                break
            # retry ONLY rendezvous-class failures (the port was taken in
            # the TOCTOU window, or the coordinator wasn't reachable);
            # any other worker crash is a real defect and must surface
            # immediately, not be averaged away by a silent re-run
            rendezvous_err = timed_out or any(
                p.returncode != 0 and re.search(
                    r"[Aa]ddress already in use|UNAVAILABLE|"
                    r"DEADLINE_EXCEEDED|[Ff]ailed to connect|"
                    r"errno 98", o or "")
                for p, o in zip(procs, outs))
            if attempt == 2 or not rendezvous_err:
                raise AssertionError(
                    [(p.returncode, o) for p, o in zip(procs, outs)])
        rep_rows.append(json.loads([ln for ln in outs[0].splitlines()
                                    if ln.startswith("{")][-1]))
      row = dict(rep_rows[0])
      if len(rep_rows) > 1:
          samples = sorted(r["step_ms"] for r in rep_rows)
          row["step_ms"] = round(float(np.mean(samples)), 3)
          row["step_ms_min"] = samples[0]
          row["step_ms_max"] = samples[-1]
          row["reps"] = len(samples)
          # derived from the mean step time (harmonic aggregation), so
          # the row's two fields stay mutually consistent
          row["examples_per_sec"] = round(
              nprocs * per_rank_bs / (row["step_ms"] / 1e3), 1)
      if row.get("exchange") == "bucketed" and row.get("n_buckets", 0) <= 1:
          # worker output is captured, so the launcher owns the warning
          print(f"bench_scaling: bucketed plan degenerated to ONE bucket "
                f"at bucket_mb={row.get('bucket_mb')} (gradient payload "
                f"fits the bound) — structurally identical to flat; set "
                f"CHAINERMN_TPU_BUCKET_MB below the payload for a real "
                f"bucketed-vs-flat A/B", file=sys.stderr, flush=True)
      rows.append(row)
      print(json.dumps(row), flush=True)
    base = next(r["step_ms"] for r in rows if r["processes"] == 1)
    n_cores = os.cpu_count() or 1
    for row in rows:
        if row["processes"] == 1:
            continue
        p = row["processes"]
        # With fewer cores than processes the P workers' compute
        # time-slices one core, so the raw delta over the 1-proc step is
        # mostly contention; the serialized-compute baseline
        # (ceil(P/cores) × 1-proc step) isolates the transport/dispatch
        # overhead the framework actually adds per process boundary.
        serial_ms = -(-p // n_cores) * base
        print(json.dumps({
            "processes": p, "n_cores": n_cores,
            "per_hop_overhead_raw_ms": round(row["step_ms"] - base, 3),
            "overhead_vs_serialized_compute_ms": round(
                row["step_ms"] - serial_ms, 3),
            "scaling_efficiency_vs_1proc": round(
                base / row["step_ms"], 4)}), flush=True)
    return rows


def _gloo_elastic_worker(pid, nprocs, port, per_rank_bs, hidden, steps,
                         preempt_rank):
    """One process of the elastic preempt-and-rejoin measurement
    (ISSUE 10): a Trainer-supervised run over real gloo transport in
    which rank ``preempt_rank`` is hard-preempted a third of the way
    in, the survivors shrink and keep training, and the rank re-joins
    (world grows back).  ``preempt_rank < 0`` is the uninterrupted
    baseline leg of the A/B.  Rank 0 prints the row; ``step_ms`` is
    wall-clock over ALL iterations, so the resize + state-sync tax is
    IN the number — that tax vs the baseline row is the measurement."""
    import time as _time

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from chainermn_tpu.communicators._communication_utility import (
        initialize_distributed)
    assert initialize_distributed(f"localhost:{port}",
                                  num_processes=nprocs, process_id=pid)
    import tempfile

    import chainermn_tpu as ct
    from chainermn_tpu.communicators import (FaultInjectionCommunicator,
                                             FaultSchedule)
    from chainermn_tpu.core.optimizer import MomentumSGD
    from chainermn_tpu.dataset import SerialIterator, TupleDataset
    from chainermn_tpu.extensions import ElasticRecovery
    from chainermn_tpu.models import MLP, Classifier
    from chainermn_tpu.training import StandardUpdater, Trainer
    from chainermn_tpu.training.trainer import Extension

    out = tempfile.mkdtemp(prefix=f"elastic_bench_{pid}_")
    rng = np.random.RandomState(0)
    gbs = per_rank_bs * nprocs
    x = np.asarray(rng.normal(0, 1, (gbs, 64)).astype(np.float32))
    t = np.asarray(rng.randint(0, 10, gbs).astype(np.int32))

    comm = ct.create_communicator("jax_ici")
    comm._host_channel()._timeout_ms = 6000  # typed detection in seconds
    if preempt_rank >= 0:
        # beacon + join-poll = two bcast_obj calls per iteration; fire
        # at the target iteration's beacon
        k = max(2, steps // 3)
        comm = FaultInjectionCommunicator(comm, FaultSchedule(
            [dict(op="bcast_obj", nth=2 * (k - 1) + 1, action="preempt",
                  rank=preempt_rank)], seed=0))
    model = Classifier(MLP(n_units=hidden, n_out=10, seed=0))
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.01, momentum=0.9), comm).setup(model)
    it = SerialIterator(TupleDataset(x, t), gbs, shuffle=False)
    trainer = Trainer(StandardUpdater(it, opt), (steps, "iteration"),
                      out=out)
    cp = ct.create_multi_node_checkpointer(comm, name="eb", path=out)
    recovery = ElasticRecovery(checkpointer=cp, comm=comm,
                               rejoin_after_s=1.0,
                               resolve_timeout_ms=120_000, verbose=False)

    class _Beacon(Extension):
        trigger = (1, "iteration")
        priority = 400

        def __call__(self, trainer):
            recovery.comm.bcast_obj(
                {"it": trainer.updater.iteration}, root=0)

    class _Pacer(Extension):
        # keeps the survivor in the loop long enough for the rejoin to
        # land mid-run (the elastic leg only; the baseline pays the
        # SAME dwell so the A/B delta isolates the elasticity tax)
        trigger = (1, "iteration")
        priority = 350

        def __call__(self, trainer):
            _time.sleep(0.1)

    trainer.extend(_Beacon())
    trainer.extend(_Pacer())
    trainer.extend(cp, trigger=(max(2, steps // 6), "iteration"))
    trainer.extend(recovery)
    start = _time.perf_counter()
    trainer.run()
    wall = _time.perf_counter() - start
    if pid == 0:
        stats = recovery.stats
        print(json.dumps({
            "processes": nprocs, "per_rank_bs": per_rank_bs,
            "elastic": preempt_rank >= 0,
            "preempt_rank": preempt_rank if preempt_rank >= 0 else None,
            "world_size": recovery.comm.inter_size,
            "resizes": stats["resizes"],
            "ranks_lost": stats["ranks_lost"],
            "ranks_joined": stats["ranks_joined"],
            "iterations": trainer.updater.iteration,
            "wall_s": round(wall, 3),
            "step_ms": round(wall / max(1, trainer.updater.iteration)
                             * 1e3, 3),
            "examples_per_sec": round(
                trainer.updater.iteration * gbs / wall, 1)}), flush=True)


def _gloo_fleet_worker(pid, nprocs, port, n_requests, kill_step):
    """One process of the serving-fleet kill-under-load A/B (ISSUE 15):
    process 0 runs the router + replica 0, every other process one
    :class:`FleetWorker` replica over the REAL host channel.  On the
    kill leg the worker replica preempts at decode step ``kill_step``
    (announced leave + silence — the router detects through the typed
    channel timeout), its in-flight requests replay on the survivor
    with ZERO drops, and the preempted replica re-joins via the
    multicast-tree weight sync.  ``kill_step < 0`` is the uninterrupted
    baseline leg; the p99 completion-latency delta between the legs is
    the detection-bounded spike the FIRST-CHIP-CONTACT checklist item 9
    stamps."""
    import time as _time

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from chainermn_tpu.communicators._communication_utility import (
        initialize_distributed)
    assert initialize_distributed(f"localhost:{port}",
                                  num_processes=nprocs, process_id=pid)

    import chainermn_tpu as ct
    from chainermn_tpu.communicators import ElasticMembership
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.serving import (FleetWorker, RemoteReplica,
                                       ReplicaFleet, Request,
                                       ServingEngine)

    comm = ct.create_communicator("jax_ici")
    ch = comm._host_channel()
    ch._timeout_ms = 6000   # typed detection in seconds, not minutes
    membership = ElasticMembership(ch._client, rank=pid, world=nprocs,
                                   role="fleet", settle_s=0.5,
                                   poll_s=0.02, timeout_ms=90_000)
    model = TransformerLM(n_vocab=257, d_model=64, n_heads=2,
                          n_layers=2, max_len=64, seed=0)
    engine = ServingEngine(model, num_pages=64, page_size=16,
                           max_batch=4, max_context=64,
                           prefix_cache=False)

    if pid != 0:
        worker = FleetWorker(engine, ch, membership=membership,
                             router_process=0)
        outcome = worker.serve(kill_at=kill_step if kill_step >= 0
                               else None)
        if outcome == "preempted":
            # park until the survivors' shrink decision lands (a join
            # announced mid-shrink would collapse shrink+grow into one
            # no-op resolve — the elastic _preempted discipline)
            epoch_at_leave = membership.current_epoch()
            deadline = _time.monotonic() + 60
            while membership.current_epoch() == epoch_at_leave \
                    and _time.monotonic() < deadline:
                _time.sleep(0.05)
            _time.sleep(0.5)
            membership.announce_join(note="rejoin after preemption")
            view = membership.resolve(
                expect={0, pid}, require={0})
            worker.sync_weights(view, joiners=(pid,))
            worker.serve()   # back in rotation until the router stops us
        return

    # -- process 0: router + local replica 0 --------------------------------
    remotes = {p: RemoteReplica(p, ch, p) for p in range(1, nprocs)}
    fleet = ReplicaFleet(engines={0: engine, **remotes},
                         membership=membership)
    rng = np.random.RandomState(0)
    reqs = [Request(rng.randint(0, 257, 8).astype(np.int32), 4,
                    tenant=f"t{i % 2}", arrival_time=0.0)
            for i in range(n_requests)]
    submit_wall = {}
    t0 = _time.monotonic()
    for r in reqs:
        fleet.submit(r)
        submit_wall[r.request_id] = _time.monotonic()
    rejoined = kill_step < 0
    deadline = _time.monotonic() + 120
    while (fleet.pending() or not rejoined) \
            and _time.monotonic() < deadline:
        if fleet.pending():
            fleet.step()
        if not rejoined:
            if fleet.sheds:
                joins = membership.pending_joins(fleet.view)
                if joins:
                    fleet.join(engines={joins[0]: RemoteReplica(
                        joins[0], ch, joins[0])})
                    rejoined = True
                else:
                    _time.sleep(0.05)
            elif not fleet.pending():
                break   # kill never fired: report the row honestly
    wall = _time.monotonic() - t0
    for rep in fleet.replicas.values():
        if rep.remote and rep.live:
            rep.stop()
    done_ms = [(r.finish_time - submit_wall[r.request_id]) * 1e3
               for r in fleet.completed if r.finish_time is not None
               and r.request_id in submit_wall]
    print(json.dumps({
        "fleet": True, "processes": nprocs, "kill_step": kill_step
        if kill_step >= 0 else None, "requests": n_requests,
        "completed": len(fleet.completed),
        "dropped": n_requests - len(fleet.completed),
        "reroutes": fleet.reroutes, "sheds": fleet.sheds,
        "rejoined": rejoined and kill_step >= 0,
        "detection_s": round(fleet.last_detection_s, 3)
        if fleet.last_detection_s is not None else None,
        "weight_sync_s": round(fleet.weight_sync_s, 3),
        "p99_completion_ms": round(float(
            np.percentile(done_ms, 99)), 2) if done_ms else None,
        "wall_s": round(wall, 3)}), flush=True)


def _run_fleet_ab(nprocs, n_requests, kill_step):
    """The 2-replica gloo fleet kill-under-load A/B (ISSUE 15): one
    uninterrupted run, one kill-and-rejoin run; the summary line is the
    detection-bounded p99 completion spike + the tree weight-sync cost
    (FIRST-CHIP-CONTACT checklist item 9)."""
    import re
    import socket
    import subprocess
    import sys
    if kill_step < 0:
        raise SystemExit(f"--fleet-kill {kill_step} must be a decode "
                         f"step index >= 0")
    env = dict(os.environ)
    if "XLA_FLAGS" in env:
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+\s*", "",
            env["XLA_FLAGS"])
    rows = []
    for leg_kill in (-1, kill_step):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--gloo-fleet-worker", str(pid), str(nprocs), str(port),
             str(n_requests), str(leg_kill)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for pid in range(nprocs)]
        outs = []
        for p in procs:
            try:
                outs.append(p.communicate(timeout=600)[0])
            except subprocess.TimeoutExpired:
                p.kill()
                outs.append(p.communicate()[0])
        assert all(p.returncode == 0 for p in procs), \
            [(p.returncode, o[-2000:]) for p, o in zip(procs, outs)]
        row = json.loads([ln for ln in outs[0].splitlines()
                          if ln.startswith("{")][-1])
        rows.append(row)
        print(json.dumps(row), flush=True)
    base, killed = rows
    print(json.dumps({
        "fleet_ab": True, "processes": nprocs,
        "kill_step": kill_step,
        "dropped": killed["dropped"],
        "reroutes": killed["reroutes"],
        "detection_s": killed["detection_s"],
        "weight_sync_s": killed["weight_sync_s"],
        "p99_spike_ms_vs_baseline": round(
            (killed["p99_completion_ms"] or 0)
            - (base["p99_completion_ms"] or 0), 2)}), flush=True)
    return rows


def _gloo_capacity_worker(pid, nprocs, port, n_requests, convert):
    """One process of the capacity-transfer A/B (ISSUE 16).  BOTH legs
    train the same data-parallel MLP over real gloo transport and serve
    the same open-loop burst from process 0 — they differ only in what
    the cluster does with rank 1 during the burst.  Baseline
    (``convert=0``): rank 1 keeps training (full world) and ONE replica
    serves.  Capacity leg (``convert=1``): queue pressure trips the
    hysteresis policy's +1 and the :class:`CapacityBroker` converts
    rank 1 into a second replica over the REAL KV membership +
    multicast tree (training continues at world 1 on rank 0's data
    shard), the drained queues trip the -1 and rank 1 retires back
    into training.  Both legs run the SAME total optimizer-step count
    and end with a root-0 param resync (the rejoin's state sync), so
    the runner can gate final-loss parity."""
    import time as _time

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from chainermn_tpu.communicators._communication_utility import (
        initialize_distributed)
    assert initialize_distributed(f"localhost:{port}",
                                  num_processes=nprocs, process_id=pid)

    import chainermn_tpu as ct
    from chainermn_tpu.communicators import ElasticMembership
    from chainermn_tpu.core.optimizer import MomentumSGD
    from chainermn_tpu.elastic import CapacityBroker
    from chainermn_tpu.models import MLP, Classifier, TransformerLM
    from chainermn_tpu.serving import (FleetWorker, RemoteReplica,
                                       ReplicaFleet, Request,
                                       ServingEngine)
    from chainermn_tpu.serving.fleet import QueueDepthScalePolicy

    CAP_TAG = 7003
    T_JOINT_IN, T_STINT, T_JOINT_OUT = 4, 6, 6
    comm = ct.create_communicator("jax_ici")
    ch = comm._host_channel()
    ch._timeout_ms = 30_000   # solo-step compiles pause the pump loop
    kv = ch._client
    train_mem = ElasticMembership(kv, rank=pid, world=nprocs,
                                  role="elastic",
                                  settle_s=2.0 if pid == 0 else 0.5,
                                  poll_s=0.02, timeout_ms=90_000)
    fleet_mem = ElasticMembership(kv, rank=pid, world=nprocs,
                                  role="fleet",
                                  settle_s=2.0 if pid == 0 else 0.5,
                                  poll_s=0.02, timeout_ms=90_000)

    rng = np.random.RandomState(0)
    # a SMOOTH training problem (large batch, learnable labels): the
    # parity gate compares the two legs' final loss, so the landscape
    # must not be a memorization cliff where any trajectory split
    # explodes the relative delta
    gbs = 128 * nprocs
    x = rng.normal(0, 1, (gbs, 64)).astype(np.float32)
    w_true = rng.normal(0, 1, (64, 10)).astype(np.float32)
    t = np.argmax(x @ w_true, axis=1).astype(np.int32)
    model = Classifier(MLP(n_units=64, n_out=10, seed=0))
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.05, momentum=0.9), comm).setup(model)

    # the convertible rank's engine seeds DIFFERENT weights (seed=pid):
    # the tree sync must overwrite them from replica 0
    serve_model = TransformerLM(n_vocab=257, d_model=32, n_heads=1,
                                n_layers=1, max_len=32, seed=pid)
    engine = ServingEngine(serve_model, num_pages=64, page_size=8,
                           max_batch=4, max_context=32,
                           prefix_cache=False)

    for _ in range(T_JOINT_IN):
        opt.update(model, x, t)

    if pid != 0:
        msg = ch.recv_obj(0, tag=CAP_TAG)
        if msg == ("stint",):   # baseline: keep training at full world
            for _ in range(T_STINT):
                opt.update(model, x, t)
        else:                   # capacity leg: become a serving replica
            assert msg == ("convert",), msg
            fleet_mem.announce_join(note="capacity transfer")
            fview = fleet_mem.resolve(expect=set(range(nprocs)),
                                      require={0})
            worker = FleetWorker(engine, ch, membership=fleet_mem,
                                 router_process=0)
            worker.sync_weights(fview, joiners=(pid,))
            outcome = worker.serve()   # until the retire stops us
            assert outcome == "stopped", outcome
            train_mem.announce_join(note="capacity transfer: rejoin")
            train_mem.resolve(expect=set(range(nprocs)), require={0})
        comm.bcast_data(model)  # root-0 resync (the rejoin's state
        #                         sync; an idempotent no-op baseline)
        for _ in range(T_JOINT_OUT):
            opt.update(model, x, t)
        return

    # -- process 0: router + replica 0 + the broker --------------------------
    policy = QueueDepthScalePolicy(scale_up_depth=2, scale_down_depth=0,
                                   min_replicas=1, max_replicas=2)
    fleet = ReplicaFleet(engines={0: engine}, membership=fleet_mem,
                         min_replicas=1,
                         scale_policy=policy if convert else None)
    broker = CapacityBroker(
        train_mem, fleet,
        engine_factory=lambda r: RemoteReplica(r, ch, r),
        min_world=1) if convert else None

    srng = np.random.RandomState(3)
    reqs = [Request(srng.randint(1, 257, 8).astype(np.int32), 4,
                    tenant=f"t{i % 2}", arrival_time=0.0, request_id=i)
            for i in range(n_requests)]
    submit_wall = {}
    t0 = _time.monotonic()
    for r in reqs:
        fleet.submit(r)
        submit_wall[r.request_id] = _time.monotonic()

    if convert:
        st = fleet.step()
        assert st["scale_decision"] == 1, st
        ch.send_obj(("convert",), 1, tag=CAP_TAG)
        # wait for the worker's fleet join intent so the admission
        # resolve can never settle without it
        deadline = _time.monotonic() + 60
        while fleet_mem._try_get(f"{fleet_mem._base}/join/1") is None \
                and _time.monotonic() < deadline:
            _time.sleep(0.02)
        res = broker.apply(st["scale_decision"])
        assert res == ("convert", 1), res
    else:
        ch.send_obj(("stint",), 1, tag=CAP_TAG)

    # the stint: training continues WHILE the burst is served —
    # baseline at full world (rank 1 in lockstep), capacity leg at
    # world 1 on rank 0's own data shard (rank 1 is busy serving)
    decision = 0
    shard = slice(0, gbs // nprocs)
    for _ in range(T_STINT):
        if convert:
            opt.actual_optimizer.update(model, x[shard], t[shard])
        else:
            opt.update(model, x, t)
        for _ in range(4):
            if not fleet.pending():
                break
            st = fleet.step()
            if st.get("scale_decision"):
                decision = st["scale_decision"]
    steps = 0
    while fleet.pending() and steps < 10_000:
        st = fleet.step()
        if st.get("scale_decision"):
            decision = st["scale_decision"]
        steps += 1
    if convert:
        assert decision == -1, decision  # the drain tripped the -1
        res = broker.apply(decision)
        assert res == ("retire", 1), res
        deadline = _time.monotonic() + 60
        while not train_mem.pending_joins() \
                and _time.monotonic() < deadline:
            _time.sleep(0.05)
        train_mem.resolve(expect=set(range(nprocs)))
    comm.bcast_data(model)
    final_loss = None
    for _ in range(T_JOINT_OUT):
        final_loss = float(opt.update(model, x, t))
    wall = _time.monotonic() - t0

    done_ms = [(r.finish_time - submit_wall[r.request_id]) * 1e3
               for r in fleet.completed if r.finish_time is not None
               and r.request_id in submit_wall]
    print(json.dumps({
        "capacity": True, "processes": nprocs,
        "convert": bool(convert), "requests": n_requests,
        "completed": len(fleet.completed),
        "dropped": n_requests - len(fleet.completed),
        "p99_completion_ms": round(float(
            np.percentile(done_ms, 99)), 2) if done_ms else None,
        "final_loss": round(final_loss, 6),
        "conversions": broker.stats["conversions"]
        if broker is not None else 0,
        "role_transfers": broker.stats["role_transfers"]
        if broker is not None else 0,
        "convert_s": round(broker.stats["convert_s"], 3)
        if broker is not None else 0.0,
        "weight_sync_s": round(fleet.weight_sync_s, 3),
        "wall_s": round(wall, 3)}), flush=True)


def _run_capacity_ab(nprocs, n_requests):
    """The 2-process gloo capacity-transfer A/B (ISSUE 16): one leg
    where rank 1 keeps training through the serving burst (one
    replica), one where the CapacityBroker converts it into a second
    replica for the burst and retires it after the drain.  Gates: ZERO
    drops on both legs, exactly one conversion + retire on the
    capacity leg, and final training loss parity within ±5% — lending
    a rank to serving must not cost the training run.  The summary
    line is the p99 completion delta the borrowed replica bought
    (FIRST-CHIP-CONTACT checklist item 10)."""
    import re
    import socket
    import subprocess
    import sys
    env = dict(os.environ)
    if "XLA_FLAGS" in env:
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+\s*", "",
            env["XLA_FLAGS"])
    rows = []
    for leg_convert in (0, 1):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--gloo-capacity-worker", str(pid), str(nprocs), str(port),
             str(n_requests), str(leg_convert)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for pid in range(nprocs)]
        outs = []
        for p in procs:
            try:
                outs.append(p.communicate(timeout=600)[0])
            except subprocess.TimeoutExpired:
                p.kill()
                outs.append(p.communicate()[0])
        assert all(p.returncode == 0 for p in procs), \
            [(p.returncode, o[-2000:]) for p, o in zip(procs, outs)]
        row = json.loads([ln for ln in outs[0].splitlines()
                          if ln.startswith("{")][-1])
        rows.append(row)
        print(json.dumps(row), flush=True)
    base, cap = rows
    assert base["dropped"] == 0 and cap["dropped"] == 0, (base, cap)
    assert cap["conversions"] == 1 and cap["role_transfers"] == 2, cap
    parity = abs(cap["final_loss"] - base["final_loss"]) \
        / max(abs(base["final_loss"]), 1e-9)
    assert parity <= 0.05, \
        f"capacity stint cost training: final loss {cap['final_loss']}" \
        f" vs baseline {base['final_loss']} ({parity:.1%} > 5%)"
    print(json.dumps({
        "capacity_ab": True, "processes": nprocs,
        "loss_parity_frac": round(parity, 4),
        "conversions": cap["conversions"],
        "role_transfers": cap["role_transfers"],
        "convert_s": cap["convert_s"],
        "weight_sync_s": cap["weight_sync_s"],
        "p99_ms_saved_vs_training_priority": round(
            (base["p99_completion_ms"] or 0)
            - (cap["p99_completion_ms"] or 0), 2)}), flush=True)
    return rows


def _run_elastic_ab(nprocs, per_rank_bs, hidden, steps, preempt_rank):
    """The ≥2-host elastic A/B (ISSUE 10): one uninterrupted P-process
    run, one preempt-and-rejoin run, and the delta — the end-to-end
    cost of losing and re-admitting a rank (typed detection + two
    membership resolves + two rebuilds + snapshot sync) under real
    process boundaries."""
    import re
    import socket
    import subprocess
    import sys
    if not 0 <= preempt_rank < nprocs:
        raise SystemExit(f"--preempt-rank {preempt_rank} is not a rank "
                         f"of a {nprocs}-process run")
    env = dict(os.environ)
    if "XLA_FLAGS" in env:
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+\s*", "",
            env["XLA_FLAGS"])
    rows = []
    for leg_preempt in (-1, preempt_rank):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--gloo-elastic-worker", str(pid), str(nprocs), str(port),
             str(per_rank_bs), str(hidden), str(steps),
             str(leg_preempt)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for pid in range(nprocs)]
        outs = []
        for p in procs:
            try:
                outs.append(p.communicate(timeout=600)[0])
            except subprocess.TimeoutExpired:
                p.kill()
                outs.append(p.communicate()[0])
        assert all(p.returncode == 0 for p in procs), \
            [(p.returncode, o[-2000:]) for p, o in zip(procs, outs)]
        row = json.loads([ln for ln in outs[0].splitlines()
                          if ln.startswith("{")][-1])
        rows.append(row)
        print(json.dumps(row), flush=True)
    base, elastic = rows
    print(json.dumps({
        "processes": nprocs, "preempt_rank": preempt_rank,
        "elastic_overhead_s": round(
            elastic["wall_s"] - base["wall_s"], 3),
        "elastic_step_ms_vs_baseline": round(
            elastic["step_ms"] - base["step_ms"], 3),
        "resizes": elastic["resizes"]}), flush=True)
    return rows


def _gloo_autotune_worker(pid, nprocs, port, per_rank_bs, hidden, steps,
                          mode, ratio):
    """One process of the ISSUE 19 autotune A/B: the same hierarchical
    compiled DP step as ``_gloo_worker``'s striped legs, but leg
    ``auto`` builds its communicator with ``autotune=True`` (the
    startup micro-bench runs over the real gloo fabric and the agreed
    plan fills the knobs the caller left free) while leg ``hand`` pins
    ``stripe_ratio`` to the value the auto leg derived.  Every per-step
    loss travels in the row as ``float.hex()`` — the parent gates
    BITWISE equality between the two legs (the golden-trajectory
    contract: a derived plan matching the hand knobs must compile the
    identical program)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from chainermn_tpu.communicators._communication_utility import (
        initialize_distributed)
    assert initialize_distributed(f"localhost:{port}",
                                  num_processes=nprocs, process_id=pid)
    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import MomentumSGD
    from chainermn_tpu.models import MLP, Classifier

    if mode == "auto":
        # stripe_ratio deliberately NOT passed: the knob must stay free
        # for the agreed plan to fill (hand knobs always win — a pinned
        # ratio here would make the A/B compare hand vs hand)
        comm = ct.create_communicator("hierarchical",
                                      batch_collectives=True,
                                      autotune=True)
        assert comm.autotune_plan is not None
        assert comm.striped, \
            "autotune must have applied the derived stripe plan"
    else:
        comm = ct.create_communicator("hierarchical",
                                      batch_collectives=True,
                                      stripe_ratio=float(ratio))
    assert comm.size == nprocs == jax.device_count()
    model = Classifier(MLP(n_units=hidden, n_out=10, seed=0))
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.01, momentum=0.9), comm).setup(model)

    gbs = per_rank_bs * nprocs
    rng = np.random.RandomState(0)
    x = np.asarray(rng.normal(0, 1, (gbs, 64)).astype(np.float32))
    t = np.asarray(rng.randint(0, 10, gbs).astype(np.int32))

    losses = []
    for _ in range(3):  # trace+compile, then steady-state warmup
        losses.append(float(opt.update(model, x, t)))
    if nprocs > 1:
        comm._host_channel().barrier()
    start = time.perf_counter()
    for _ in range(steps):
        # the per-step float() sync is part of BOTH legs' measured
        # loop, so the step_ms rows stay comparable — and the full
        # loss trajectory is what the bitwise gate compares
        losses.append(float(opt.update(model, x, t)))
    dt = time.perf_counter() - start
    if pid == 0:
        row = {"mode": mode, "processes": nprocs,
               "per_rank_bs": per_rank_bs,
               "stripe_ratio": comm.stripe_ratio,
               "step_ms": round(dt / steps * 1e3, 3),
               "examples_per_sec": round(steps * gbs / dt, 1),
               "losses_hex": [float(v).hex() for v in losses]}
        if mode == "auto":
            plan = comm.autotune_plan
            dcn = plan["measurements"]["hops"].get("dcn") or {}
            row["plan"] = {
                "fingerprint": plan["fingerprint"],
                "stripe_ratio": plan["stripe_ratio"],
                "bucket_mb": plan["bucket_mb"],
                "grad_dtype": plan["grad_dtype"],
                "dcn_gbps": dcn.get("gbps"),
                "dcn_lat_us": dcn.get("lat_us"),
                "notes": plan["derivation"]["notes"]}
        print(json.dumps(row), flush=True)


#: sweep legs of the --autotune optimum-band gate, and how far (mean
#: step_ms, relative) a ratio may sit above the sweep winner and still
#: count as inside the band.  Generous on purpose: loopback gloo on a
#: time-sliced host is noisy, and at one device per process the ICI hop
#: is wireless, which flattens the ratio curve toward a tie
AUTOTUNE_SWEEP_RATIOS = (0.25, 0.5, 0.75)
AUTOTUNE_BAND_TOL = 0.35


def _run_autotune_ab(nprocs, per_rank_bs, hidden, steps):
    """The 2-process gloo autotune A/B (ISSUE 19) — the promotion of
    the queued three-invocation striped ratio sweep into ONE
    self-gating invocation.  Leg 1 builds its communicator with
    ``autotune=True`` (startup micro-bench over the real gloo fabric,
    agreed plan applied); leg 2 hand-pins ``stripe_ratio`` to the
    derived value.  Gates: (a) BITWISE golden-trajectory equality
    between the two legs — the derived plan must compile exactly the
    program the equivalent hand knobs would; (b) the derived ratio
    lands inside the measured optimum band of the
    ``AUTOTUNE_SWEEP_RATIOS`` sweep (mean step_ms within
    ``AUTOTUNE_BAND_TOL`` of the sweep winner).  In the gloo world the
    ICI axis is size 1 (unmeasurable), so the derived ratio is the
    documented DEFAULT_STRIPE_RATIO fallback — the band gate then
    checks the fallback itself is not a measured pessimization."""
    import re
    import socket
    import subprocess
    import sys
    env = dict(os.environ)
    if "XLA_FLAGS" in env:
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+\s*", "",
            env["XLA_FLAGS"])
    # a leaked ratio env var would hand-pin the auto leg's knob and turn
    # the golden gate into hand-vs-hand
    env.pop("CHAINERMN_TPU_STRIPE_RATIO", None)

    def leg(mode, ratio):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--gloo-autotune-worker", str(pid), str(nprocs), str(port),
             str(per_rank_bs), str(hidden), str(steps), mode, str(ratio)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for pid in range(nprocs)]
        outs = []
        for p in procs:
            try:
                outs.append(p.communicate(timeout=600)[0])
            except subprocess.TimeoutExpired:
                p.kill()
                outs.append(p.communicate()[0])
        assert all(p.returncode == 0 for p in procs), \
            [(p.returncode, o[-2000:]) for p, o in zip(procs, outs)]
        row = json.loads([ln for ln in outs[0].splitlines()
                          if ln.startswith("{")][-1])
        print(json.dumps({k: v for k, v in row.items()
                          if k != "losses_hex"}), flush=True)
        return row

    auto = leg("auto", "-")
    derived = auto["plan"]["stripe_ratio"]
    hand = leg("hand", derived)
    assert auto["losses_hex"] == hand["losses_hex"], \
        f"golden-trajectory gate FAILED: autotune (plan " \
        f"{auto['plan']['fingerprint']}) diverged from hand knobs at " \
        f"stripe_ratio={derived}"

    sweep = {}
    for r in AUTOTUNE_SWEEP_RATIOS:
        # the hand leg already measured the derived ratio — reuse its
        # datum rather than burning a fourth spawn on the same point
        sweep[r] = hand["step_ms"] if abs(r - derived) < 1e-9 \
            else leg("hand", r)["step_ms"]
    winner_ms = min(sweep.values())
    band = [r for r in AUTOTUNE_SWEEP_RATIOS
            if sweep[r] <= winner_ms * (1.0 + AUTOTUNE_BAND_TOL)]
    assert any(abs(derived - r) < 1e-9 for r in band), \
        f"derived stripe_ratio {derived} is outside the measured " \
        f"optimum band {band} (sweep step_ms {sweep}, winner " \
        f"{winner_ms} ms, tol {AUTOTUNE_BAND_TOL:.0%})"

    print(json.dumps({
        "autotune_ab": True, "processes": nprocs,
        "derived_stripe_ratio": derived,
        "plan_fingerprint": auto["plan"]["fingerprint"],
        "golden_trajectory_equal": True,
        "sweep_step_ms": {str(r): sweep[r] for r in sorted(sweep)},
        "optimum_band": band,
        "derived_in_band": True,
        "measured_dcn_gbps": auto["plan"]["dcn_gbps"],
        "measured_dcn_lat_us": auto["plan"]["dcn_lat_us"]}), flush=True)
    return auto, hand, sweep


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--per-chip-bs", type=int, default=8)
    parser.add_argument("--size", type=int, default=96)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--model", default="resnet18",
                        choices=["resnet18", "resnet50"])
    parser.add_argument("--allreduce-floats", type=int, default=1 << 22)
    parser.add_argument("--platform", default=None)
    parser.add_argument("--simulate-devices", type=int, default=0)
    parser.add_argument("--project", action="store_true",
                        help="print analytic pod projections from a "
                             "measured single-chip step time (--step-ms)")
    parser.add_argument("--step-ms", type=float, default=None,
                        help="measured single-chip step time for --project")
    parser.add_argument("--gloo-procs", default=None,
                        help="comma list, e.g. 1,2,4: measure the REAL "
                             "cross-process compiled DP step at each "
                             "process count (gloo CPU backend)")
    parser.add_argument("--gloo-worker", nargs=8, default=None,
                        help=argparse.SUPPRESS)  # internal
    parser.add_argument("--gloo-elastic-worker", nargs=7, default=None,
                        help=argparse.SUPPRESS)  # internal
    parser.add_argument("--gloo-fleet-worker", nargs=5, default=None,
                        help=argparse.SUPPRESS)  # internal
    parser.add_argument("--gloo-capacity-worker", nargs=5, default=None,
                        help=argparse.SUPPRESS)  # internal
    parser.add_argument("--gloo-autotune-worker", nargs=8, default=None,
                        help=argparse.SUPPRESS)  # internal
    parser.add_argument("--autotune", action="store_true",
                        help="run the self-tuning A/B (ISSUE 19): one "
                             "gloo leg builds its communicator with "
                             "autotune=True (startup micro-bench over "
                             "the real fabric, agreed plan applied), "
                             "one hand-pins the derived knobs; gates "
                             "BITWISE golden-trajectory equality plus "
                             "'derived ratio inside the measured "
                             "optimum band' of the {0.25, 0.5, 0.75} "
                             "sweep — replaces the queue's three "
                             "striped ratio-sweep invocations; P = max "
                             "of --gloo-procs (default 2)")
    parser.add_argument("--capacity", action="store_true",
                        help="run the capacity-transfer A/B (ISSUE 16):"
                             " one gloo leg where rank 1 keeps training"
                             " through a serving burst (one replica), "
                             "one where the CapacityBroker converts it "
                             "into a second replica and retires it "
                             "after the drain; gates zero drops + "
                             "training loss parity (±5%); the summary "
                             "line is the p99 completion delta the "
                             "borrowed replica bought.  Request count "
                             "from --fleet-requests; P = max of "
                             "--gloo-procs (default 2)")
    parser.add_argument("--fleet-kill", type=int, default=None,
                        help="run the serving-fleet kill-under-load A/B"
                             " (ISSUE 15): an uninterrupted 2-replica "
                             "gloo fleet run vs one where the worker "
                             "replica preempts at this decode step, its"
                             " in-flight requests replay on the "
                             "survivor (zero drops) and the replica "
                             "re-joins via the multicast-tree weight "
                             "sync; P = max of --gloo-procs (default "
                             "2).  The summary line is the detection-"
                             "bounded p99 spike + the sync cost")
    parser.add_argument("--fleet-requests", type=int, default=16,
                        help="open-loop request count for --fleet-kill")
    parser.add_argument("--preempt-rank", type=int, default=None,
                        help="run the elastic preempt-and-rejoin A/B "
                             "(ISSUE 10): an uninterrupted P-process "
                             "gloo run vs one where this rank is "
                             "hard-preempted mid-run, shrinks out, "
                             "re-joins and the world grows back; P = "
                             "max of --gloo-procs (default 2).  The "
                             "summary line is the end-to-end "
                             "elasticity tax")
    parser.add_argument("--gloo-hidden", type=int, default=512,
                        help="MLP hidden width for --gloo-procs")
    parser.add_argument("--gloo-zero", action="store_true",
                        help="use the ZeRO-1 sharded step (psum_scatter"
                             " + all_gather) instead of plain DP pmean")
    parser.add_argument("--gloo-reps", type=int, default=1,
                        help="repeat each P-process measurement and "
                             "report mean/min/max (noise quantification"
                             " on time-sliced hosts)")
    parser.add_argument("--gloo-exchange", default="flat",
                        help="gradient-exchange structure under test: "
                             "per_leaf|flat|bucketed|reduce_scatter|"
                             "hierarchical|hierarchical_rs|striped|"
                             "striped_rs (validated against "
                             "communicators.EXCHANGES — the "
                             "ISSUE 5 exposed-comm A/B: run the curve "
                             "once with flat, once with bucketed — the "
                             "delta across real process boundaries is "
                             "the overlap payoff.  The ISSUE 6 "
                             "hierarchical legs run the two-level "
                             "exchange with the DCN hop on the real "
                             "process boundary: dcn=P × ici=1 at one "
                             "device per process; the ISSUE 11 striped "
                             "legs run the multi-path exchange — sweep "
                             "--stripe-ratio over {0.25, 0.5, 0.75} to "
                             "measure the per-topology split a pod "
                             "should commit)")
    parser.add_argument("--stripe-ratio", type=float, default=None,
                        help="DCN share of the striped exchange for "
                             "this invocation (striped legs only; "
                             "default: the committed "
                             "DEFAULT_STRIPE_RATIO).  The first-chip-"
                             "contact queue runs the {0.25, 0.5, 0.75} "
                             "sweep as three invocations")
    args = parser.parse_args()

    if args.gloo_worker:
        pid, nprocs, port, bs, hidden, steps, zero = \
            map(int, args.gloo_worker[:7])
        _gloo_worker(pid, nprocs, port, bs, hidden, steps, bool(zero),
                     exchange=args.gloo_worker[7])
        return
    if args.gloo_elastic_worker:
        _gloo_elastic_worker(*map(int, args.gloo_elastic_worker))
        return
    if args.gloo_fleet_worker:
        _gloo_fleet_worker(*map(int, args.gloo_fleet_worker))
        return
    if args.gloo_capacity_worker:
        _gloo_capacity_worker(*map(int, args.gloo_capacity_worker))
        return
    if args.gloo_autotune_worker:
        pid, nprocs, port, bs, hidden, steps = \
            map(int, args.gloo_autotune_worker[:6])
        _gloo_autotune_worker(pid, nprocs, port, bs, hidden, steps,
                              args.gloo_autotune_worker[6],
                              args.gloo_autotune_worker[7])
        return
    if args.autotune:
        nprocs = max(int(c) for c in args.gloo_procs.split(",")) \
            if args.gloo_procs else 2
        _run_autotune_ab(nprocs, args.per_chip_bs, args.gloo_hidden,
                         args.steps)
        return
    if args.capacity:
        nprocs = max(int(c) for c in args.gloo_procs.split(",")) \
            if args.gloo_procs else 2
        _run_capacity_ab(nprocs, args.fleet_requests)
        return
    if args.fleet_kill is not None:
        nprocs = max(int(c) for c in args.gloo_procs.split(",")) \
            if args.gloo_procs else 2
        _run_fleet_ab(nprocs, args.fleet_requests, args.fleet_kill)
        return
    if args.preempt_rank is not None:
        nprocs = max(int(c) for c in args.gloo_procs.split(",")) \
            if args.gloo_procs else 2
        _run_elastic_ab(nprocs, args.per_chip_bs, args.gloo_hidden,
                        args.steps, args.preempt_rank)
        return
    if args.gloo_procs:
        # lazy: the vocabulary lives with the communicator mapping (the
        # parent never touches devices, so the import is safe here; the
        # --gloo-worker branch above stays import-free until its own
        # platform pinning has run)
        from chainermn_tpu.communicators import EXCHANGES
        if args.gloo_exchange not in EXCHANGES:
            parser.error(f"unknown --gloo-exchange "
                         f"{args.gloo_exchange!r} "
                         f"({'|'.join(EXCHANGES)})")
        if args.gloo_zero and args.gloo_exchange in ("reduce_scatter",
                                                     "hierarchical_rs",
                                                     "striped_rs"):
            # fail before any worker spawns: every worker would raise
            # create_multi_node_optimizer's zero×reduce_scatter
            # ValueError after ports are bound and gloo is up — in the
            # unattended queue that burns the slot with no datum
            parser.error("--gloo-zero already exchanges gradients via "
                         "reduce-scatter; drop --gloo-exchange "
                         f"{args.gloo_exchange}")
        if args.stripe_ratio is not None \
                and args.gloo_exchange not in ("striped", "striped_rs"):
            parser.error("--stripe-ratio only applies to the striped "
                         "legs; drop it or use --gloo-exchange striped")
        counts = [int(c) for c in args.gloo_procs.split(",")]
        _run_gloo_curve(counts, args.per_chip_bs, args.gloo_hidden,
                        args.steps, zero=args.gloo_zero,
                        reps=args.gloo_reps, exchange=args.gloo_exchange,
                        stripe_ratio=args.stripe_ratio)
        return

    if args.project:
        if args.step_ms is None:
            parser.error("--project requires --step-ms (from bench.py)")
        for n in (2, 4, 8, 16, 32, 64):
            eff = project_efficiency(args.step_ms, n)
            print(json.dumps({"devices": n, "step_ms_1chip": args.step_ms,
                              "projected_scaling_efficiency": round(eff, 4)}))
        return

    if args.simulate_devices:
        from chainermn_tpu.utils import simulate_devices
        simulate_devices(args.simulate_devices)
    if args.platform:
        from chainermn_tpu.utils import use_platform
        use_platform(args.platform)

    import jax
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()
    max_devices = len(jax.devices())
    counts = [n for n in (1, 2, 4, 8, 16, 32) if n <= max_devices]

    base = None
    results = []
    for n in counts:
        thr = measure_step_throughput(n, args.per_chip_bs, args.size,
                                      args.steps, args.model)
        if base is None:
            base = thr
        eff = thr / (n * base)
        bw = measure_allreduce_bandwidth(n, args.allreduce_floats) \
            if n > 1 else 0.0
        row = {"devices": n, "images_per_sec": round(thr, 2),
               "scaling_efficiency": round(eff, 4),
               "allreduce_gbps_per_chip": round(bw, 2)}
        results.append(row)
        print(json.dumps(row), flush=True)

    print(json.dumps({
        "metric": f"{args.model}_dp_scaling_efficiency_1_to_{counts[-1]}",
        "value": results[-1]["scaling_efficiency"],
        "unit": "fraction",
        "vs_baseline": round(results[-1]["scaling_efficiency"] / 0.9, 3),
    }))


if __name__ == "__main__":
    main()
