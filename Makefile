# Common workflows.  The CPU-simulated mesh flags are applied by each
# entry point itself (tests/conftest.py pins cpu; examples take
# --platform/--simulate-devices; bench/dryrun self-configure).

PY := PYTHONPATH=$(CURDIR):$$PYTHONPATH python

.PHONY: test chaos chaos-elastic chaos-fleet chaos-convert bench bench-smoke bench-input chip-smoke scaling scaling-gloo probe-input probe-bytes probe-flash probe-comm probe-autotune probe-serving probe-obs sweep-flash audit dryrun examples clean

test:
	$(PY) -m pytest tests/ -x -q

chaos:            ## fault-injection suite, rotating seed (echoed for repro)
	@# CHAOS_SEED pins a repro; otherwise rotate from the clock.  Tier-1
	@# runs the same suite with the deterministic default seed (the
	@# chaos marker is not slow-marked), so this target's job is the
	@# seed sweep.
	@seed=$${CHAOS_SEED:-$$(python3 -c "import time; print(int(time.time()) % 100000)")}; \
	echo "chaos seed: $$seed  (repro: CHAOS_SEED=$$seed make chaos)"; \
	CHAINERMN_TPU_CHAOS_SEED=$$seed $(PY) -m pytest tests/ -q -m chaos

chaos-elastic:    ## elastic preempt-and-rejoin E2E (2-process gloo)
	@# ISSUE 10 acceptance: rank 1 hard-preempted mid-run -> survivors
	@# shrink and keep training -> rank re-joins, world grows back ->
	@# convergence parity + cross-world-size checkpoint bit-exactness.
	@# Runs under the chaos marker (tier-1 runs it too; this target is
	@# the focused repro loop).
	$(PY) -m pytest tests/multiprocess_tests/test_elastic_chaos.py -q -m chaos

chaos-fleet:      ## serving-fleet kill-a-replica E2E (2-process gloo)
	@# ISSUE 15 acceptance: one of two decode replicas preempted under
	@# open-loop load -> typed-timeout detection, fleet membership
	@# shrinks, in-flight sequences replay on the survivor with ZERO
	@# drops and solo-run trajectories -> the replica re-joins and
	@# adopts bit-identical weights over the multicast-tree sync ->
	@# the router spreads new admissions to it.  Chaos-marked (tier-1
	@# runs it too; this target is the focused repro loop).
	$(PY) -m pytest tests/multiprocess_tests/test_fleet_chaos.py -q -m chaos

chaos-convert:    ## capacity-transfer E2E (2-process gloo)
	@# ISSUE 16 acceptance: a seeded preempt kills a training->serving
	@# conversion mid-flight -> the survivor's recover_orphans sweep
	@# aborts the orphan through the real KV journal and the rank
	@# rejoins training; then queue pressure trips the hysteresis +1,
	@# the CapacityBroker converts the rank into a serving replica
	@# (bit-identical tree weight sync), the fleet drains with ZERO
	@# drops, the -1 retires it back into training.  Chaos-marked
	@# (tier-1 runs it too; this target is the focused repro loop).
	$(PY) -m pytest tests/multiprocess_tests/test_capacity_chaos.py -q -m chaos

bench:            ## real-hardware benchmark (one JSON line)
	$(PY) bench.py

bench-smoke:      ## CPU rehearsal of the bench mechanics (labelled rows)
	JAX_PLATFORMS=cpu BENCH_BS=2 BENCH_SIZE=64 BENCH_STEPS=2 $(PY) bench.py

chip-smoke:       ## the trainer and the serving engine, once, on the chip
	$(PY) chip_smoke.py

scaling:
	$(PY) bench_scaling.py --platform cpu --simulate-devices 8 --per-chip-bs 4 --size 64 --steps 3

scaling-gloo:     ## real cross-process compiled-DP + ZeRO curves (CPU gloo)
	$(PY) bench_scaling.py --gloo-procs 1,2,4 --per-chip-bs 64 --steps 200
	$(PY) bench_scaling.py --gloo-procs 1,2,4 --per-chip-bs 64 --steps 200 --gloo-zero

probe-input:      ## host input-pipeline bandwidth at flagship scale (no chip)
	PROBE=input_pipeline PROBE_PLATFORM=cpu $(PY) tools/probe_perf.py

probe-bytes:      ## flagship HBM byte bill vs committed budget (no chip)
	@# per-op-category bytes_accessed table + memory_analysis peaks for
	@# the flagship ResNet-50 train step, checked against
	@# tools/hbm_budgets.json (the tier-1 regression gate's data).
	@# PROBE_COMPILE=0 skips backend codegen (lowered accounting only).
	PROBE=hbm_bytes PROBE_PLATFORM=cpu $(PY) tools/probe_perf.py

bench-input:      ## GIL-bound transform: MultiprocessIterator vs MultithreadIterator (no chip, no jax)
	$(PY) tools/bench_input.py

sweep-flash:      ## on-chip flash fwd/bwd/fwd+bwd tile sweep; regenerates tools/flash_budgets.json
	@# the r5 BENCH_NOTES sweep methodology as one command.  On a
	@# chip-less box this interpret-smokes clamped T and REFUSES the
	@# budget rewrite (budgets are measured artifacts).
	$(PY) tools/flash_sweep.py --write-budgets

probe-flash:      ## committed flash budgets joined with live fused-vs-split rows (cpu = smoke)
	PROBE=flash PROBE_PLATFORM=cpu $(PY) tools/probe_perf.py

probe-serving:    ## committed serving budgets + live decode/prefill census + per-phase + fleet tables (no chip)
	@# decode: one gather per pool per layer through the block table,
	@# no [T, T] score dot; prefill: flash forward kernels, zero bwd
	@# kernels — joined with tools/serving_budgets.json (the tier-1
	@# gate tests/test_serving_budget.py's data) and the decode
	@# roofline byte table; plus the ISSUE 15 fleet table (one row per
	@# replica seat: live, queue depth, routed/reroute counters) from a
	@# tiny live 2-replica fleet with one replica preempted mid-load.
	PROBE=serving PROBE_PLATFORM=cpu $(PY) tools/probe_perf.py

probe-obs:        ## runtime observability join: trace schema + merged metrics registry (no chip)
	@# runs a tiny seeded trainer + one serving request with the span
	@# tracer on (CHAINERMN_TPU_TRACE=events), validates the exported
	@# Chrome-trace shard against the committed schema, round-trips it
	@# through tools/trace_merge.py, and renders the rank-merged
	@# metrics registry in Prometheus text format (docs/observability.md).
	PROBE=obs PROBE_PLATFORM=cpu $(PY) tools/probe_perf.py

probe-comm:       ## committed gradient-exchange budgets + live per-bucket/per-hop tables (no chip)
	@# jaxpr collective census per exchange config (per_leaf / flat /
	@# bucketed / bucketed_bf16 / reduce_scatter / hierarchical*)
	@# joined with tools/comm_budgets.json, the live bucket plan at
	@# PROBE_BUCKET_MB (default 4), and the hierarchical configs'
	@# per-hop table (hop, collective, bytes, dtype) on the simulated
	@# 2-host split.  Trace property — chip-free.
	PROBE=comm PROBE_PLATFORM=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8 $$XLA_FLAGS" $(PY) tools/probe_perf.py

probe-autotune:   ## committed autotune plan artifact + live micro-bench/derivation (no chip)
	@# the startup fabric micro-bench on the simulated 8-device mesh,
	@# the plan it derives (fingerprint, bucket_mb, stripe_ratio,
	@# grad_dtype + derivation notes), the join against
	@# tools/autotune_plan.json (the tier-1 gate
	@# tests/test_autotune_plan.py's data), and the per-knob provenance
	@# table (plan value / hand-set / applied).  CPU-sim numbers are
	@# labeled mechanics-only — the artifact's numeric half is stamped
	@# only from a run on the real fabric.
	PROBE=autotune PROBE_PLATFORM=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8 $$XLA_FLAGS" $(PY) tools/probe_perf.py

audit:            ## StableHLO dtype census, resnet + transformer (no chip)
	PROBE=precision_audit $(PY) tools/probe_perf.py

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

examples:         ## quick battery on the simulated mesh
	$(PY) examples/train_mnist_dp.py -e 1 -o /tmp/mk_dp --platform cpu --simulate-devices 8
	$(PY) examples/train_mnist_model_parallel.py -e 1 -u 24 -o /tmp/mk_mp --platform cpu --simulate-devices 8
	$(PY) examples/train_seq2seq.py -e 1 -u 16 -o /tmp/mk_s2s --platform cpu --simulate-devices 8

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null; rm -f chainermn_tpu/utils/native/_dataloader.so; rm -rf .jax_cache
