# Common workflows.  The CPU-simulated mesh flags are applied by each
# entry point itself (tests/conftest.py pins cpu; examples take
# --platform/--simulate-devices; dryrun self-configures).

PY := PYTHONPATH=$(CURDIR):$$PYTHONPATH python

.PHONY: test chaos chaos-elastic chaos-fleet chaos-convert benchmark chip-smoke sweep-flash dryrun examples clean

# the cell `make benchmark` runs (one entry of BENCHMARK.json's workloads)
CELL ?= gpt2m-train-1chip

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

benchmark:        ## one cell of BENCHMARK.json on the chip (CELL=<workload>)
	python3 -m benchmark.run --workload $(CELL) --seed 1 --seconds 40 --trace 0

chip-smoke:       ## the trainer and the serving engine, once, on the chip
	$(PY) chip_smoke.py

sweep-flash:      ## on-chip flash fwd/bwd/fwd+bwd tile sweep; rewrites tools/flash_budgets.json's sweep section
	@# On a chip-less box this interpret-smokes clamped T and REFUSES
	@# the budget rewrite (budgets are measured artifacts).
	$(PY) tools/flash_sweep.py --write-budgets

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

examples:         ## quick battery on the simulated mesh
	$(PY) examples/train_mnist_dp.py -e 1 -o /tmp/mk_dp --platform cpu --simulate-devices 8
	$(PY) examples/train_mnist_model_parallel.py -e 1 -u 24 -o /tmp/mk_mp --platform cpu --simulate-devices 8
	$(PY) examples/train_seq2seq.py -e 1 -u 16 -o /tmp/mk_s2s --platform cpu --simulate-devices 8

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null; rm -f chainermn_tpu/utils/native/_dataloader.so; rm -rf .jax_cache
