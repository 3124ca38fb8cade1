# Developer entry points. CI runs the same commands
# (.github/workflows/); the driver runs bench.py directly.

.PHONY: test native bench bench-smoke soak soak-smoke distributed \
	chaos lint analyze-device query-dryrun fleetquery-dryrun \
	trace-dryrun churn-smoke clean

native:
	$(MAKE) -C retina_tpu/native

test: native
	python -m pytest tests/ -q

# Real-TPU benchmark (one JSON line; device step + e2e system number).
bench: native
	python bench.py

bench-smoke: native
	python bench.py --smoke

# Time-travel closed loop: burst detection -> range-query attribution
# -> targeted capture, with the query API under concurrent load.
query-dryrun: native
	python bench.py --query-dryrun

# Fleet query plane + detector diversity, CI-sized: 8 simulated nodes
# under a query storm with a mid-storm kill, plus all three builtin
# detectors driving the closed capture loop. The 64-node headline run
# is `python bench.py --fleetquery-dryrun` on hardware.
fleetquery-dryrun: native
	python bench.py --fleetquery-dryrun --smoke

# Multi-process fleet churn, CI-sized: 12 real node-agent processes,
# 3 zone relays re-shipping to a root aggregator, rolling restart +
# both asymmetric partitions + a live seed rotation, scored against
# exact ground truth. The 64-process acceptance run is
# `python bench.py --churn-dryrun`. See docs/operations.md §10.
churn-smoke: native
	python bench.py --churn-dryrun --smoke

# Flight-recorder acceptance: the <3% overhead guard, the debug
# endpoints, and the fleet dryrun's cross-process span-lineage check
# (ship span and aggregator merge span share the window-epoch trace
# ID). See docs/observability.md.
trace-dryrun: native
	python -m pytest tests/test_obs.py \
	    tests/test_chaos.py::test_fleet_node_dropout_rollup_continues -q

# 5-minute paced soak with rate/loss/RSS/scrape budgets.
soak: native
	RETINA_SOAK=1 RETINA_SOAK_SECONDS=300 \
	    python -m pytest tests/test_soak.py -q

# Endurance soak, CI-sized: live agent + 2 heavy-tail regimes + 1
# injected fault, every leak sentinel sampled per window, <=90 s.
# Emits SOAK_*.json; exit code is the sentinel verdict. The full
# rotation (>=30 min, 6 regimes, alternating faults) is
# `python bench.py --soak --soak-seconds 1800` on hardware.
soak-smoke: native
	python bench.py --soak --smoke

# Fault-injection suite: every injected fault (transfer error, hung
# harvest, plugin crash, corrupt checkpoint) must recover in-process.
chaos: native
	python -m pytest tests/ -q -m chaos

# Two-process jax.distributed mesh test (spawns 2 JAX procs).
distributed:
	RETINA_DISTRIBUTED_TESTS=1 \
	    python -m pytest tests/test_distributed_two_process.py -q

# Critical-error gate (matches .github/workflows/lint.yaml). The TPU
# image has no ruff/mypy; tools/lint.py runs the tools/analyze suite —
# the offline mirror of the high-precision ruff rules PLUS the
# repo-specific analyzers (thread safety, JAX trace purity,
# metric/config drift). See docs/static-analysis.md.
lint:
	python -m compileall -q retina_tpu tests tools bench.py chip_smoke.py __graft_entry__.py
	python tools/lint.py

# Device-program analysis (RT300 family): AOT-lowers every registered
# @device_entry program on the CPU backend and checks merge algebra,
# counter overflow, donation, replication and predicate parity.
# Seconds, not milliseconds — separate target so `make lint` stays fast.
analyze-device:
	python tools/lint.py --device

clean:
	$(MAKE) -C retina_tpu/native clean
