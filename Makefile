# Convenience targets for the canonical workflows. Each one is the
# exact invocation the docs/tests/driver use — no hidden flags.

PYTEST_ENV = XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu

.PHONY: test test-fast lint check check-update chaos soak scope meter \
        fleet spec zero route wire scale quant dryrun bench bench-cpu \
        chip-smoke \
        store trace life clean

# graftlint: AST-only jit-hygiene gate (no jax import, milliseconds).
# Exit 1 on any non-baselined finding; the tier-1 suite enforces the
# same gate.
lint:
	python -m pytorch_multiprocessing_distributed_tpu.analysis.lint

# graftcheck + graftmeter: jaxpr-level program auditor — collective
# budgets, donation/resharding/dtype audits, golden fingerprints —
# plus the committed cost/memory budgets (analysis/costs.json:
# FLOPs, bytes accessed, argument/output/temp HBM per canonical
# program), all in ONE pass (traces/compiles on the 8-device CPU
# mesh; never executes). Exit 1 on any drift; enforced in tier-1
# (tests/test_graftcheck.py).
check:
	$(PYTEST_ENV) python -m pytorch_multiprocessing_distributed_tpu.analysis.check

# refresh analysis/fingerprints.json AND analysis/costs.json after a
# DELIBERATE program change (review the JSON diffs in the PR; inline
# invariants still enforce)
check-update:
	$(PYTEST_ENV) python -m pytorch_multiprocessing_distributed_tpu.analysis.check --update

# graftfault: the deterministic fault matrix — every registered
# injection site swept (recover or fail fast, unaffected requests
# token-exact), plus checkpoint-corruption recovery and the SIGTERM
# preemption path. Seeded FaultPlans: the same faults hit the same
# operations on every run. Part of tier-1; this target runs it alone.
chaos:
	$(PYTEST_ENV) python -m pytest tests/test_graftfault.py tests/test_runtime_store.py -q

# graftrace: the concurrency gate alone — the GL119/120/121 static
# pass over the package (part of `make lint`, split out here) plus
# the deterministic-interleaving suite: pinned adversarial schedules
# over the real runtime objects (the PR-15 stale-worker canary,
# kill-vs-drain, journal close-vs-fsync), exhaustive small-schedule
# enumeration, and the realized-vs-static lock-graph audit.
trace:
	python -m pytorch_multiprocessing_distributed_tpu.analysis.lint
	$(PYTEST_ENV) python -m pytest tests/test_graftrace.py -q

# graftheal: the elastic-supervision suite (liveness gate, coordinated
# abort, supervised restart, graceful drain + redelivery journal) PLUS
# the slow-marked chaos soak — N requests under a background fault
# rate with one injected mid-run restart; every request completes
# token-exact or fails named, journal replay accounted.
soak:
	$(PYTEST_ENV) python -m pytest tests/test_graftheal.py -q

# graftscope: observability smoke — a synthetic engine run must emit a
# Perfetto-loadable Chrome trace, a JSONL event log with COMPLETE
# per-request lifecycles, and a parseable Prometheus text exposition
# (plus one live scrape of the /metrics endpoint). Schema drift fails
# here, not during an incident. Same body runs in tier-1
# (test_scope_smoke_end_to_end in tests/test_graftscope.py).
scope:
	$(PYTEST_ENV) python benchmarks/scope_smoke.py

# graftmeter: capacity/efficiency smoke — a registry canary must
# re-measure clean against the committed analysis/costs.json budgets,
# plan_capacity's slot prediction must match a real CPU-backend
# SlotPool allocation within 0.5%, a served engine with the HBM
# ledger armed must expose pmdt_hbm_* gauges on a live /metrics
# scrape, and the ledger must render to a breakdown PNG. Same body
# runs in tier-1 (test_meter_smoke_end_to_end in
# tests/test_graftmeter.py); the full 15-program budget gate is
# `make check`.
meter:
	$(PYTEST_ENV) python benchmarks/meter_smoke.py

# graftfleet: cross-host observability smoke — a synthetic 2-rank run
# over an in-process store must produce ONE merged per-rank timeline
# (a Chrome-trace lane per rank, clock-aligned), a straggler report
# NAMING the injected-slow rank with its arrival-skew percentiles,
# and a goodput fraction on a live /snapshot.json scrape. Same body
# runs in tier-1 (test_fleet_smoke_end_to_end in
# tests/test_graftfleet.py).
fleet:
	$(PYTEST_ENV) python benchmarks/fleet_smoke.py

# graftspec: speculative-decode smoke — the spec engine's greedy
# streams must be byte-identical to the non-speculative engine AND
# generate(), a repetitive stream must clear >1.0 accepted tokens per
# target-model step in FEWER dispatches, k=0 must run zero spec
# passes, and acceptance telemetry + goodput_spec_waste_s must ride
# the bus. Same body runs in tier-1 (test_spec_smoke_end_to_end in
# tests/test_graftspec.py).
spec:
	$(PYTEST_ENV) python benchmarks/spec_smoke.py

# graftquant: int8-KV smoke — the quantized engine's greedy streams
# (dense AND paged) must be byte-identical to the model-dtype engine
# at the head_dim=64 geometry, per_slot_kv_bytes must match a real
# int8 pool byte-for-byte with the bf16 ratio clearing 1.8x, the
# teacher-forced logit delta must be NONZERO and < 5e-3, and a
# quantized detached prefill must splice transcript-equal at < 0.6x
# the model-dtype payload. Same body runs in tier-1
# (test_quant_smoke_end_to_end in tests/test_graftquant.py).
quant:
	$(PYTEST_ENV) python benchmarks/quant_smoke.py

# graftzero: sharded-weight-update smoke — on a 2-shard CPU mesh the
# traced zero DP step must move grads as exactly ONE reduce-scatter +
# ONE all-gather with ZERO grad-sized psums (budget flip), the armed
# HBM ledger must show hbm_opt_state_bytes == the plan's per-chip
# shard bytes (~1/N, byte-exact vs plan_capacity(zero_shards=N)), a
# 3-step sharded trajectory must be BIT-identical to the replicated
# one, and a gather-on-save checkpoint must round-trip into a
# replicated run. Same body runs in tier-1
# (test_zero_smoke_end_to_end in tests/test_graftzero.py).
zero:
	$(PYTEST_ENV) python benchmarks/zero_smoke.py

# graftroute: disaggregated-fleet smoke — 2 paged replicas behind the
# router over an in-process MemStore must serve byte-identically to
# the single-engine baseline, survive one injected replica death by
# journal redelivery to the peer (fleet token count dedup-verified),
# route an identical prompt to the replica holding its cached pages
# (engine-level FULL hit, warm TTFT < cold), and publish the replica
# directory to the store. Same body runs in tier-1
# (test_route_smoke_end_to_end in tests/test_graftroute.py).
route:
	$(PYTEST_ENV) python benchmarks/route_smoke.py

# graftwire: socket-transport smoke — a router in THIS process drives
# 2 replica-server SUBPROCESSES over localhost: prefill->decode
# PageTransfer as raw framed numpy (bytes metered, clean drain, both
# children exit 0), then a SIGKILL -9 of the busiest replica process
# mid-run -> its WAL redelivers to the peer under original uids,
# every stream byte-identical to the in-process fleet, fleet token
# count dedup-verified. Same body runs in tier-1 (slow-marked
# test_wire_smoke_end_to_end in tests/test_graftwire.py).
wire:
	$(PYTEST_ENV) python benchmarks/wire_smoke.py

# graftscale: elastic-fleet smoke — spawn-from-zero, a traffic burst
# scaling REAL --listen replica subprocesses UP (sustained sheds ->
# supervised spawn + prefix prewarm before admission), an idle
# plateau draining them back DOWN (hysteresis + cooldown, children
# exit on their own), then a rolling v1->v2 weight rollout under
# continuous load: zero failed requests, every stream byte-exact to
# ONE version, every child pid reaped loudly at exit. Same body runs
# in tier-1 (slow-marked test_scale_smoke_script_end_to_end in
# tests/test_graftscale.py).
scale:
	$(PYTEST_ENV) python benchmarks/scale_smoke.py

# graftlife: the resource-lifecycle gate — the GL123-125 static pass
# over the package (part of `make lint`, split out here) plus the
# churny ownership-ledger soak: an autoscaled fleet under deadlines,
# withdraws, work stealing and one injected replica death must
# drain to an EMPTY ledger for every resource class (slots, pages,
# buffers, journal admits, transfers, sockets, threads, files), and
# every realized acquire site must be one the static model admits.
life:
	python -m pytorch_multiprocessing_distributed_tpu.analysis.lint
	$(PYTEST_ENV) python benchmarks/life_smoke.py

# full suite on the virtual 8-device CPU mesh (incl. slow e2e CLI runs)
test:
	$(PYTEST_ENV) python -m pytest tests/ -q

# fast suite (slow-marked e2e runs excluded)
test-fast:
	$(PYTEST_ENV) python -m pytest tests/ -q -m "not slow"

# the driver's multi-chip dry-run: full sharded train steps
# (dp/tp/zero1/fsdp/sp/zigzag/ulysses/moe/pp/1f1b/chunked-CE) on 8
# virtual devices
dryrun:
	python -c "import jax; jax.config.update('jax_platforms','cpu'); \
	import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

# one-JSON-line benchmark; fails (non-zero) without a TPU
bench:
	python bench.py

# rehearse the bench's control flow on the CPU: no device metric
bench-cpu:
	python bench.py --platform cpu

# the standing chip check (one TPU; `--chips 4` = the DP phase only)
chip-smoke:
	python chip_smoke.py

# the C++ TCP rendezvous store (ctypes-loaded on demand at runtime)
store:
	$(MAKE) -C csrc

clean:
	rm -rf csrc/build .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
