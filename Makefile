# Suite partitioning mirroring the reference's Makefile:17-75 CI jobs.
# Everything runs on a virtual 8-device CPU mesh — no TPU needed.

ENV = XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu
PYTEST = $(ENV) python -m pytest -q

.PHONY: chip_smoke test test_smoke test_core test_models test_parallel test_big_modeling \
        test_cli test_examples test_checkpointing test_hub test_tpu quality \
        telemetry-smoke warmup-smoke faulttol-smoke serving-smoke plan-smoke \
        reshard-smoke disagg-smoke chaos-smoke chaos-train-smoke publish-smoke \
        autoscale-smoke trace-smoke gameday-smoke sdc-smoke profile-smoke \
        fleet-smoke spec-smoke smoke-all

# Parallel across available cores (pytest-xdist): launched subprocess tests
# draw fresh rendezvous ports per gang (utils/other.py get_free_port), so
# workers never collide — the role of the reference's unique-port trick
# (test_utils/testing.py:810-820). Single-core boxes gain nothing from -n;
# the persistent XLA compile cache was tried for them and reverted (see
# tests/conftest.py: ring-attention executables SIGABRT on cache replay).
test:
	$(PYTEST) -n auto tests/

test_serial:
	$(PYTEST) tests/

# Smoke tier (<10 min serial on one core): one representative file per
# subsystem — runtime/mesh, collectives, data, training loop, flagship model,
# generation, checkpoint roundtrip, review regressions. The full suite is the
# bar; this is the budget-constrained pre-commit gate.
test_smoke:
	$(PYTEST) tests/test_state_and_mesh.py tests/test_operations.py \
	    tests/test_training.py tests/test_llama.py tests/test_megatron.py \
	    tests/test_review_regressions.py

# Runtime + ops + data + training loop (excludes models/examples/big-model).
test_core:
	$(PYTEST) tests/test_state_and_mesh.py tests/test_operations.py \
	    tests/test_data_loader.py tests/test_training.py tests/test_zero.py \
	    tests/test_local_sgd.py tests/test_tracking.py tests/test_native.py

test_models:
	$(PYTEST) tests/test_llama.py tests/test_bert.py tests/test_gpt2.py \
	    tests/test_t5.py tests/test_moe.py tests/test_opt.py tests/test_neox.py \
	    tests/test_vit.py tests/test_resnet.py tests/test_whisper.py \
	    tests/test_generation.py

test_parallel:
	$(PYTEST) tests/test_pp.py tests/test_attention.py tests/test_inference.py \
	    tests/test_fp8.py tests/test_quantization.py

test_big_modeling:
	$(PYTEST) tests/test_big_modeling.py

test_checkpointing:
	$(PYTEST) tests/test_checkpointing.py

test_cli:
	$(PYTEST) tests/test_cli.py

test_examples:
	$(PYTEST) tests/test_examples.py

test_hub:
	$(PYTEST) tests/test_hub.py

# The chip targets need a TPU host and take the chip: run them one at a time,
# each as one process. Off-chip they fail; they never fall back to the CPU.
# chip_smoke: the whole main path once, at full width (~1.5 min cold on a v5e).
chip_smoke:
	python chip_smoke.py

# TPU kernel tier: compiled-mode Pallas/fp8/int8/train-step health (~1 min).
# With ACCELERATE_TEST_USE_TPU=1 a missing chip fails the tier, it does not skip.
test_tpu:
	ACCELERATE_TEST_USE_TPU=1 python -m pytest -q -rs tests/tpu/

# Observability gate: 20-step toy loop with telemetry on, then assert the
# per-rank JSONL report is well-formed (schema, recompile counting, summary
# percentiles). Seconds on the CPU mesh; see docs/usage_guides/observability.md.
telemetry-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.telemetry_smoke

# Compile-manager gate: ragged toy loop (8 raw shapes) under pow2 bucketing
# compiles <= 4 executables; a restart warms every shapes-manifest signature
# before step 0 and telemetry reports 0 recompiles afterwards. See
# docs/usage_guides/performance.md "Taming recompiles".
warmup-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.warmup_smoke

# Continuous-batching gate: 32 mixed-length requests through a tiny Llama on
# the CPU mesh must all complete with continuations bit-equal to static
# generate(), keep the decode steady state at ONE executable (zero
# post-warmup recompiles), and beat static-batch generate()'s aggregate
# tokens/s on the same request set. See docs/usage_guides/serving.md.
serving-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.serving_smoke

# Disaggregated-serving gate: an open-loop Poisson trace of mixed-length
# requests replays through the colocated engine and through the two-mesh
# router (planner-sized prefill/decode slices on the 8-device CPU mesh,
# streamed KV-page handoff). All requests must complete with rows bit-equal
# between the paths, the disagg decode steady state must stay ONE executable
# (zero post-warmup recompiles), the stats block must report real handoff
# traffic, and disagg p95 TTFT must be STRICTLY lower than colocated at the
# same offered load. See docs/usage_guides/serving.md "Disaggregated serving".
disagg-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.disagg_smoke

# Serving-under-fire gate: a 32-request Poisson trace (tick-driven, fully
# deterministic) replays through the disagg engine fault-free and twice under
# an identical FaultInjector spec (one dead prefill lane, a poisoned KV page,
# rate-driven handoff transfer errors). No hang (idle-tick guard armed),
# every request ends with an explicit status, ok rows are bit-equal to the
# fault-free run, decode stays ONE executable with 0 steady recompiles, chaos
# p95 TTFT stays within 5x fault-free, and the second chaos run reproduces
# the first's fault schedule/statuses/rows exactly. See
# docs/usage_guides/serving.md "Serving under faults".
chaos-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.chaos_smoke

# Request-tracing gate: a seeded 24-request chaos trace through the disagg
# engine with a TraceRecorder attached. Every poll() row carries a complete
# span tree, explain()'s critical-path terms sum to the measured TTFT,
# the exported Chrome trace parses with cross-lane KV-handoff flow events,
# a second seeded run replays a bit-identical tick-domain trace, decode
# stays ONE executable with 0 steady recompiles, and throughput stays
# within 5% of tracing-off. See docs/usage_guides/observability.md
# "Tracing a request".
trace-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.trace_smoke

# Training-under-fire gate: a 10-step toy loop replays one seeded chaos
# schedule twice (torn checkpoint write -> save retry, two nonfinite_grad
# steps -> sentinel rollback, a slow_step straggler -> watchdog
# training_stalled event naming the rank). Both chaos runs must draw a
# bit-identical fault log, the chaos final loss must be bit-equal to a
# fault-free run (rollback restored exact state + data order), and the
# telemetry recompile counter must not move after the two-step warmup —
# including across the rollback replay. See
# docs/usage_guides/fault_tolerance.md "Training under fire".
chaos-train-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.chaos_train_smoke

# Weight-publication gate: a training run commits verified checkpoints
# (steps 3 and 5) while a live engine drains a deterministic Poisson trace
# in the same process; the WeightPublisher hot-swaps both — a canary
# promote, then a seeded canary_window/slo_regression rollback that stays
# quarantined. Zero dropped/shed/failed requests across both swaps, ONE
# decode executable with 0 steady recompiles, version tags flip only
# post-swap (v0 rows bit-equal to a publish-free reference), the
# post-rollback probe is bit-equal to loading checkpoint 3 directly, and a
# second seeded run replays the whole thing bit-identically. See
# docs/usage_guides/serving.md "Continuous deployment".
publish-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.publish_smoke

# Crash-durability game day: the ENTIRE serving stack — train gang
# committing a verified checkpoint, journaled disagg engine with autoscaler
# and tracing attached, WeightPublisher — under one seeded chaos schedule
# that tears a journal append and then hard-kills the engine (os._exit 78)
# mid-trace. The parent plays supervisor (classify_exit -> "serving-crash"
# -> zero-backoff relaunch); the resumed child recovers the write-ahead
# journal: every request reaches an explicit terminal status exactly once
# (cached pre-crash completions never re-execute, in-flight rows replay
# bit-equal to an uninterrupted reference), the publisher still promotes
# post-recovery, decode stays ONE executable, and a second seeded round
# replays bit-identically. See docs/usage_guides/serving.md
# "Surviving engine crashes".
gameday-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.gameday_smoke

# Elastic-serving gate: a seeded diurnal trace (10x rate swing, shifting
# prompt:decode mix) replays through a disagg engine that starts on half
# the mesh with an AutoscaleController polling every tick; mid-trace a
# device is reported dead. Every request must end ok, every row bit-equal
# to a fixed 8-device reference, the controller must grow AND shrink-on-
# death within a bounded resize count, the injected flap must be damped
# (no resize), decode keeps 0 steady recompiles across every layout, p95
# TTFT holds the smoke SLO on both load plateaus, and a second seeded run
# replays decisions/faults/rows bit-identically. See
# docs/usage_guides/serving.md "Autoscaling".
autoscale-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.autoscale_smoke

# Auto-parallelism gate: plan a tiny Llama on the 8-device CPU mesh —
# search must be deterministic (byte-identical JSON), every candidate must
# satisfy the divisibility constraints, 10 training steps run under the
# chosen layout with measured peak HBM within 2x of the prediction, and a
# second run loads the cached plan (no re-search) and records calibration
# deltas into it. See docs/usage_guides/auto_parallelism.md.
plan-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.plan_smoke

# Fault-tolerance gate: SIGTERM a training worker mid-epoch (preemption
# auto-save + resumable exit code), relaunch with ACCELERATE_RESTART_ATTEMPT=1
# and assert the resumed step equals the preemption-save step and the final
# loss matches an uninterrupted run. See docs/usage_guides/fault_tolerance.md.
faulttol-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.faulttol_smoke

# Elastic-resharding gate: preempt a 4-way training worker, then resume its
# checkpoint on 2-way AND 8-way meshes with ACCELERATE_RESTART_ATTEMPT=1.
# Each resume must restore through the planned collective schedule (no
# host-staged leaves within the staging budget), report the telemetry
# `reshard` block, and finish with the uninterrupted run's final loss. See
# docs/usage_guides/elastic_resharding.md. (The driver pins each child's
# device count itself, so this target sets no XLA_FLAGS.)
reshard-smoke:
	JAX_PLATFORMS=cpu python -m accelerate_tpu.test_utils.scripts.reshard_smoke

# Silent-data-corruption gate: the sdc.py sentinel end to end. A 4-rank
# gloo gang draws a transient train_step/bit_flip on a vote tick — the
# cross-replica integrity vote isolates the outlier, the redundant-compute
# probe on the cached golden batch clears the silicon, and the majority
# broadcast repairs in place (final loss bit-equal to a fault-free
# reference, jit cache flat). A 2-rank gang draws the same flip sticky —
# the probe reproduces it, the convicted rank quarantines itself on disk
# and exits 79, classify_exit maps it to "sdc", and GangSupervisor orders
# the zero-backoff SHRUNK relaunch that resumes from the newest verified
# checkpoint with the host still excluded. A decode canary (known prompt,
# pinned RNG, journal/poll-invisible) catches an injected decode_tick
# bit_flip and shrinks the engine around the device via mark_device_dead.
# Every leg replays bit-identically on its second seeded round. See
# docs/usage_guides/fault_tolerance.md "Silent data corruption".
sdc-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.sdc_smoke

# Device-time attribution + flight-recorder gate: a dp-sharded train run
# and a chaos-seeded serving replay with the profiler on must emit
# exactly-summing attribution terms (5% bar), an overlap ratio, per-axis
# bandwidth residuals, and a flat jit cache; a hard-killed child (rc 78)
# and an SDC-convicted gang rank (rc 79) must each leave a readable
# flight_<exit_class>.json whose newest ring entries identify the dying
# tick/step. See docs/usage_guides/observability.md.
profile-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.profile_smoke

# Whole-cell-loss game day: a FleetRouter over two journaled cells drains a
# seeded Poisson trace; chaos partitions cell 0 (terminals pile up
# journaled but unreported) then hard-kills it mid-trace. The router adopts
# the dead cell's journal and drains it onto the survivor — cached
# terminals re-emit without re-executing, in-flight requests resubmit by
# client_request_id — with every request ok exactly once, rows bit-equal
# to an uninterrupted reference, the survivor executing exactly N minus
# what the dead cell already ran, 1 decode executable / 0 steady
# recompiles per survivor, and scale_up + a cell-granular publish canary
# promoting fleet-wide afterwards. A second seeded round replays
# bit-identically. See docs/usage_guides/serving.md "Fleet serving".
fleet-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.fleet_smoke

# Speculative-decoding + quantized-KV gate: a seeded 24-request trace runs
# non-speculative, speculative (n-gram self-draft, k=4 verified in ONE
# batched forward), int8-KV colocated, and int8-KV disagg with speculation
# on. Speculative greedy rows must be BIT-EQUAL to the reference (exact
# rejection sampling), decode must stay ONE executable with 0 steady
# recompiles with speculation AND int8 KV enabled, int8 disagg rows must be
# bit-equal to int8 colocated (lossless quantized handoff) with the byte
# accounting showing >= 40% handoff savings, and int8 output must stay
# within the documented cross-dtype tolerance of the float reference. See
# docs/usage_guides/serving.md "Speculative decoding".
spec-smoke:
	$(ENV) python -m accelerate_tpu.test_utils.scripts.spec_smoke

# Every acceptance gate back to back with a one-line pass/fail table and a
# nonzero exit if any gate failed. Serial on purpose: the gates share the
# CPU cores and several launch their own subprocess gangs.
SMOKES = telemetry warmup serving plan reshard disagg chaos chaos-train \
         publish autoscale trace faulttol gameday sdc profile fleet spec
smoke-all:
	@fail=0; \
	for s in $(SMOKES); do \
	    start=$$(date +%s); \
	    if $(MAKE) -s $$s-smoke >/tmp/smoke_$$s.log 2>&1; then \
	        printf 'PASS  %-14s %4ss\n' $$s $$(( $$(date +%s) - start )); \
	    else \
	        printf 'FAIL  %-14s %4ss  (tail: /tmp/smoke_%s.log)\n' \
	            $$s $$(( $$(date +%s) - start )) $$s; \
	        fail=1; \
	    fi; \
	done; \
	exit $$fail
