"""`make profile-smoke`: the device-time attribution + flight-recorder
acceptance gate on the 8-device virtual CPU mesh.

Four legs, all seeded and deterministic:

1. **Train attribution.** A tiny Llama trains under an auto-parallelism
   plan pinned to a dp-sharded layout with ``TelemetryKwargs(profile=True)``.
   Every finalized step record's terms (device compute, exposed comm, data
   wait, straggler skew, dispatch residual) sum to its measured wall within
   the 5% tolerance (exact by construction — the bar catches emission
   bugs); the comm/compute overlap ratio is emitted; per-axis achieved
   bandwidth lands in ``summary()["profile"]["bandwidth_residuals"]`` as
   residuals against the plan's BandwidthTable; ``cost_analysis()`` capture
   succeeds; and the telemetry JSONL's cumulative recompile counter stays
   FLAT across the profiled run (the AOT cost capture must not touch the
   jit dispatch cache).
2. **Serving tick attribution + replay.** A chaos-seeded disagg replay with
   the profiler on: every tick record's sections (admit, prefill, decode,
   host fetch, bookkeeping residual) sum to the tick wall; the fused
   device_get shows up as ``host_fetch_s``; decode stays ONE executable
   with zero steady recompiles (the profiler's timers are host-side only);
   the serving-availability SLO burn rate renders from the MetricsHub; the
   legacy metric names still render as aliases; and a second identically
   seeded run produces bit-identical rows and fault log.
3. **Hard-kill game day (rc 78).** A child serving process dies through an
   injected ``engine_crash`` with ``$ACCELERATE_FLIGHT_DIR`` set: the
   parent asserts the readable ``flight_serving-crash.json`` whose newest
   ring entries identify the dying tick and whose gauges carry the chaos
   schedule and jit-cache census.
4. **SDC quarantine game day (rc 79).** A 2-rank gang draws a sticky
   bit_flip; the convicted rank exits ``SDC_EXIT_CODE`` leaving
   ``flight_sdc.json`` whose newest step entries identify the poisoned
   step; the peer exits clean.

The child processes are this same file with ``--mode=crash|sdcworker``.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

SEQ, BATCH, TRAIN_STEPS = 64, 8, 12
TERM_TOL = 0.05  # the ProfilerConfig default the smoke re-derives

N_REQS = 12
N_SLOTS = 6
N_LANES = 2
SERVE_CHAOS_SEED = 13
MAX_TICKS = 20_000

CRASH_TICK = 6
CRASH_CHAOS_SEED = 23

SDC_VOTE_EVERY = 2
SDC_FLIP_TICK = 4  # must land on a vote tick (tick % VOTE_EVERY == 0)
SDC_TOTAL_STEPS = 8
SDC_CHAOS_SEED = 7
CHILD_TIMEOUT_S = 420.0


# ---------------------------------------------------------------------------
# Leg 1+2 helpers (parent process)
# ---------------------------------------------------------------------------


def _assert_identity(rec, kind):
    terms = rec["terms"]
    total = sum(terms.values())
    wall = rec["wall_s"]
    assert abs(total - wall) <= max(1e-8, TERM_TOL * wall), (
        f"{kind} {rec.get(kind)}: terms sum {total} != wall {wall} "
        f"(> {TERM_TOL:.0%})")
    for name, v in terms.items():
        if name not in ("dispatch_s", "bookkeeping_s"):
            assert v >= 0.0, f"{kind} term {name} negative: {v}"


def _train_leg(acc, module, model_ids):
    import jax
    import optax

    from accelerate_tpu import Model
    from accelerate_tpu.models import cross_entropy_loss

    model = Model.from_flax(module, jax.random.key(0), model_ids)
    model, _ = acc.prepare(model, optax.adamw(1e-3))

    def loss_fn(params, batch):
        logits = model.module.apply({"params": params}, batch["input_ids"])
        return cross_entropy_loss(logits, batch["labels"])

    step = acc.prepare_train_step(loss_fn)
    state = acc.train_state
    rng = np.random.default_rng(0)
    for _ in range(TRAIN_STEPS):
        batch = {
            "input_ids": rng.integers(0, 255, (BATCH, SEQ)).astype(np.int32),
            "labels": rng.integers(0, 255, (BATCH, SEQ)).astype(np.int32),
        }
        state, _ = step(state, batch)

    prof = acc.telemetry.profiler
    assert prof is not None, "TelemetryKwargs(profile=True) built no profiler"
    prof.flush()  # finalize the lagged last step
    recs = [r for r in prof.records() if r["kind"] == "step"]
    assert len(recs) == TRAIN_STEPS, (len(recs), TRAIN_STEPS)
    for r in recs:
        _assert_identity(r, "step")
    summary = prof.summary()
    assert summary["steps"] == TRAIN_STEPS, summary
    assert summary["cost_captured"] is True, (
        "cost_analysis() capture failed on the CPU backend")
    assert summary["overlap_ratio_mean"] is not None, (
        "no overlap ratio for a dp-sharded step")
    assert 0.0 <= summary["overlap_ratio_mean"] <= 1.0, summary
    bw = summary["bandwidth_residuals"]
    assert bw, "no per-axis bandwidth residuals despite an active plan"
    for axis, agg in bw.items():
        assert agg["predicted_gbps"] > 0, (axis, agg)
        assert agg["residual_mean"] > 0, (axis, agg)
        assert agg["samples"] > 0, (axis, agg)
    # Per-record: the comm split and overlap made it into the ring entries.
    with_overlap = [r for r in recs if r["overlap_ratio"] is not None]
    assert with_overlap, "no step record carries an overlap ratio"
    assert any(r["comm_axes_s"] for r in recs), "no per-axis comm split"
    # The hub renders the profile block under the pinned scheme.
    names = acc.telemetry.hub.metric_names()
    assert "accelerate_tpu_profile_steps" in names, sorted(names)[:20]
    assert "accelerate_tpu_telemetry_steps" in names, sorted(names)[:20]
    return summary


def _serve_workload(cfg_vocab):
    rng = np.random.default_rng(11)
    lengths = [int(rng.integers(5, 15)) for _ in range(N_REQS)]
    budgets = [int(rng.integers(4, 9)) for _ in range(N_REQS)]
    prompts = [rng.integers(1, cfg_vocab, (n,)).astype(np.int32)
               for n in lengths]
    arrivals = np.floor(np.cumsum(
        rng.exponential(2.0, size=N_REQS))).astype(int).tolist()
    return prompts, budgets, arrivals


def _serve_replay(eng, prompts, budgets, arrivals):
    ids, results = {}, {}
    nxt = t = 0
    while nxt < N_REQS or eng.pending:
        while nxt < N_REQS and arrivals[nxt] <= t:
            ids[nxt] = eng.submit(prompts[nxt], max_new_tokens=budgets[nxt])
            nxt += 1
        eng.tick()
        for r in eng.poll():
            results[r["id"]] = r
        t += 1
        assert t < MAX_TICKS, "serve replay backstop tripped"
    rows = [results[ids[i]] for i in range(N_REQS)]
    return [(r["status"], np.asarray(r["tokens"]).tolist())
            for r in rows], eng.stats()


def _serving_leg(acc, module, probe):
    import jax
    import jax.numpy as jnp  # noqa: F401  (device backend already up)

    from accelerate_tpu import (
        DisaggConfig,
        DisaggServingEngine,
        FaultInjector,
        Model,
        ServingConfig,
    )

    cfg = module.config
    prompts, budgets, arrivals = _serve_workload(cfg.vocab_size)
    sc = ServingConfig(n_slots=N_SLOTS, max_len=96, prefill_chunks=[16],
                      temperature=0.0, seed=0, max_retries=3,
                      max_idle_ticks=200)
    dc = DisaggConfig(n_prefill_lanes=N_LANES, handoff_retries=3)
    prof = acc.telemetry.profiler

    def run():
        model = Model.from_flax(module, jax.random.key(0), probe)
        eng = DisaggServingEngine(model, sc, disagg=dc,
                                  telemetry=acc.telemetry)
        eng.warmup()  # reset_metrics re-zeroes the tick clock AND the ring
        eng.chaos = FaultInjector(
            seed=SERVE_CHAOS_SEED,
            rates={"handoff_device_put": {"transfer_error": 0.25}},
        )
        rows, stats = _serve_replay(eng, prompts, budgets, arrivals)
        return rows, stats, list(eng.chaos.injected)

    rows1, stats1, log1 = run()
    prof.flush()
    ticks = [r for r in prof.records() if r["kind"] == "tick"]
    assert ticks, "no tick attribution records"
    for r in ticks:
        _assert_identity(r, "tick")
    assert any(r["terms"]["host_fetch_s"] > 0 for r in ticks), (
        "the fused device_get never showed up as host_fetch_s")
    assert any(r["terms"]["decode_s"] > 0 for r in ticks), ticks[-1]
    # Zero-device-sync + flat-cache contract: the profiled replay keeps the
    # one-executable decode census and zero steady-state recompiles.
    assert stats1["decode_executables"] == 1, stats1["decode_executables"]
    assert stats1["steady_recompiles"] == 0, stats1["steady_recompiles"]
    assert stats1["faults"]["injected"] > 0, "seeded chaos injected nothing"
    summary = prof.summary()
    assert summary["ticks"] >= len(ticks), summary
    assert summary["tick_terms_mean_s"], summary

    # MetricsHub: SLO burn rate + alias rendering from the ONE renderer.
    hub = acc.telemetry.hub
    burn = hub.burn_rates()
    assert "serving_availability" in burn, burn
    assert burn["serving_availability"]["events"] > 0, burn
    names = hub.metric_names()
    for required in (
        "accelerate_tpu_slo_serving_availability_burn_rate",
        "accelerate_tpu_serving_ticks",
        "accelerate_tpu_tracing_spans_total",
    ):
        assert required in names, (required, sorted(names)[:30])
    assert acc.telemetry.tracing.metrics_text() == hub.render(), (
        "TraceRecorder.metrics_text() is not delegating to the hub")

    # Seeded replay with the profiler ON is bit-identical.
    rows2, stats2, log2 = run()
    assert rows1 == rows2, "profiled replay diverged between seeded runs"
    assert log1 == log2, "chaos schedule diverged between seeded runs"
    return {"ticks": len(ticks), "injected": stats1["faults"]["injected"]}


# ---------------------------------------------------------------------------
# Leg 3 child: injected engine_crash -> rc 78 + flight bundle
# ---------------------------------------------------------------------------


def crash_child(project_dir):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import (
        Accelerator,
        DisaggConfig,
        DisaggServingEngine,
        FaultInjector,
        Model,
        ServingConfig,
    )
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.utils import TelemetryKwargs, set_seed

    set_seed(0)
    acc = Accelerator(
        project_dir=project_dir,
        kwargs_handlers=[TelemetryKwargs(tracing=True, profile=True,
                                         log_every=0)],
    )
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8),
                                              dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), probe)
    chaos = FaultInjector(seed=CRASH_CHAOS_SEED, schedule=[
        {"point": "engine_crash", "kind": "crash", "tick": CRASH_TICK}])
    eng = DisaggServingEngine(
        model,
        ServingConfig(n_slots=4, max_len=64, prefill_chunks=[8],
                      temperature=0.0, seed=0),
        disagg=DisaggConfig(n_prefill_lanes=2),
        telemetry=acc.telemetry, chaos=chaos,
    )
    rng = np.random.default_rng(7)
    for _ in range(6):
        eng.submit(rng.integers(1, 256, (6,), dtype=np.int32),
                   max_new_tokens=16)
    for _ in range(200):
        eng.tick()  # dies inside this call at CRASH_TICK
        eng.poll()
    raise AssertionError("the scheduled engine_crash never fired")


# ---------------------------------------------------------------------------
# Leg 4 child: one gang rank drawing a sticky bit_flip -> rc 79 on rank 0
# ---------------------------------------------------------------------------


def sdc_worker(project_dir, status_file):
    import jax
    import optax
    import flax.linen as nn

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.utils import (
        FaultToleranceKwargs,
        ProjectConfiguration,
        TelemetryKwargs,
        set_seed,
    )

    set_seed(0)

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(16)(x)
            x = nn.relu(x)
            return nn.Dense(1)(x)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = x.sum(-1, keepdims=True).astype(np.float32)

    class Dataset:
        def __len__(self):
            return len(x)

        def __getitem__(self, i):
            return {"x": x[i], "y": y[i]}

    class Spec:
        dataset = Dataset()
        batch_size = 16
        sampler = None
        drop_last = False

    acc = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=project_dir, automatic_checkpoint_naming=True),
        kwargs_handlers=[
            FaultToleranceKwargs(
                sentinel="warn",
                chaos=dict(seed=SDC_CHAOS_SEED, schedule=[
                    {"point": "train_step", "kind": "bit_flip",
                     "tick": SDC_FLIP_TICK, "unit": 0, "mode": "sticky"}]),
                sdc=dict(vote_every=SDC_VOTE_EVERY, repair="rollback"),
            ),
            TelemetryKwargs(log_every=0, profile=True),
        ],
    )
    print(f"SDC_RANK {acc.process_index}/{acc.num_processes}", flush=True)
    module = Net()
    model = Model.from_flax(module, jax.random.key(0), x[:1])
    model, _, dl = acc.prepare(model, optax.adam(1e-2), Spec())

    def loss_fn(params, batch):
        import jax.numpy as jnp

        pred = module.apply({"params": params}, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    step = acc.prepare_train_step(loss_fn)
    state = acc.train_state
    ft = acc.fault_tolerance
    done = 0
    while done < SDC_TOTAL_STEPS:
        for batch in dl:
            state, _ = step(state, batch)
            # Rank 0 convicts inside step's observe path (exits 79); the
            # peer sees the conviction and leaves the loop cleanly.
            if ft.sdc is not None and ft.sdc.peer_quarantined:
                with open(status_file, "w") as f:
                    json.dump({"rank": acc.process_index,
                               "peer_quarantined": True}, f)
                print("SDC_PEER_QUARANTINED", flush=True)
                os._exit(0)  # coordinator died with the convicted rank
            done = int(np.asarray(state.step))
            if done >= SDC_TOTAL_STEPS:
                break
    raise AssertionError("the sticky flip never convicted a rank")


# ---------------------------------------------------------------------------
# Parent-side child plumbing
# ---------------------------------------------------------------------------


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def _child_env(n_devices, flight_dir):
    env = {**os.environ}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH"), _repo_root(), os.getcwd()) if p)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["ACCELERATE_FLIGHT_DIR"] = flight_dir
    for k in ("ACCELERATE_COORDINATOR_ADDRESS", "ACCELERATE_NUM_PROCESSES",
              "ACCELERATE_PROCESS_INDEX", "ACCELERATE_LOCAL_PROCESS_INDEX",
              "ACCELERATE_RESTART_ATTEMPT"):
        env.pop(k, None)
    return env


def _wait(proc, log_path, want_rc, what):
    try:
        rc = proc.wait(timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        proc.kill()
        rc = -9
    if rc != want_rc:
        with open(log_path) as f:
            sys.stderr.write(f.read()[-4000:])
        raise AssertionError(f"{what}: rc={rc}, want {want_rc}")
    return rc


def _load_flight(flight_dir, exit_class):
    path = os.path.join(flight_dir, f"flight_{exit_class}.json")
    assert os.path.exists(path), (
        f"no flight bundle at {path}: {os.listdir(flight_dir)}")
    with open(path) as f:
        doc = json.load(f)
    assert doc["exit_class"] == exit_class, doc["exit_class"]
    assert doc["entries"], "flight ring is empty"
    return doc, path


def _crash_leg(tmp):
    from accelerate_tpu.utils.constants import SERVING_CRASH_EXIT_CODE

    flight_dir = os.path.join(tmp, "flight78")
    project = os.path.join(tmp, "crash_run")
    os.makedirs(flight_dir, exist_ok=True)
    log_path = os.path.join(tmp, "crash.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--mode=crash",
             f"--project-dir={project}"],
            stdout=log, stderr=subprocess.STDOUT,
            env=_child_env(8, flight_dir))
        _wait(proc, log_path, SERVING_CRASH_EXIT_CODE, "crash child")
    doc, path = _load_flight(flight_dir, "serving-crash")
    assert "engine_crash" in (doc["reason"] or ""), doc["reason"]
    tick_entries = [e for e in doc["entries"] if e["kind"] == "tick"]
    assert tick_entries, "no tick attribution in the crash bundle"
    last_tick = tick_entries[-1]["tick"]
    assert last_tick >= CRASH_TICK - 2, (
        f"newest ring tick {last_tick} does not identify the dying tick "
        f"(crash at {CRASH_TICK})")
    for e in tick_entries:
        _assert_identity(e, "tick")
    gauges = doc["gauges"]
    assert gauges.get("jit_cache"), gauges
    chaos_gauge = gauges.get("chaos")
    assert chaos_gauge and chaos_gauge.get("injected", 0) >= 1, gauges
    assert doc.get("recent_spans"), "tracing spans missing from the bundle"
    return {"path": path, "last_tick": last_tick,
            "ring": len(doc["entries"])}


def _sdc_leg(tmp):
    from accelerate_tpu.utils.constants import SDC_EXIT_CODE

    flight_dir = os.path.join(tmp, "flight79")
    project = os.path.join(tmp, "sdc_run")
    os.makedirs(flight_dir, exist_ok=True)
    os.makedirs(project, exist_ok=True)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for i in range(2):
        env = _child_env(4, flight_dir)
        env.update(
            ACCELERATE_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            ACCELERATE_NUM_PROCESSES="2",
            ACCELERATE_PROCESS_INDEX=str(i),
            ACCELERATE_LOCAL_PROCESS_INDEX=str(i),
        )
        log_path = os.path.join(tmp, f"sdc_rank_{i}.log")
        log = open(log_path, "w")
        procs.append((subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--mode=sdcworker",
             f"--project-dir={project}",
             f"--status-file={os.path.join(project, f'status_{i}.json')}"],
            stdout=log, stderr=subprocess.STDOUT, env=env), log, log_path))
    rcs = []
    for i, (p, log, log_path) in enumerate(procs):
        want = SDC_EXIT_CODE if i == 0 else 0  # the flip targets rank 0
        rcs.append(_wait(p, log_path, want, f"sdc rank {i}"))
        log.close()
    doc, path = _load_flight(flight_dir, "sdc")
    assert "sticky SDC conviction" in (doc["reason"] or ""), doc["reason"]
    step_entries = [e for e in doc["entries"] if e["kind"] == "step"]
    assert step_entries, "no step attribution in the sdc bundle"
    last_step = step_entries[-1]["step"]
    assert last_step >= SDC_FLIP_TICK - 1, (
        f"newest ring step {last_step} does not identify the poisoned "
        f"step (flip at {SDC_FLIP_TICK})")
    return {"path": path, "last_step": last_step, "exit_codes": rcs}


# ---------------------------------------------------------------------------
# Parent
# ---------------------------------------------------------------------------


def main() -> int:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.utils import AutoPlanKwargs, TelemetryKwargs, set_seed

    if len(jax.devices()) < 8:
        raise SystemExit(
            "profile-smoke needs the 8-device mesh; run via "
            "`make profile-smoke` (XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8)")

    tmp = tempfile.mkdtemp(prefix="profile_smoke_")
    set_seed(0)
    acc = Accelerator(
        parallelism_config="auto",
        project_dir=tmp,
        kwargs_handlers=[
            AutoPlanKwargs(hbm_gib=16.0, seq=SEQ, per_chip_batch=BATCH // 8,
                           pinned={"dp_shard": 8}, calibrate_after=0),
            TelemetryKwargs(log_every=0, sync_timing=True,
                            straggler_probe_every=5, profile=True,
                            tracing=True),
        ],
    )
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    module = LlamaForCausalLM(cfg)
    ids = np.zeros((BATCH, SEQ), np.int32)

    train_summary = _train_leg(acc, module, ids)
    print(json.dumps({"row": "train", **{
        k: train_summary[k] for k in
        ("steps", "cost_captured", "overlap_ratio_mean")}}), flush=True)

    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8),
                                              dtype=np.int32)
    serve_row = _serving_leg(acc, module, probe)
    print(json.dumps({"row": "serve", **serve_row}), flush=True)

    acc.end_training()
    # Flat jit cache across the profiled train run: the cumulative
    # recompile counter in the telemetry JSONL must not move after the
    # first step's compile (AOT cost capture bypasses the dispatch cache).
    jsonl = os.path.join(tmp, "telemetry", f"rank_{acc.process_index}.jsonl")
    with open(jsonl) as fh:
        records = [json.loads(ln) for ln in fh]
    steps = [r for r in records if r["event"] == "step"]
    assert len(steps) == TRAIN_STEPS, len(steps)
    # Baseline at step 2: the watchdog observes the first step's own
    # compile one record late; after that the counter must not move.
    assert steps[-1]["recompiles"] == steps[1]["recompiles"], (
        f"jit cache grew across the profiled run: "
        f"{steps[1]['recompiles']} -> {steps[-1]['recompiles']}")
    summary_rec = records[-1]
    assert summary_rec["event"] == "summary" and "profile" in summary_rec, (
        "telemetry summary lost the profile block")

    crash_row = _crash_leg(tmp)
    print(json.dumps({"row": "crash78", **crash_row}), flush=True)

    sdc_row = _sdc_leg(tmp)
    print(json.dumps({"row": "sdc79", **sdc_row}), flush=True)

    print(json.dumps({
        "row": "ok",
        "train_steps": train_summary["steps"],
        "overlap_ratio_mean": train_summary["overlap_ratio_mean"],
        "bandwidth_axes": sorted(train_summary["bandwidth_residuals"]),
        "serve_ticks": serve_row["ticks"],
        "flight_bundles": [crash_row["path"], sdc_row["path"]],
    }), flush=True)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="parent",
                    choices=["parent", "crash", "sdcworker"])
    ap.add_argument("--project-dir", default=None)
    ap.add_argument("--status-file", default=None)
    ns = ap.parse_args()
    if ns.mode == "crash":
        sys.exit(crash_child(ns.project_dir))
    elif ns.mode == "sdcworker":
        sys.exit(sdc_worker(ns.project_dir, ns.status_file))
    sys.exit(main())
