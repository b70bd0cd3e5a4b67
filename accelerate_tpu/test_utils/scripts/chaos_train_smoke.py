"""`make chaos-train-smoke`: training under fire on the virtual CPU mesh.

Acceptance shape of the training-side chaos pillar end to end
(fault_tolerance.py + chaos.py):

1. A fault-free reference worker trains ``TOTAL_STEPS`` and records its
   final loss.
2. A chaos worker runs the SAME training with a seeded fault schedule:
   a ``torn_write`` on the first checkpoint save attempt (the save must
   retry and commit), two consecutive ``nonfinite_grad`` steps (the
   divergence sentinel must trip and roll back to the committed
   checkpoint), and a ``slow_step`` straggler (the step watchdog must emit
   a ``training_stalled`` event naming the rank within its warn deadline).
3. A second chaos worker replays the IDENTICAL seed/schedule; the smoke
   asserts both chaos runs drew a bit-identical fault log, and that the
   chaos final loss equals the fault-free reference bit-for-bit — the
   rollback restored the exact pre-fault state and replayed the exact data
   order, and ``nonfinite_grad`` poisons only the sentinel's metrics,
   never the model state.
4. Zero steady-state recompiles: the telemetry recompile counter after
   step 2 (on a mesh the second call may compile once more, when the state
   comes back in GSPMD's shardings — see telemetry.py) equals the final
   count, across the rollback replay.

The worker subprocess is this same file with ``--worker``.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

TOTAL_STEPS = 10
SAVE_AT = 2  # mid-epoch: the rollback also exercises mid-epoch data resume
CHAOS_SEED = 7
# Ticks are monotonic observe counts (step K is tick K-1 until a rollback).
CHAOS_SCHEDULE = [
    # First save attempt tears; the retry (attempt 1) must commit clean.
    {"point": "checkpoint_save", "kind": "torn_write", "tick": 0, "unit": 0},
    # Two consecutive poisoned sentinel samples = sentinel_window -> rollback.
    {"point": "train_step", "kind": "nonfinite_grad", "tick": 5},
    {"point": "train_step", "kind": "nonfinite_grad", "tick": 6},
    # A straggling step during the post-rollback replay; > watchdog_warn_s.
    {"point": "train_step", "kind": "slow_step", "tick": 9, "seconds": 0.6},
]
WATCHDOG_WARN_S = 0.25


def worker(project_dir: str, status_file: str, chaos: bool) -> int:
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

    ft_kwargs = FaultToleranceKwargs(
        sentinel="rollback",
        sentinel_window=2,
        max_rollbacks=2,
        save_retries=2,
        retry_backoff_s=0.01,
        retry_backoff_max_s=0.05,
        chaos=dict(seed=CHAOS_SEED, schedule=CHAOS_SCHEDULE) if chaos else None,
        watchdog="warn",
        watchdog_warn_s=WATCHDOG_WARN_S,
        watchdog_stall_s=30.0,
        watchdog_poll_s=0.05,
    )
    acc = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=project_dir,
            automatic_checkpoint_naming=True,
        ),
        kwargs_handlers=[ft_kwargs, TelemetryKwargs(log_every=0)],
    )
    module = Net()
    model = Model.from_flax(module, jax.random.key(0), x[:1])
    model, _, dl = acc.prepare(model, optax.adam(1e-2), Spec())

    def loss_fn(params, batch):
        import jax.numpy as jnp

        pred = module.apply({"params": params}, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    step = acc.prepare_train_step(loss_fn)
    state = acc.train_state
    done = int(np.asarray(state.step))
    saved = False
    rollbacks_seen = 0
    last_loss = None
    recompiles_after_warmup = None
    while done < TOTAL_STEPS:
        for batch in dl:
            state, metrics = step(state, batch)
            new_done = int(np.asarray(state.step))
            if new_done < done:
                # The sentinel rolled back mid-iteration: the restored
                # dataloader cursor only applies on the next __iter__, so
                # the stale iterator must be abandoned.
                rollbacks_seen += 1
                done = new_done
                print(f"CHAOSTRAIN_ROLLBACK to {done}", flush=True)
                break
            done = new_done
            last_loss = float(np.asarray(metrics["loss"]))
            if recompiles_after_warmup is None and done >= 2:
                # Step 2 absorbed the one expected same-shape recompile
                # (telemetry.py); anything past this point is a regression.
                recompiles_after_warmup = acc.telemetry.recompiles
            print(f"CHAOSTRAIN_STEP {done} {last_loss}", flush=True)
            if done == SAVE_AT and not saved:
                acc.save_state()
                saved = True
            if done >= TOTAL_STEPS:
                break
    ft = acc.fault_tolerance
    status = {
        "final_step": done,
        "final_loss": last_loss,
        "rollbacks": ft.rollbacks_done,
        "rollbacks_seen": rollbacks_seen,
        "save_retries": ft.save_retries_total,
        "faults_injected": ft.faults_injected,
        "fault_log": list(ft.chaos.injected) if ft.chaos is not None else [],
        "watchdog": ft.watchdog.summary() if ft.watchdog is not None else None,
        "recompiles_after_warmup": recompiles_after_warmup,
        "recompiles_final": acc.telemetry.recompiles,
    }
    acc.end_training()
    with open(status_file, "w") as f:
        json.dump(status, f)
    print(f"CHAOSTRAIN_DONE {done} {last_loss}", flush=True)
    return 0


def _launch_worker(project_dir: str, status_file: str, chaos: bool):
    env = {**os.environ}
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    )
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH"), repo_root, os.getcwd()) if p
    )
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           f"--project-dir={project_dir}", f"--status-file={status_file}"]
    if chaos:
        cmd.append("--chaos")
    return subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        bufsize=1, env=env,
    )


def _drain(proc, timeout_s: float = 300.0) -> str:
    out = []
    deadline = time.monotonic() + timeout_s
    while proc.poll() is None and time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line:
            out.append(line)
            sys.stderr.write(line)
    if proc.poll() is None:
        proc.kill()
        raise AssertionError("worker hung past the smoke timeout")
    out.append(proc.stdout.read() or "")
    sys.stderr.write(out[-1])
    return "".join(out)


def _run(tmp: str, name: str, chaos: bool) -> dict:
    project_dir = os.path.join(tmp, name)
    status_file = os.path.join(tmp, f"{name}_status.json")
    proc = _launch_worker(project_dir, status_file, chaos)
    _drain(proc)
    assert proc.returncode == 0, f"{name} worker failed rc={proc.returncode}"
    with open(status_file) as f:
        status = json.load(f)
    status["project_dir"] = project_dir
    return status


def _telemetry_records(project_dir: str) -> list:
    path = os.path.join(project_dir, "telemetry", "rank_0.jsonl")
    assert os.path.exists(path), f"no telemetry report at {path}"
    with open(path) as f:
        return [json.loads(line) for line in f]


def main() -> int:
    import tempfile

    tmp = tempfile.mkdtemp(prefix="chaos_train_smoke_")

    ref = _run(tmp, "reference", chaos=False)
    assert ref["final_step"] == TOTAL_STEPS, ref
    assert ref["rollbacks"] == 0 and ref["faults_injected"] == 0, ref

    c1 = _run(tmp, "chaos1", chaos=True)
    c2 = _run(tmp, "chaos2", chaos=True)

    # -- determinism: same seed => bit-identical fault schedule, twice ----
    assert c1["fault_log"], "chaos run drew no faults"
    assert c1["fault_log"] == c2["fault_log"], (
        "same seed drew different fault schedules:\n"
        f"  run1: {c1['fault_log']}\n  run2: {c2['fault_log']}"
    )
    assert len(c1["fault_log"]) == len(CHAOS_SCHEDULE), c1["fault_log"]

    # -- recovery: every injected fault took the real path ----------------
    for c in (c1, c2):
        assert c["final_step"] == TOTAL_STEPS, c
        assert c["save_retries"] >= 1, (
            f"torn_write did not drive the save retry path: {c}")
        assert c["rollbacks"] == 1 and c["rollbacks_seen"] == 1, (
            f"nonfinite_grad did not drive exactly one rollback: {c}")

    # -- bit-equality: rollback + replay == never-faulted ------------------
    assert c1["final_loss"] == c2["final_loss"], (
        f"chaos replays disagree: {c1['final_loss']!r} != {c2['final_loss']!r}")
    assert c1["final_loss"] == ref["final_loss"], (
        "chaos run's final loss is not bit-equal to the fault-free run "
        f"after rollback: {c1['final_loss']!r} != {ref['final_loss']!r}")

    # -- watchdog: the injected straggler was named within the deadline ----
    wd = c1["watchdog"]
    assert wd is not None and wd["warnings"] >= 1, (
        f"watchdog never warned on the injected slow_step: {wd}")
    records = _telemetry_records(c1["project_dir"])
    stalls = [r for r in records if r.get("event") == "training_stalled"]
    assert stalls, "no training_stalled telemetry event was recorded"
    assert any(r.get("straggler") == 0 for r in stalls), stalls
    assert all(float(r["age_s"]) >= WATCHDOG_WARN_S for r in stalls), stalls
    faults = [r for r in records if r.get("event") == "fault_injected"]
    assert len(faults) == len(CHAOS_SCHEDULE), faults
    summary = records[-1]
    assert summary.get("event") == "summary", summary
    assert summary.get("faults", {}).get("injected") == len(CHAOS_SCHEDULE), summary
    assert summary.get("watchdog", {}).get("warnings", 0) >= 1, summary

    # -- zero steady-state recompiles (including across the rollback) -----
    for c in (ref, c1, c2):
        assert c["recompiles_final"] == c["recompiles_after_warmup"], (
            f"steady-state recompiles: {c['recompiles_after_warmup']} after "
            f"the two-step warmup vs {c['recompiles_final']} at the end")

    print(
        "CHAOS TRAIN SMOKE OK — "
        f"{len(c1['fault_log'])} faults replayed identically twice; "
        f"1 rollback; {c1['save_retries']} save retry; final loss "
        f"{c1['final_loss']:.6f} bit-equal to fault-free; "
        f"{len(stalls)} stall event(s) naming rank 0; 0 steady-state "
        "recompiles"
    )
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--worker", action="store_true")
    parser.add_argument("--chaos", action="store_true")
    parser.add_argument("--project-dir", default=None)
    parser.add_argument("--status-file", default=None)
    args = parser.parse_args()
    if args.worker:
        sys.exit(worker(args.project_dir, args.status_file, args.chaos))
    sys.exit(main())
