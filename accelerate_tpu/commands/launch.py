"""`accelerate-tpu launch` — env encoding + process fan-out.

Reference analog: commands/launch.py:986-1193 + utils/launch.py:100-427. The
reference forks N CUDA workers per node via torchrun; a JAX/TPU pod instead
runs ONE process per host, each seeing its local chips, rendezvousing through
the JAX coordinator (state.py:_maybe_init_jax_distributed decodes the env this
command writes). Fan-out modes:

- single process: exec the script with the encoded env.
- local multi-process (num_processes > 1, no remote hosts): spawn all
  processes on this machine — the CI / `accelerate test` path; combined with
  ``--virtual_devices`` this simulates a pod on CPU.
- pod member (--machine_rank / TPU_POD): run this host's single process with
  its process index; every pod worker runs the same command with its own rank
  (the reference's tpu_pod_launcher role, driven by `tpu-config`-style ssh or
  a cluster scheduler).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import NamedTuple, Optional

from .config_args import LaunchConfig, load_config_file
from ..utils.constants import PROTOCOL_EXIT_CLASSES


def add_launch_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("launch configuration")
    g.add_argument("--config_file", default=None, help="Config file created by `accelerate-tpu config`")
    g.add_argument("--num_processes", type=int, default=None, help="Total JAX processes (1 per host)")
    g.add_argument("--num_machines", type=int, default=None)
    g.add_argument("--machine_rank", type=int, default=None, help="Index of this host (pod launch)")
    g.add_argument("--main_process_ip", default=None, help="Coordinator (rank 0) address")
    g.add_argument("--main_process_port", type=int, default=None)
    g.add_argument("--mixed_precision", default=None, choices=["no", "bf16", "fp16", "fp8"])
    g.add_argument("--cpu", action="store_true", help="Force JAX_PLATFORMS=cpu")
    g.add_argument("--virtual_devices", type=int, default=None,
                   help="Force N virtual CPU devices per process (pod simulation)")
    g.add_argument("--debug", action="store_true", help="Enable collective shape verification")
    g.add_argument("--gradient_accumulation_steps", type=int, default=None)

    par = p.add_argument_group("parallelism degrees")
    for ax in ("dp_replicate", "dp_shard", "tp", "cp", "sp", "ep", "pp"):
        par.add_argument(f"--{ax}_size", type=int, default=None)
    par.add_argument("--pp_virtual_stages", type=int, default=None,
                     help="Interleaved pipeline schedule degree (bubble/V)")

    f = p.add_argument_group("FSDP / ZeRO")
    f.add_argument("--use_fsdp", action="store_true", default=None)
    f.add_argument("--fsdp_sharding_strategy", default=None,
                   choices=["FULL_SHARD", "SHARD_GRAD_OP", "NO_SHARD", "HYBRID_SHARD"])
    f.add_argument("--fsdp_offload_params", action="store_true", default=None)
    f.add_argument("--fsdp_activation_checkpointing", action="store_true", default=None)

    c = p.add_argument_group("compilation")
    c.add_argument("--remat_policy", default=None, choices=["none", "full", "dots_saveable", "offload"])
    c.add_argument("--no_scan_layers", action="store_true")
    c.add_argument("--jit_cache_dir", default=None)

    el = p.add_argument_group("elastic restarts (reference: torch.distributed.run max_restarts)")
    el.add_argument("--max_restarts", type=int, default=0,
                    help="Restart the whole process gang up to N times after a "
                         "worker failure (fresh rendezvous each attempt)")
    el.add_argument("--monitor_interval", type=float, default=0.2,
                    help="Seconds between worker health polls")
    el.add_argument("--restart_backoff", type=float, default=1.0,
                    help="Base seconds of capped exponential backoff between "
                         "gang restarts (0 disables; preemption restarts are "
                         "never delayed)")
    el.add_argument("--restart_backoff_cap", type=float, default=30.0,
                    help="Ceiling on the restart backoff delay")
    el.add_argument("--shrink_after_dead_hosts", type=int, default=0,
                    help="After N consecutive dead-host exits, relaunch the "
                         "local gang at a planner-validated smaller size and "
                         "let the elastic resume reshard (0 = off)")

    pod = p.add_argument_group("pod launch (ssh fan-out, reference tpu_pod_launcher)")
    pod.add_argument("--pod_hosts", default=None,
                     help="Comma list of ssh targets, or gcloud:NAME:ZONE — fans the "
                          "per-host launch to every pod worker with computed ranks")
    pod.add_argument("--pod_working_dir", default=None, help="cd here on each host first")
    pod.add_argument("--pod_ssh_port", type=int, default=None)
    pod.add_argument("--pod_dry_run", action="store_true",
                     help="Print the per-host commands without running them")

    p.add_argument("-m", "--module", action="store_true", help="Treat the script as a python module")
    p.add_argument("training_script", help="Script (or module with -m) to launch")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER, help="Script arguments")


def resolve_launch_config(args: argparse.Namespace) -> LaunchConfig:
    """Merge CLI flags over the config file (reference:
    commands/launch.py:1196-1383 `_validate_launch_command`)."""
    cfg = LaunchConfig.from_dict(load_config_file(args.config_file))
    overrides = {
        "num_processes": args.num_processes,
        "num_machines": args.num_machines,
        "machine_rank": args.machine_rank,
        "main_process_ip": args.main_process_ip,
        "main_process_port": args.main_process_port,
        "mixed_precision": args.mixed_precision,
        "virtual_devices": args.virtual_devices,
        "gradient_accumulation_steps": args.gradient_accumulation_steps,
        "fsdp_sharding_strategy": args.fsdp_sharding_strategy,
        "remat_policy": args.remat_policy,
        "jit_cache_dir": args.jit_cache_dir,
        "use_fsdp": args.use_fsdp,
        "fsdp_offload_params": args.fsdp_offload_params,
        "fsdp_activation_checkpointing": args.fsdp_activation_checkpointing,
    }
    for ax in ("dp_replicate", "dp_shard", "tp", "cp", "sp", "ep", "pp"):
        overrides[f"{ax}_size"] = getattr(args, f"{ax}_size")
    overrides["pp_virtual_stages"] = args.pp_virtual_stages
    for k, v in overrides.items():
        if v is not None:
            setattr(cfg, k, v)
    if args.cpu:
        cfg.use_cpu = True
    if args.debug:
        cfg.debug = True
    if args.no_scan_layers:
        cfg.scan_layers = False
    if cfg.num_machines > 1 and cfg.num_processes < cfg.num_machines:
        cfg.num_processes = cfg.num_machines
    return cfg


def _script_cmd(args: argparse.Namespace) -> list[str]:
    cmd = [sys.executable]
    if args.module:
        cmd += ["-m"]
    cmd += [args.training_script, *args.training_script_args]
    return cmd


def _spawn(cmd, env, rank: int | None = None) -> subprocess.Popen:
    return subprocess.Popen(cmd, env=env)


# ----------------------------------------------------------------------
# Failure-classifying gang supervisor
# ----------------------------------------------------------------------


def classify_exit(rc: int) -> str:
    """Map a gang exit code to a failure class the supervisor acts on.

    The resumable protocol codes come first, resolved from the single
    source of truth in ``utils.constants.EXIT_CODE_TABLE`` (workers choose
    them on purpose: fault_tolerance.py preemption/watchdog/divergence
    paths, serving.py engine crashes, sdc.py sticky-corruption convictions);
    everything else is inferred from POSIX conventions — negative rc is a
    Popen "killed by signal", 128+N is a shell-style signal death (the chaos
    ``dead_host`` default is 139 = 128+SIGSEGV)."""
    if rc == 0:
        return "ok"
    if rc == 130 or rc == -signal.SIGINT:
        return "interrupted"
    if rc in PROTOCOL_EXIT_CLASSES:
        return PROTOCOL_EXIT_CLASSES[rc]
    if rc == 137 or rc == -signal.SIGKILL:
        # SIGKILL is almost always the kernel OOM killer on a training host.
        return "oom"
    if rc < 0 or 128 < rc < 160:
        return "dead-host"
    return "fatal"


def _backoff_s(n_restarts: int, base_s: float, cap_s: float) -> float:
    """Capped exponential backoff with deterministic jitter (±25%, keyed on
    the restart index via a Weyl-style multiplier so repeated runs of the
    same failure sequence sleep identically — no RNG, replayable)."""
    if base_s <= 0:
        return 0.0
    delay = min(cap_s, base_s * (2.0 ** n_restarts))
    frac = ((n_restarts + 1) * 2654435761 % 1000) / 1000.0
    return delay * (0.75 + 0.5 * frac)


class SupervisorDecision(NamedTuple):
    action: str  # "stop" | "restart" | "refuse"
    classification: str  # classify_exit() result
    delay_s: float = 0.0
    num_processes: Optional[int] = None  # set when the gang should shrink
    reason: str = ""


class GangSupervisor:
    """Restart policy for the local gang loop: classify each exit, spend the
    restart budget with capped backoff, shrink the topology after repeated
    dead-host deaths, and refuse to thrash on crashes a restart cannot fix
    (poisoned checkpoints, the same fatal rc twice in quick succession).

    Pure state machine over (rc, uptime, world size) → decision; the launch
    loop owns the side effects (sleeping, respawning, stderr). Unit-tested
    directly in tests/test_cli.py."""

    def __init__(
        self,
        max_restarts: int,
        backoff_s: float = 1.0,
        backoff_cap_s: float = 30.0,
        shrink_after: int = 0,
        fatal_repeat_limit: int = 2,
        thrash_uptime_s: float = 60.0,
        layout: Optional[dict] = None,
    ):
        self.max_restarts = max(0, int(max_restarts))
        self.backoff_s = float(backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.shrink_after = max(0, int(shrink_after))
        self.fatal_repeat_limit = max(1, int(fatal_repeat_limit))
        self.thrash_uptime_s = float(thrash_uptime_s)
        self.layout = layout
        self.restarts_used = 0
        self._dead_streak = 0
        # Recent fast fatal exit codes; None breaks a streak (a slow crash
        # had time to make progress, so it may not be deterministic).
        self._fatal_history: list = []

    def decide(self, rc: int, uptime_s: float, num_processes: int) -> SupervisorDecision:
        cls = classify_exit(rc)
        if cls in ("ok", "interrupted"):
            return SupervisorDecision("stop", cls)
        if cls == "poisoned":
            return SupervisorDecision(
                "refuse", cls,
                reason="the divergence reproduces from the newest checkpoint; "
                       "a relaunch replays the same failure",
            )
        if cls == "fatal":
            self._fatal_history.append(rc if uptime_s < self.thrash_uptime_s else None)
            recent = self._fatal_history[-self.fatal_repeat_limit:]
            if len(recent) == self.fatal_repeat_limit and all(r == rc for r in recent):
                return SupervisorDecision(
                    "refuse", cls,
                    reason=f"rc={rc} repeated {self.fatal_repeat_limit}x within "
                           f"{self.thrash_uptime_s:.0f}s of launch — the crash "
                           "is deterministic, restarting would thrash",
                )
        else:
            self._fatal_history.clear()
        if self.restarts_used >= self.max_restarts:
            return SupervisorDecision(
                "stop", cls,
                reason=f"restart budget exhausted ({self.max_restarts})",
            )
        new_procs = None
        if cls == "sdc":
            # Sticky silent corruption convicted one host's silicon; the
            # worker already quarantined it on disk (sdc_quarantine.json).
            # Shrink immediately — correctness, not a death streak — so the
            # relaunch excludes it, and skip backoff: waiting cannot heal
            # bad hardware.
            from ..resharding import shrink_world_size

            shrunk = shrink_world_size(num_processes, lost=1, layout=self.layout)
            if shrunk is not None and shrunk < num_processes:
                new_procs = shrunk
            self._dead_streak = 0
        elif cls == "dead-host":
            self._dead_streak += 1
            if self.shrink_after and self._dead_streak >= self.shrink_after:
                from ..resharding import shrink_world_size

                shrunk = shrink_world_size(num_processes, lost=1, layout=self.layout)
                if shrunk is not None and shrunk < num_processes:
                    new_procs = shrunk
                    self._dead_streak = 0
        else:
            self._dead_streak = 0
        n = self.restarts_used
        self.restarts_used += 1
        # Zero backoff where waiting buys nothing: a preemption auto-saved,
        # a serving/cell crash left a journal the relaunch replays, and SDC
        # already quarantined the bad host. "fleet-degraded" deliberately
        # backs off — every cell is breaching, so a hot relaunch just sheds.
        delay = (0.0 if cls in ("preempted", "serving-crash", "sdc",
                                "cell-dead")
                 else _backoff_s(n, self.backoff_s, self.backoff_cap_s))
        return SupervisorDecision("restart", cls, delay_s=delay, num_processes=new_procs)


def launch_command(args: argparse.Namespace) -> int:
    cfg = resolve_launch_config(args)
    if getattr(args, "pod_hosts", None):
        from .pod import pod_launch

        # Pod mode never runs the script here: each worker host re-enters
        # `accelerate-tpu launch` with its own --machine_rank (-m rides along
        # in the forwarded launch flags).
        return pod_launch(args, cfg, [args.training_script, *args.training_script_args])
    base_env = {**os.environ, **cfg.to_env()}
    # Script-mode children resolve imports from the script's directory, not the
    # launcher's cwd — propagate the cwd so repo-checkout runs work uninstalled.
    base_env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.environ.get("PYTHONPATH"), os.getcwd()) if p
    )
    cmd = _script_cmd(args)

    if cfg.num_processes <= 1:
        return subprocess.call(cmd, env=base_env)

    coordinator_ip = cfg.main_process_ip or "127.0.0.1"
    port = cfg.main_process_port
    remote = cfg.main_process_ip not in (None, "", "127.0.0.1", "localhost") or cfg.num_machines > 1

    if remote:
        # This invocation is ONE pod member; its peers run the same command
        # with their own --machine_rank. --main_process_ip=auto defers the
        # whole rendezvous to jax's TPU-metadata discovery (gcloud pods).
        coord = (
            "auto" if coordinator_ip == "auto" else f"{coordinator_ip}:{port or 8476}"
        )
        env = {
            **base_env,
            "ACCELERATE_COORDINATOR_ADDRESS": coord,
            "ACCELERATE_NUM_PROCESSES": str(cfg.num_processes),
            "ACCELERATE_PROCESS_INDEX": str(cfg.machine_rank),
            "ACCELERATE_LOCAL_PROCESS_INDEX": "0",
        }
        return subprocess.call(cmd, env=env)

    if base_env.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        # A chip belongs to one process: N local ranks on an accelerator
        # backend would each claim every chip and hang in start-up.
        print(
            f"[accelerate-tpu] refusing --num_processes {cfg.num_processes} "
            "on one host without --cpu: every rank would claim all local "
            "chips. One process drives all local chips — use "
            "--num_processes 1 (or --cpu / --virtual_devices N for a CPU "
            "gang).",
            file=sys.stderr,
        )
        return 2

    # Local fan-out: all processes on this machine. The gang restarts
    # together under the failure-classifying supervisor (the reference
    # delegates this to torch elastic's max_restarts,
    # commands/launch.py:998-1030): resumable protocol exits (preemption 75,
    # watchdog stall 76) and crash-like deaths spend the --max_restarts
    # budget with capped backoff; poisoned checkpoints (77) and repeated
    # identical fast crashes end the run instead of thrashing; repeated
    # dead-host exits can shrink the gang (--shrink_after_dead_hosts). Each
    # attempt gets a fresh rendezvous port so stale coordinator state can't
    # poison the retry.
    max_restarts = max(0, int(getattr(args, "max_restarts", 0) or 0))
    monitor_interval = float(getattr(args, "monitor_interval", 0.2) or 0.2)
    supervisor = GangSupervisor(
        max_restarts=max_restarts,
        backoff_s=float(getattr(args, "restart_backoff", 1.0) or 0.0),
        backoff_cap_s=float(getattr(args, "restart_backoff_cap", 30.0) or 0.0),
        shrink_after=int(getattr(args, "shrink_after_dead_hosts", 0) or 0),
    )
    attempt = 0
    while True:
        started = time.monotonic()
        started_wall = time.time()
        rc = _run_gang(cmd, base_env, cfg, port, monitor_interval, attempt)
        decision = supervisor.decide(rc, time.monotonic() - started, cfg.num_processes)
        if rc != 0:
            _surface_flight_bundles(started_wall, attempt)
        left = max_restarts - supervisor.restarts_used
        if decision.action == "stop":
            if decision.reason:
                print(
                    f"[accelerate-tpu] attempt {attempt} exited rc={rc} "
                    f"({decision.classification}); {decision.reason}",
                    file=sys.stderr,
                )
            return rc
        if decision.action == "refuse":
            print(
                f"[accelerate-tpu] attempt {attempt} exited rc={rc} "
                f"({decision.classification}); refusing to relaunch: "
                f"{decision.reason}",
                file=sys.stderr,
            )
            return rc
        if decision.num_processes is not None:
            # Repeated dead-host deaths: relaunch smaller and let the elastic
            # resume reshard the newest verified checkpoint onto the shrunken
            # gang (resharding.py shrink_world_size picked a size the planner
            # validates).
            print(
                f"[accelerate-tpu] shrinking gang "
                f"{cfg.num_processes} -> {decision.num_processes} processes "
                "after repeated dead-host exits",
                file=sys.stderr,
            )
            cfg.num_processes = decision.num_processes
            base_env = {**base_env, **cfg.to_env()}
        if decision.classification == "preempted":
            # A preemption-triggered save completed and the workers asked
            # for a resumable restart (fault_tolerance.py): the relaunch
            # carries ACCELERATE_RESTART_ATTEMPT so elastic auto-resume
            # continues from the preemption checkpoint. If the relaunch
            # lands on a different device count, an ElasticKwargs handler
            # reshards the restore onto whatever came back (resharding.py);
            # without one the mismatched load fails fast with both
            # topologies named.
            print(
                f"[accelerate-tpu] attempt {attempt}: preemption save "
                f"complete (rc={rc}); relaunching gang to resume "
                f"({left} restarts left; a changed "
                f"slice size reshards under ElasticKwargs)",
                file=sys.stderr,
            )
        else:
            print(
                f"[accelerate-tpu] attempt {attempt} failed (rc={rc}, "
                f"{decision.classification}); restarting gang "
                f"({left} restarts left"
                + (f"; backoff {decision.delay_s:.1f}s" if decision.delay_s else "")
                + ")",
                file=sys.stderr,
            )
        if decision.delay_s:
            time.sleep(decision.delay_s)
        port = None  # re-draw a fresh port next attempt
        attempt += 1


def _surface_flight_bundles(started_wall: float, attempt: int) -> None:
    """After an abnormal gang exit, point the operator at any crash flight
    bundle a child wrote during this attempt (profiler.FlightRecorder dumps
    ``flight_<exit_class>.json`` on its way down). Only bundles newer than
    the attempt's start count — stale bundles from earlier runs stay quiet."""
    try:
        from ..profiler import find_flight_bundles
    except Exception:
        return
    import json

    for path in find_flight_bundles():
        try:
            if os.path.getmtime(path) < started_wall - 1.0:
                continue
            with open(path) as f:
                bundle = json.load(f)
        except (OSError, ValueError):
            continue
        ring = bundle.get("entries") or []
        tail = ring[-3:]
        print(
            f"[accelerate-tpu] attempt {attempt}: flight recorder bundle at "
            f"{path} (exit_class={bundle.get('exit_class')}, "
            f"reason={bundle.get('reason')!r}, {len(ring)} ring entries)",
            file=sys.stderr,
        )
        for entry in tail:
            print(f"[accelerate-tpu]   last: {json.dumps(entry, default=str)}",
                  file=sys.stderr)


def _run_gang(cmd, base_env, cfg, port, monitor_interval: float, attempt: int) -> int:
    """One launch attempt of the full process gang; fail fast on ANY rank's
    crash (not just rank 0's) so a dead peer doesn't leave siblings blocked in
    coordinator rendezvous until their own timeout."""
    import time

    if port is None:
        from ..utils.other import get_free_port

        port = get_free_port()
    procs: list[subprocess.Popen] = []
    try:
        for rank in range(cfg.num_processes):
            env = {
                **base_env,
                "ACCELERATE_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
                "ACCELERATE_NUM_PROCESSES": str(cfg.num_processes),
                "ACCELERATE_PROCESS_INDEX": str(rank),
                "ACCELERATE_LOCAL_PROCESS_INDEX": str(rank),
                "ACCELERATE_RESTART_ATTEMPT": str(attempt),
            }
            procs.append(_spawn(cmd, env, rank))
        exit_code = 0
        while any(p.poll() is None for p in procs):
            for rank, proc in enumerate(procs):
                rc = proc.poll()
                if rc is not None and rc != 0 and exit_code == 0:
                    exit_code = rc
                    print(
                        f"[accelerate-tpu] process {rank} exited with code {rc}; "
                        "terminating remaining processes",
                        file=sys.stderr,
                    )
                    for other in procs:
                        if other.poll() is None:
                            other.send_signal(signal.SIGTERM)
            time.sleep(monitor_interval)
        if exit_code == 0:
            exit_code = next((p.returncode for p in procs if p.returncode != 0), 0)
        return exit_code
    except KeyboardInterrupt:
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGINT)
        for proc in procs:
            proc.wait()
        return 130


def add_parser(subparsers) -> argparse.ArgumentParser:
    p = subparsers.add_parser("launch", help="Launch a training script on this host / pod member")
    add_launch_args(p)
    p.set_defaults(func=launch_command)
    return p
