"""CLI layer tests (reference analog: tests/test_cli.py).

The launched-subprocess tests follow the reference's central trick: assertions
run inside processes spawned by the product's own launcher (SURVEY.md §4).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


def _run_cli(*argv, timeout=600):
    result = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", *argv],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.getcwd()},
    )
    assert result.returncode == 0, (
        f"CLI {' '.join(argv)} failed:\n{result.stdout}\n{result.stderr}"
    )
    return result.stdout


def test_config_default(tmp_path):
    path = str(tmp_path / "cfg.json")
    out = _run_cli("config", "--default", "--config_file", path, "--mixed_precision", "bf16")
    assert "saved" in out
    with open(path) as f:
        cfg = json.load(f)
    assert cfg["mixed_precision"] == "bf16"
    assert cfg["num_processes"] == 1


def test_config_env_encoding():
    from accelerate_tpu.commands.config_args import LaunchConfig

    cfg = LaunchConfig(
        mixed_precision="bf16",
        dp_shard_size=4,
        tp_size=2,
        use_fsdp=True,
        gradient_accumulation_steps=3,
        debug=True,
        virtual_devices=8,
    )
    env = cfg.to_env()
    assert env["ACCELERATE_MIXED_PRECISION"] == "bf16"
    assert env["PARALLELISM_CONFIG_DP_SHARD_SIZE"] == "4"
    assert env["PARALLELISM_CONFIG_TP_SIZE"] == "2"
    assert env["ACCELERATE_USE_FSDP"] == "true"
    assert env["ACCELERATE_GRADIENT_ACCUMULATION_STEPS"] == "3"
    assert env["ACCELERATE_DEBUG_MODE"] == "true"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"


def test_env_command():
    out = _run_cli("env")
    assert "accelerate_tpu version" in out
    assert "JAX version" in out


def test_estimate_memory_builtin():
    out = _run_cli("estimate-memory", "llama:tiny", "--json", "--dtypes", "fp32", "bf16")
    rows = json.loads(out.strip().splitlines()[-1])
    fp32, bf16 = rows
    assert fp32["dtype"] == "fp32"
    # bf16 inference weights are half the fp32 size.
    assert abs(bf16["inference_total"] * 2 - fp32["inference_total"]) <= 2
    # Training adds grads + Adam moments (+ master for low precision).
    assert fp32["training_total"] == fp32["inference_total"] * 4


def test_merge_weights(tmp_path):
    from accelerate_tpu.utils.other import (
        load_safetensors,
        save_sharded_safetensors,
    )

    flat = {
        "layer1/kernel": np.arange(12, dtype=np.float32).reshape(3, 4),
        "layer2/kernel": np.ones((2, 2), dtype=np.float32),
    }
    src = tmp_path / "ckpt"
    src.mkdir()
    # Force two shards with a tiny max size.
    save_sharded_safetensors(flat, str(src), weights_name="model.safetensors", max_shard_size=40)
    out = tmp_path / "merged"
    _run_cli("merge-weights", str(src), str(out))
    merged = load_safetensors(str(out / "model.safetensors"))
    assert set(merged) == set(flat)
    np.testing.assert_array_equal(merged["layer1/kernel"], flat["layer1/kernel"])


@pytest.mark.slow
def test_launched_test_script_multiprocess():
    """The reference's flagship pattern: `launch --num_processes=2 <script>`
    with assertions inside (tests/test_multidevice.py:41-60 analog)."""
    from accelerate_tpu.test_utils import execute_subprocess, get_launch_command

    cmd = get_launch_command(num_processes=2, virtual_devices=2) + [
        "-m", "accelerate_tpu.test_utils.scripts.test_script"
    ]
    out = execute_subprocess(cmd, env={"PYTHONPATH": os.getcwd()})
    assert "All launched checks passed" in out


def test_launched_elastic_auto_resume(tmp_path):
    """Kill one rank mid-run → the launcher restarts the gang → attempt 1
    auto-resumes from the latest automatic checkpoint (assertions inside
    test_utils/scripts/test_elastic.py)."""
    from accelerate_tpu.test_utils import execute_subprocess, get_launch_command

    cmd = get_launch_command(
        num_processes=2, virtual_devices=2, max_restarts=1
    ) + ["-m", "accelerate_tpu.test_utils.scripts.test_elastic"]
    out = execute_subprocess(
        cmd, env={"PYTHONPATH": os.getcwd(), "ELASTIC_TEST_DIR": str(tmp_path)}
    )
    assert "Elastic resume test passed" in out


def test_launch_single_process_env(tmp_path):
    script = tmp_path / "show_env.py"
    script.write_text(
        "import os, json\n"
        "print(json.dumps({k: os.environ.get(k) for k in "
        "('ACCELERATE_MIXED_PRECISION', 'PARALLELISM_CONFIG_TP_SIZE')}))\n"
    )
    out = _run_cli(
        "launch", "--mixed_precision", "fp16", "--tp_size", "2", "--dp_shard_size", "4",
        str(script),
    )
    env = json.loads(out.strip().splitlines()[-1])
    assert env["ACCELERATE_MIXED_PRECISION"] == "fp16"
    assert env["PARALLELISM_CONFIG_TP_SIZE"] == "2"


def test_launch_refuses_local_gang_on_accelerator(tmp_path, monkeypatch, capsys):
    """A chip belongs to one process: N local ranks without --cpu would each
    claim every chip and hang, so the launcher refuses before spawning and
    names --num_processes 1. (``tpu,cpu`` is what a TPU host exports.)"""
    from unittest import mock

    from accelerate_tpu.commands.accelerate_cli import main

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with mock.patch("subprocess.Popen", side_effect=AssertionError("spawned")):
        rc = main(["launch", "--num_processes", "2", str(tmp_path / "never_run.py")])
    assert rc == 2
    assert "--num_processes 1" in capsys.readouterr().err


def _square(x):
    assert x == 3


def test_notebook_launcher_single():
    from accelerate_tpu import notebook_launcher

    notebook_launcher(_square, (3,), num_processes=1)


def test_pod_launch_dry_run_ssh(capsys):
    """Pod fan-out (reference tpu_pod_launcher, commands/launch.py:1117-1173):
    dry-run prints one ssh command per host with computed ranks and the
    coordinator pinned to host 0."""
    import sys
    from unittest import mock

    from accelerate_tpu.commands.accelerate_cli import main

    argv = ["accelerate-tpu", "launch",
            "--pod_hosts", "tpu-w0,tpu-w1,tpu-w2",
            "--pod_working_dir", "/srv/job",
            "--pod_dry_run", "--tp_size", "4", "--mixed_precision", "bf16",
            "train.py", "--lr", "1e-4"]
    with mock.patch.object(sys, "argv", argv):
        rc = main()
    assert not rc
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 3
    for rank, line in enumerate(out):
        assert line.startswith(f"[tpu-w{rank}] ssh ")
        assert f"--machine_rank={rank}" in line
        assert "--num_machines=3" in line
        assert "--main_process_ip=tpu-w0" in line
        assert "--main_process_port=8476" in line
        assert "cd /srv/job &&" in line
        assert "--tp_size=4" in line
        assert "--mixed_precision=bf16" in line
        assert "train.py --lr 1e-4" in line


def test_pod_launch_dry_run_gcloud(capsys):
    import sys
    from unittest import mock

    from accelerate_tpu.commands.accelerate_cli import main

    argv = ["accelerate-tpu", "launch",
            "--pod_hosts", "gcloud:my-pod:us-central2-b",
            "--num_machines", "2", "--pod_dry_run", "train.py"]
    with mock.patch.object(sys, "argv", argv):
        rc = main()
    assert not rc
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2
    for rank, line in enumerate(out):
        assert "gcloud compute tpus tpu-vm ssh my-pod" in line
        assert f"--worker={rank}" in line
        assert "--zone=us-central2-b" in line
        assert f"--machine_rank={rank}" in line
        assert "--main_process_ip=auto" in line  # jax TPU-metadata rendezvous


def test_estimate_memory_hub_config_meta_init(tmp_path):
    """Hub-model sizing via transformers meta-device init (reference:
    commands/estimate.py:66-318) — a config.json-only directory must size
    through AutoModel.from_config on the meta device, no weights."""
    import json as _json

    from accelerate_tpu.commands.estimate import estimate_memory

    cfg = {"architectures": ["LlamaForCausalLM"], "model_type": "llama",
           "hidden_size": 256, "intermediate_size": 688, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 1000,
           "max_position_embeddings": 128}
    (tmp_path / "config.json").write_text(_json.dumps(cfg))
    rows = estimate_memory(str(tmp_path), ["bf16", "fp32"])
    assert rows[0]["inference_total"] > 1_000_000  # ~2.1M params * 2 bytes
    assert rows[0]["training_total"] > rows[0]["inference_total"]


def test_pod_launch_forwards_all_config_flags(capsys):
    """Every launch-config flag must reach the per-host command — a dropped
    flag silently diverges worker configs."""
    import sys
    from unittest import mock

    from accelerate_tpu.commands.accelerate_cli import main

    argv = ["accelerate-tpu", "launch",
            "--pod_hosts", "h0,h1", "--pod_dry_run",
            "--gradient_accumulation_steps", "4",
            "--use_fsdp", "--fsdp_sharding_strategy", "SHARD_GRAD_OP",
            "--fsdp_activation_checkpointing", "--remat_policy", "full",
            "--no_scan_layers", "--debug", "--jit_cache_dir", "/tmp/jc",
            "train.py"]
    with mock.patch.object(sys, "argv", argv):
        assert not main()
    out = capsys.readouterr().out
    for frag in ("--gradient_accumulation_steps=4", "--use_fsdp",
                 "--fsdp_sharding_strategy=SHARD_GRAD_OP",
                 "--fsdp_activation_checkpointing", "--remat_policy=full",
                 "--no_scan_layers", "--debug", "--jit_cache_dir=/tmp/jc"):
        assert frag in out, frag


def test_elastic_restart_recovers(tmp_path):
    """--max_restarts: the gang restarts after a worker failure and the retry
    succeeds (reference: torch elastic max_restarts passthrough,
    commands/launch.py:998-1030)."""
    import subprocess
    import sys

    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        "attempt = int(os.environ.get('ACCELERATE_RESTART_ATTEMPT', '0'))\n"
        "rank = os.environ.get('ACCELERATE_PROCESS_INDEX', '0')\n"
        "if attempt == 0 and rank == '1':\n"
        "    sys.exit(17)  # simulated worker crash on first attempt\n"
        "print(f'attempt={attempt} rank={rank} ok')\n"
    )
    base = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
            "--num_processes=2", "--cpu"]
    env = {**os.environ, "PYTHONPATH": os.getcwd(), "XLA_FLAGS": ""}

    # Without restarts: fails.
    r = subprocess.run(base + [str(script)], env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 17, (r.returncode, r.stdout, r.stderr)

    # With one restart: recovers.
    r = subprocess.run(base + ["--max_restarts=1", str(script)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert "restarting gang" in r.stderr
    assert "attempt=1 rank=0 ok" in r.stdout


def test_classify_exit_table():
    """The supervisor's failure classes, pinned (commands/launch.py)."""
    import signal as _signal

    from accelerate_tpu.commands.launch import classify_exit

    assert classify_exit(0) == "ok"
    assert classify_exit(130) == "interrupted"
    assert classify_exit(-_signal.SIGINT) == "interrupted"
    assert classify_exit(75) == "preempted"  # PREEMPTION_EXIT_CODE
    assert classify_exit(76) == "stalled"  # TRAINING_STALLED_EXIT_CODE
    assert classify_exit(77) == "poisoned"  # POISONED_CHECKPOINT_EXIT_CODE
    assert classify_exit(78) == "serving-crash"  # SERVING_CRASH_EXIT_CODE
    assert classify_exit(137) == "oom"
    assert classify_exit(-_signal.SIGKILL) == "oom"
    assert classify_exit(139) == "dead-host"  # chaos dead_host default
    assert classify_exit(-_signal.SIGSEGV) == "dead-host"
    assert classify_exit(134) == "dead-host"  # 128 + SIGABRT
    assert classify_exit(79) == "sdc"  # SDC_EXIT_CODE
    assert classify_exit(80) == "cell-dead"  # CELL_DEAD_EXIT_CODE
    assert classify_exit(81) == "fleet-degraded"  # FLEET_DEGRADED_EXIT_CODE
    assert classify_exit(1) == "fatal"
    assert classify_exit(17) == "fatal"


def test_exit_code_table_is_single_source_of_truth():
    """EXIT_CODE_TABLE (utils/constants.py) is what classify_exit and the
    docs render from: every row's classification must round-trip through
    the classifier, and every protocol constant must appear exactly once."""
    from accelerate_tpu.commands.launch import classify_exit
    from accelerate_tpu.utils import constants

    codes = [row["code"] for row in constants.EXIT_CODE_TABLE]
    assert codes == sorted(codes), "table rows must stay sorted by code"
    assert len(codes) == len(set(codes)), "duplicate exit code rows"
    for row in constants.EXIT_CODE_TABLE:
        assert classify_exit(row["code"]) == row["classification"], row
        assert row["response"], row
        if row["constant"] is not None and row["constant"].isidentifier():
            assert getattr(constants, row["constant"]) == row["code"], row
    # The resumable protocol subset the classifier resolves table-first.
    assert constants.PROTOCOL_EXIT_CLASSES == {
        75: "preempted", 76: "stalled", 77: "poisoned",
        78: "serving-crash", 79: "sdc", 80: "cell-dead",
        81: "fleet-degraded"}


def test_supervisor_sdc_shrinks_with_zero_backoff():
    """A sticky-SDC conviction (exit 79) relaunches immediately and SHRUNK:
    waiting cannot heal bad silicon, and the convicted host is already
    quarantined on disk by the worker."""
    from accelerate_tpu.commands.launch import GangSupervisor
    from accelerate_tpu.utils.constants import SDC_EXIT_CODE

    sup = GangSupervisor(max_restarts=3)
    d = sup.decide(SDC_EXIT_CODE, uptime_s=100.0, num_processes=4)
    assert d.action == "restart" and d.classification == "sdc"
    assert d.delay_s == 0.0
    assert d.num_processes == 2  # largest power of two <= 4 - 1
    # Unlike dead-host, sdc shrinks on the FIRST conviction — correctness,
    # not a death streak — and does not disturb the dead-host streak logic.
    sup2 = GangSupervisor(max_restarts=9, shrink_after=2)
    assert sup2.decide(139, uptime_s=5.0, num_processes=4).num_processes is None
    d2 = sup2.decide(SDC_EXIT_CODE, uptime_s=5.0, num_processes=4)
    assert d2.num_processes == 2 and d2.delay_s == 0.0
    assert sup2._dead_streak == 0


def test_supervisor_fleet_exit_codes():
    """The fleet classes (PR 18): a dead CELL relaunches with zero backoff
    (the router already drained its journal onto survivors, so the restart
    is immediately productive with a fresh WAL dir); a degraded FLEET backs
    off — every cell is breaching, so a hot relaunch would just shed."""
    from accelerate_tpu.commands.launch import GangSupervisor
    from accelerate_tpu.utils.constants import (
        CELL_DEAD_EXIT_CODE, FLEET_DEGRADED_EXIT_CODE)

    sup = GangSupervisor(max_restarts=3, backoff_s=0.5)
    d = sup.decide(CELL_DEAD_EXIT_CODE, uptime_s=100.0, num_processes=4)
    assert d.action == "restart" and d.classification == "cell-dead"
    assert d.delay_s == 0.0
    d = sup.decide(FLEET_DEGRADED_EXIT_CODE, uptime_s=100.0, num_processes=4)
    assert d.action == "restart" and d.classification == "fleet-degraded"
    assert d.delay_s > 0


def test_restart_backoff_deterministic_and_capped():
    from accelerate_tpu.commands.launch import _backoff_s

    # Replayable: no RNG, same inputs -> same sleep.
    assert _backoff_s(2, 1.0, 30.0) == _backoff_s(2, 1.0, 30.0)
    # Exponential until the cap; jitter stays within +-25%.
    for n in range(8):
        d = _backoff_s(n, 1.0, 30.0)
        raw = min(30.0, 2.0**n)
        assert 0.75 * raw <= d <= 1.25 * raw
    assert _backoff_s(3, 0.0, 30.0) == 0.0


def test_supervisor_budget_poisoned_and_preempted():
    from accelerate_tpu.commands.launch import GangSupervisor

    sup = GangSupervisor(max_restarts=1, backoff_s=0.5)
    d = sup.decide(139, uptime_s=5.0, num_processes=4)
    assert d.action == "restart" and d.classification == "dead-host"
    assert d.delay_s > 0
    d = sup.decide(139, uptime_s=5.0, num_processes=4)
    assert d.action == "stop" and "budget exhausted" in d.reason

    # Preempted workers saved on the way out: relaunch immediately.
    sup = GangSupervisor(max_restarts=3)
    d = sup.decide(75, uptime_s=100.0, num_processes=4)
    assert d.action == "restart" and d.classification == "preempted"
    assert d.delay_s == 0.0

    # A poisoned checkpoint replays the same divergence — never relaunch,
    # even with budget left.
    d = sup.decide(77, uptime_s=100.0, num_processes=4)
    assert d.action == "refuse" and d.classification == "poisoned"

    d = GangSupervisor(max_restarts=3).decide(0, uptime_s=10.0, num_processes=4)
    assert d.action == "stop" and d.classification == "ok"


def test_supervisor_serving_crash_zero_backoff():
    """A serving-engine death (rc 78) relaunches with ZERO backoff: the
    request journal makes the relaunch immediately productive, so any sleep
    only burns the SLO budget of the requests recover() will replay."""
    from accelerate_tpu.commands.launch import GangSupervisor

    sup = GangSupervisor(max_restarts=3, backoff_s=5.0)
    d = sup.decide(78, uptime_s=2.0, num_processes=1)
    assert d.action == "restart" and d.classification == "serving-crash"
    assert d.delay_s == 0.0
    # Still spends the restart budget — a crash-looping engine must stop.
    sup.decide(78, uptime_s=2.0, num_processes=1)
    sup.decide(78, uptime_s=2.0, num_processes=1)
    d = sup.decide(78, uptime_s=2.0, num_processes=1)
    assert d.action == "stop" and "budget exhausted" in d.reason


def test_supervisor_refuses_deterministic_fatal():
    from accelerate_tpu.commands.launch import GangSupervisor

    # The same fatal rc twice in quick succession is a deterministic crash.
    sup = GangSupervisor(max_restarts=10)
    assert sup.decide(17, uptime_s=2.0, num_processes=4).action == "restart"
    d = sup.decide(17, uptime_s=2.0, num_processes=4)
    assert d.action == "refuse" and "deterministic" in d.reason

    # A slow crash between them breaks the streak (it made progress).
    sup = GangSupervisor(max_restarts=10)
    assert sup.decide(17, uptime_s=2.0, num_processes=4).action == "restart"
    assert sup.decide(17, uptime_s=600.0, num_processes=4).action == "restart"
    assert sup.decide(17, uptime_s=2.0, num_processes=4).action == "restart"


def test_supervisor_dead_host_shrink():
    from accelerate_tpu.commands.launch import GangSupervisor

    sup = GangSupervisor(max_restarts=10, backoff_s=0.0, shrink_after=2)
    d = sup.decide(139, uptime_s=5.0, num_processes=8)
    assert d.action == "restart" and d.num_processes is None
    d = sup.decide(-11, uptime_s=5.0, num_processes=8)  # second dead host
    assert d.action == "restart" and d.num_processes == 4  # pow2 below 8-1
    # The streak reset: the next dead host starts counting again.
    d = sup.decide(139, uptime_s=5.0, num_processes=4)
    assert d.num_processes is None
    # A planner layout constrains the shrink to validated sizes.
    sup = GangSupervisor(
        max_restarts=10, backoff_s=0.0, shrink_after=1,
        layout={"tp": 2, "dp_shard": 4},
    )
    d = sup.decide(139, uptime_s=5.0, num_processes=8)
    assert d.num_processes == 6  # tp=2 must still divide: 6 = 3x2 works


def test_shrink_world_size():
    from accelerate_tpu.resharding import shrink_world_size

    assert shrink_world_size(8) == 4  # largest pow2 <= 7
    assert shrink_world_size(9) == 8
    assert shrink_world_size(2) == 1
    assert shrink_world_size(1) is None
    assert shrink_world_size(8, lost=7) == 1
    assert shrink_world_size(8, layout={"tp": 4, "dp_shard": 2}) == 4
    assert shrink_world_size(4, lost=1, layout={"tp": 4}) is None
    # Edge cases: losing everything (or more) leaves nothing to shrink to,
    # and a layout whose fixed axes validate NO smaller size refuses.
    assert shrink_world_size(8, lost=8) is None
    assert shrink_world_size(8, lost=20) is None
    assert shrink_world_size(0) is None
    assert shrink_world_size(3, layout={"tp": 4, "dp_shard": 2}) is None
    assert shrink_world_size(2, lost=1) == 1  # shrink-to-1 is legal bare...
    assert shrink_world_size(2, lost=1, layout={"tp": 2}) is None  # ...not under tp=2


def test_grow_world_size():
    """The shrink helper's inverse (autoscale.py scale-up): largest viable
    size in (current, current+gained], never sideways or down."""
    from accelerate_tpu.resharding import grow_world_size

    assert grow_world_size(4, gained=4) == 8
    assert grow_world_size(4, gained=3) is None  # 7,6,5 hold no pow2 > 4
    assert grow_world_size(4, gained=12) == 16
    assert grow_world_size(1, gained=1) == 2
    assert grow_world_size(0) is None
    # A planner layout admits non-pow2 targets its fixed axes divide.
    assert grow_world_size(4, gained=2, layout={"tp": 2}) == 6
    assert grow_world_size(4, gained=2, layout={"tp": 4, "dp_shard": 2}) is None
    # dp_shard is the rescalable axis: 12 = tp4 x dp_shard3 is viable.
    assert grow_world_size(8, gained=4, layout={"tp": 4, "dp_shard": 2}) == 12
    assert grow_world_size(8, gained=8, layout={"tp": 4, "dp_shard": 2}) == 16


def test_world_size_validation_shared_helper(monkeypatch):
    """Both shrink_world_size (the GangSupervisor's dead-host path) and
    grow_world_size (the autoscaler's scale-up) route layout validation
    through planner.validate_world_size — ONE topology gate, pinned so the
    two callers can't drift apart."""
    from accelerate_tpu import planner, resharding

    assert planner.validate_world_size(8) is True
    assert planner.validate_world_size(0) is False
    assert planner.validate_world_size(6, {"tp": 2}) is True
    assert planner.validate_world_size(6, {"tp": 4}) is False

    seen = []
    real = planner.validate_world_size

    def spy(n, layout=None):
        seen.append(n)
        return real(n, layout)

    monkeypatch.setattr(planner, "validate_world_size", spy)
    resharding.shrink_world_size(8, layout={"tp": 2})
    assert seen, "shrink_world_size bypassed the shared planner gate"
    shrink_calls = list(seen)
    seen.clear()
    resharding.grow_world_size(4, gained=2, layout={"tp": 2})
    assert seen, "grow_world_size bypassed the shared planner gate"
    assert max(seen) <= 6 and max(shrink_calls) <= 7


def test_launched_dead_host_chaos_supervisor(tmp_path):
    """Satellite of the chaos-training pillar: a chaos-injected dead_host
    (exit 139 on every rank at the 4th step) must be classified dead-host by
    the supervisor, relaunched with backoff, and attempt 1 must resume from
    the newest verified checkpoint (assertions inside test_elastic.py)."""
    import subprocess
    import sys as _sys

    from accelerate_tpu.test_utils import get_launch_command

    cmd = get_launch_command(
        num_processes=2, virtual_devices=2, max_restarts=1,
        restart_backoff=0.05,
    ) + ["-m", "accelerate_tpu.test_utils.scripts.test_elastic"]
    r = subprocess.run(
        cmd,
        env={**os.environ, "PYTHONPATH": os.getcwd(),
             "ELASTIC_TEST_DIR": str(tmp_path),
             "ELASTIC_CHAOS": "dead_host"},
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert "Elastic resume test passed" in r.stdout
    assert "rc=139, dead-host" in r.stderr
    assert "restarting gang" in r.stderr


def test_convert_config_fsdp(tmp_path, capsys):
    """Reference FSDP yaml → our LaunchConfig yaml (to-fsdp2 migration role)."""
    import yaml

    from accelerate_tpu.commands.accelerate_cli import main

    ref = {
        "distributed_type": "FSDP",
        "mixed_precision": "bf16",
        "num_processes": 8,
        "fsdp_config": {
            "fsdp_sharding_strategy": "FULL_SHARD",
            "fsdp_activation_checkpointing": True,
            "fsdp_offload_params": False,
            "fsdp_state_dict_type": "SHARDED_STATE_DICT",
            "fsdp_auto_wrap_policy": "TRANSFORMER_BASED_WRAP",
        },
    }
    src = tmp_path / "ref.yaml"
    src.write_text(yaml.safe_dump(ref))
    out = tmp_path / "ours.yaml"
    assert main(["convert-config", str(src), "-o", str(out)]) == 0
    got = yaml.safe_load(out.read_text())
    assert got["use_fsdp"] is True
    assert got["dp_shard_size"] == 8
    assert got["mixed_precision"] == "bf16"
    assert got["fsdp_activation_checkpointing"] is True
    assert got["remat_policy"] == "dots"
    notes = capsys.readouterr().err
    assert "fsdp_auto_wrap_policy" in notes  # dropped keys are reported (stderr)


def test_convert_config_deepspeed_and_hybrid(tmp_path):
    import yaml

    from accelerate_tpu.commands.convert import convert_reference_config

    cfg, notes = convert_reference_config({
        "distributed_type": "DEEPSPEED",
        "num_processes": 16,
        "deepspeed_config": {"zero_stage": 2, "offload_optimizer_device": "cpu"},
    })
    assert cfg.use_fsdp and cfg.fsdp_sharding_strategy == "SHARD_GRAD_OP"
    assert cfg.dp_shard_size == 16 and cfg.fsdp_offload_params

    cfg, _ = convert_reference_config({
        "distributed_type": "FSDP",
        "num_processes": 16,
        "num_machines": 2,
        "fsdp_config": {"fsdp_sharding_strategy": "HYBRID_SHARD"},
    })
    assert cfg.dp_shard_size == 8 and cfg.dp_replicate_size == 2

    cfg, _ = convert_reference_config({
        "distributed_type": "MULTI_GPU", "num_processes": 4,
    })
    assert cfg.dp_replicate_size == 4 and not cfg.use_fsdp


def test_convert_config_fsdp2_and_unknown_subkeys():
    from accelerate_tpu.commands.convert import convert_reference_config

    cfg, notes = convert_reference_config({
        "distributed_type": "FSDP",
        "num_processes": 4,
        "fsdp_config": {"fsdp_version": 2, "fsdp_reshard_after_forward": False,
                        "fsdp_mystery_knob": 1},
    })
    assert cfg.fsdp_sharding_strategy == "SHARD_GRAD_OP"
    joined = "\n".join(notes)
    assert "fsdp_mystery_knob" in joined  # unknown sub-keys reported


def test_estimate_memory_new_builtin_families(capsys):
    from accelerate_tpu.commands.accelerate_cli import main

    for spec in ("opt:tiny", "neox:tiny", "gpt2:tiny"):
        assert main(["estimate-memory", spec]) == 0
        assert "Memory estimate" in capsys.readouterr().out
