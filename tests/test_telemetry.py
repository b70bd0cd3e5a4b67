"""Step-level telemetry subsystem (telemetry.py): JSONL schema, recompile
watchdog, collective counters, dataloader-wait accounting, straggler probe,
checkpoint durations — plus the ProfileSession schedule boundaries
(skip_first / wait+warmup / repeat limit) and the logging/tracking
satellites. All CPU-only, tier-1 fast."""

import json
import logging
import os
import time

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# Toy training-loop harness (the test_training.py regression idiom).
# ---------------------------------------------------------------------------


def _setup(seed=0, n=64, dim=8):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = (x @ rng.normal(size=(dim, 1))).astype(np.float32)
    return x, y


class _ArrayDataset:
    def __init__(self, x, y, item_delay_s: float = 0.0):
        self.x, self.y = x, y
        self.item_delay_s = item_delay_s

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        if self.item_delay_s:
            time.sleep(self.item_delay_s)
        return {"x": self.x[i], "y": self.y[i]}


class _Spec:
    def __init__(self, dataset, batch_size):
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = None
        self.drop_last = False


def _linear_model():
    import flax.linen as nn

    class Linear(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)

    return Linear()


def _accelerator(tmp_path, item_delay_s=0.0, dataloader_config=None, **tkw):
    import jax
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.utils import TelemetryKwargs, set_seed

    set_seed(0)
    kwargs = dict(sync_timing=True, straggler_probe_every=0, log_every=0)
    kwargs.update(tkw)
    acc = Accelerator(
        project_dir=str(tmp_path),
        dataloader_config=dataloader_config,
        kwargs_handlers=[TelemetryKwargs(**kwargs)],
    )
    x, y = _setup()
    module = _linear_model()
    model = Model.from_flax(module, jax.random.key(0), x[:1])
    model, opt, dl = acc.prepare(
        model, optax.sgd(0.1), _Spec(_ArrayDataset(x, y, item_delay_s), 16)
    )

    def loss_fn(params, batch):
        pred = module.apply({"params": params}, batch["x"])
        return ((pred - batch["y"]) ** 2).mean()

    return acc, dl, loss_fn, (x, y)


def _run_steps(acc, dl, loss_fn, steps):
    step = acc.prepare_train_step(loss_fn)
    state = acc.train_state
    done = 0
    while done < steps:
        for batch in dl:
            state, metrics = step(state, batch)
            done += 1
            if done >= steps:
                break
    return step, state


def _records(tmp_path, rank=0):
    path = os.path.join(str(tmp_path), "telemetry", f"rank_{rank}.jsonl")
    assert os.path.exists(path), f"no telemetry report at {path}"
    with open(path) as fh:
        return [json.loads(line) for line in fh]


def _global_batch(acc, x, y, n):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(acc.mesh, PartitionSpec(("dp_replicate", "dp_shard")))
    return {
        "x": jax.device_put(x[:n], sharding),
        "y": jax.device_put(y[:n], sharding),
    }


# ---------------------------------------------------------------------------
# Tentpole: TelemetryRecorder
# ---------------------------------------------------------------------------


def test_step_records_schema_and_summary(tmp_path):
    acc, dl, loss_fn, _ = _accelerator(tmp_path)
    _run_steps(acc, dl, loss_fn, 8)
    acc.end_training()
    records = _records(tmp_path)
    steps = [r for r in records if r["event"] == "step"]
    assert len(steps) == 8
    required = {
        "step", "time", "wall_s", "data_wait_s", "samples", "samples_per_s",
        "tokens_per_s", "ema_samples_per_s", "ema_tokens_per_s", "collectives",
        "hbm_bytes_in_use", "hbm_peak_bytes", "recompiles", "loss",
    }
    for r in steps:
        assert required <= r.keys(), f"missing {required - r.keys()}"
        assert r["wall_s"] > 0
        assert r["samples"] == 16  # loader batch size = global batch dim
    # Step counter is 1-based and monotonic.
    assert [r["step"] for r in steps] == list(range(1, 9))
    summary = records[-1]
    assert summary["event"] == "summary"
    assert summary["steps"] == 8
    assert summary["step_time_p50_s"] <= summary["step_time_p90_s"]
    assert summary["step_time_mean_s"] > 0


def test_recompile_watchdog_fires_once_on_shape_change(tmp_path, caplog):
    acc, dl, loss_fn, (x, y) = _accelerator(tmp_path)
    step, state = _run_steps(acc, dl, loss_fn, 6)
    before = acc.telemetry.recompiles
    with caplog.at_level(logging.WARNING):
        state, _ = step(state, _global_batch(acc, x, y, 8))
        state, _ = step(state, _global_batch(acc, x, y, 8))  # same shape: no new warning
    acc.end_training()
    assert acc.telemetry.recompiles >= before + 1
    watchdog = [
        r for r in caplog.records if "jitted step recompiled" in r.getMessage()
    ]
    assert len(watchdog) == 1, [r.getMessage() for r in watchdog]
    assert "float32[8, 8]" in watchdog[0].getMessage()  # offending digest
    recs = [r for r in _records(tmp_path) if r["event"] == "recompile"]
    shape_changes = [r for r in recs if r["reason"] == "batch shape/dtype change"]
    assert len(shape_changes) == 1
    assert "batch_digest" in shape_changes[0]
    # The cumulative counter in subsequent step records reflects it.
    steps = [r for r in _records(tmp_path) if r["event"] == "step"]
    assert steps[-1]["recompiles"] > steps[0]["recompiles"]


def test_settling_recompile_counted_but_not_warned(tmp_path, caplog):
    """The known cache 1->2 growth on the second call (the state comes back
    from the first in GSPMD's shardings) is recorded but must not cry wolf."""
    acc, dl, loss_fn, _ = _accelerator(tmp_path)
    with caplog.at_level(logging.WARNING):
        _run_steps(acc, dl, loss_fn, 6)
    acc.end_training()
    assert not any("recompiled" in r.getMessage() for r in caplog.records)
    recs = [r for r in _records(tmp_path) if r["event"] == "recompile"]
    assert all("expected once" in r["reason"] for r in recs)


def test_collective_counters_count_and_bytes(tmp_path):
    from accelerate_tpu.utils.operations import collective_counters

    acc, dl, loss_fn, _ = _accelerator(tmp_path)
    payload = np.ones((4, 2), dtype=np.float32)
    acc.gather(payload)
    acc.reduce(payload)
    acc.pad_across_processes(payload)
    from accelerate_tpu.utils import broadcast

    broadcast(payload)
    snap = collective_counters.snapshot()
    for op in ("gather", "reduce", "pad_across_processes", "broadcast"):
        assert snap[op]["count"] == 1, snap
        assert snap[op]["bytes"] == payload.nbytes, snap
    # The tally rides in every step record.
    _run_steps(acc, dl, loss_fn, 2)
    acc.end_training()
    steps = [r for r in _records(tmp_path) if r["event"] == "step"]
    assert steps[-1]["collectives"]["gather"]["count"] >= 1
    # Recorder teardown disables the process-global counters again.
    assert not collective_counters.enabled


def test_collective_counters_disabled_without_telemetry():
    from accelerate_tpu.utils.operations import collective_counters

    from accelerate_tpu import Accelerator

    Accelerator()
    collective_counters.enabled = False
    collective_counters.reset()
    from accelerate_tpu.utils import gather

    gather(np.ones((2,), dtype=np.float32))
    assert collective_counters.snapshot() == {}


def test_dataloader_wait_accounting(tmp_path):
    from accelerate_tpu.utils import DataLoaderConfiguration

    # prefetch_size=0: collation happens synchronously inside next(), so the
    # per-item sleep must show up as data wait.
    acc, dl, loss_fn, _ = _accelerator(
        tmp_path,
        item_delay_s=0.002,
        dataloader_config=DataLoaderConfiguration(prefetch_size=0),
    )
    _run_steps(acc, dl, loss_fn, 4)
    acc.end_training()
    steps = [r for r in _records(tmp_path) if r["event"] == "step"]
    # 16 items * 2ms each >= 32ms per batch; generous floor for CI jitter.
    assert max(r["data_wait_s"] for r in steps) > 0.01
    summary = [r for r in _records(tmp_path) if r["event"] == "summary"][0]
    assert summary["data_wait_mean_s"] > 0


def test_straggler_probe_records_skew(tmp_path):
    acc, dl, loss_fn, _ = _accelerator(tmp_path, straggler_probe_every=2)
    _run_steps(acc, dl, loss_fn, 4)
    acc.end_training()
    probes = [r for r in _records(tmp_path) if r["event"] == "straggler_probe"]
    assert len(probes) == 2  # steps 2 and 4
    for p in probes:
        assert p["step_time_max_s"] >= p["step_time_min_s"] > 0
        assert p["skew"] >= 0
        assert len(p["rank_times_s"]) == acc.num_processes


def test_checkpoint_durations_recorded(tmp_path):
    acc, dl, loss_fn, _ = _accelerator(tmp_path)
    _run_steps(acc, dl, loss_fn, 2)
    ckpt = str(tmp_path / "ckpt")
    acc.save_state(ckpt)
    acc.load_state(ckpt)
    acc.end_training()
    records = _records(tmp_path)
    saves = [r for r in records if r["event"] == "checkpoint_save"]
    loads = [r for r in records if r["event"] == "checkpoint_load"]
    assert len(saves) == 1 and len(loads) == 1
    assert saves[0]["seconds"] > 0 and saves[0]["dir"] == ckpt
    assert loads[0]["seconds"] > 0
    summary = records[-1]
    assert summary["checkpoint_events"] == 2


def test_imperative_path_records_optimizer_steps(tmp_path):
    acc, dl, loss_fn, _ = _accelerator(tmp_path)
    opt = acc._optimizers[0]
    done = 0
    for batch in dl:
        with acc.accumulate():
            acc.backward(loss_fn, batch)
            opt.step()
            opt.zero_grad()
        done += 1
        if done >= 3:
            break
    acc.end_training()
    steps = [r for r in _records(tmp_path) if r["event"] == "optimizer_step"]
    assert len(steps) == 3
    for r in steps:
        assert r["backward_s"] > 0
        assert r["apply_s"] > 0
        assert r["wall_s"] >= r["backward_s"]


def test_disabled_by_default_no_files_no_recorder(tmp_path):
    import jax
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.utils import set_seed

    set_seed(0)
    acc = Accelerator(project_dir=str(tmp_path))
    assert acc.telemetry is None
    x, y = _setup()
    module = _linear_model()
    model = Model.from_flax(module, jax.random.key(0), x[:1])
    model, opt, dl = acc.prepare(model, optax.sgd(0.1), _Spec(_ArrayDataset(x, y), 16))

    def loss_fn(params, batch):
        pred = module.apply({"params": params}, batch["x"])
        return ((pred - batch["y"]) ** 2).mean()

    step = acc.prepare_train_step(loss_fn)
    state = acc.train_state
    for batch in dl:
        state, _ = step(state, batch)
        break
    acc.end_training()
    assert not os.path.exists(os.path.join(str(tmp_path), "telemetry"))


def test_tracker_forwarding(tmp_path):
    """Every log_every steps the summary goes through Accelerator.log into
    the tracker stack under the telemetry/ prefix."""
    acc, dl, loss_fn, _ = _accelerator(tmp_path, log_every=2)

    class _Sink:
        name = "sink"
        requires_logging_directory = False
        logged = []

        def store_init_configuration(self, values):
            pass

        def log(self, values, step=None, **kwargs):
            self.logged.append((step, values))

        def finish(self):
            pass

    sink = _Sink()
    acc.trackers = [sink]
    _run_steps(acc, dl, loss_fn, 5)
    acc.end_training()
    assert [s for s, _ in sink.logged] == [2, 4]
    for _, values in sink.logged:
        assert "telemetry/step_time_s" in values
        assert "telemetry/recompiles" in values


# ---------------------------------------------------------------------------
# ProfileSession schedule boundaries (satellite coverage)
# ---------------------------------------------------------------------------


def _stubbed_session(tmp_path, schedule):
    from unittest import mock

    import accelerate_tpu.utils.profiling as P
    from accelerate_tpu.utils import ProfileKwargs

    events = []
    handler = ProfileKwargs(schedule_option=schedule, output_trace_dir=str(tmp_path))
    patches = (
        mock.patch.object(P.jax.profiler, "start_trace", lambda d: events.append(("start", d))),
        mock.patch.object(P.jax.profiler, "stop_trace", lambda: events.append(("stop",))),
    )
    return P.ProfileSession(handler, str(tmp_path)), events, patches


def test_profile_schedule_skip_first(tmp_path):
    """skip_first delays the FIRST cycle only; windows land on the same
    relative steps afterwards (torch.profiler semantics)."""
    s, events, patches = _stubbed_session(
        tmp_path, {"skip_first": 3, "wait": 1, "warmup": 1, "active": 2, "repeat": 1}
    )
    with patches[0], patches[1]:
        s.enter()
        for i in range(1, 12):
            events.append(("work", i))
            s.step()
        s.exit()
    i0 = events.index(("start", str(tmp_path / "cycle_0")))
    j0 = events.index(("stop",))
    # skip 3, wait 1, warmup 1 → active steps are 6 and 7.
    assert [e[1] for e in events[i0:j0] if e[0] == "work"] == [6, 7]


def test_profile_schedule_repeat_limit(tmp_path):
    """repeat=N caps the number of traced cycles no matter how many steps run."""
    s, events, patches = _stubbed_session(
        tmp_path, {"wait": 0, "warmup": 1, "active": 1, "repeat": 2}
    )
    with patches[0], patches[1]:
        s.enter()
        for i in range(1, 21):
            events.append(("work", i))
            s.step()
        s.exit()
    assert sum(1 for e in events if e[0] == "start") == 2
    assert s.cycles_done == 2
    assert s.trace_dirs == [str(tmp_path / "cycle_0"), str(tmp_path / "cycle_1")]


def test_profile_schedule_skip_first_with_zero_wait_warmup(tmp_path):
    """skip_first > 0 with wait+warmup == 0: the first active window starts
    right after the skipped steps, not at enter()."""
    s, events, patches = _stubbed_session(
        tmp_path, {"skip_first": 2, "active": 2, "repeat": 1}
    )
    with patches[0], patches[1]:
        s.enter()
        for i in range(1, 8):
            events.append(("work", i))
            s.step()
        s.exit()
    starts = [e for e in events if e[0] == "start"]
    assert len(starts) == 1
    i0 = events.index(("start", str(tmp_path / "cycle_0")))
    j0 = events.index(("stop",))
    assert [e[1] for e in events[i0:j0] if e[0] == "work"] == [3, 4]


# ---------------------------------------------------------------------------
# Logging satellites: warning_once + root-logger hygiene
# ---------------------------------------------------------------------------


def test_warning_once_dedups_and_handles_unhashable(caplog):
    from accelerate_tpu import PartialState
    from accelerate_tpu.logging import get_logger

    PartialState()
    logger = get_logger("test_warning_once_dedup")
    with caplog.at_level(logging.WARNING, logger="test_warning_once_dedup"):
        logger.warning_once("dup message %s", 1)
        logger.warning_once("dup message %s", 1)
        logger.warning_once("dup message %s", 2)  # different args: new warning
        # Unhashable argument must not crash (the lru_cache version did).
        logger.warning_once("unhashable %s", {"a": [1, 2]})
        logger.warning_once("unhashable %s", {"a": [1, 2]})
    messages = [r.getMessage() for r in caplog.records]
    assert messages.count("dup message 1") == 1
    assert messages.count("dup message 2") == 1
    assert messages.count("unhashable {'a': [1, 2]}") == 1


def test_warning_once_shared_across_adapters(caplog):
    """Two adapters for the same message dedup against ONE module-level set —
    no per-adapter lru_cache leak."""
    from accelerate_tpu import PartialState
    from accelerate_tpu.logging import get_logger

    PartialState()
    a = get_logger("test_warning_once_shared")
    b = get_logger("test_warning_once_shared")
    assert a is not b
    with caplog.at_level(logging.WARNING, logger="test_warning_once_shared"):
        a.warning_once("shared-once")
        b.warning_once("shared-once")
    assert sum(1 for r in caplog.records if r.getMessage() == "shared-once") == 1


def test_get_logger_does_not_clobber_root_level():
    from accelerate_tpu.logging import get_logger

    root = logging.getLogger()
    before = root.level
    logger = get_logger("test_root_untouched", log_level="DEBUG")
    assert logging.getLogger("test_root_untouched").level == logging.DEBUG
    assert root.level == before


# ---------------------------------------------------------------------------
# Tracking satellite: JSONTracker crash-safety
# ---------------------------------------------------------------------------


def test_json_tracker_flushes_each_record(tmp_path):
    from accelerate_tpu import PartialState
    from accelerate_tpu.tracking import JSONTracker

    PartialState()
    t = JSONTracker("run", logging_dir=str(tmp_path))
    t.store_init_configuration({"lr": 0.1})
    t.log({"loss": 1.0}, step=1)
    t.log({"loss": 0.5}, step=2)
    # Read WITHOUT finish(): a preempted run must still have every record.
    with open(t.path) as fh:
        lines = [json.loads(l) for l in fh]
    assert len(lines) == 3
    assert lines[0]["event"] == "config"
    assert [l["step"] for l in lines[1:]] == [1, 2]
    t.finish()
