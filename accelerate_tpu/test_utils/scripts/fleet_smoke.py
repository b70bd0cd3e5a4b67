"""`make fleet-smoke`: the whole-cell-loss game day.

Acceptance shape of the fleet pillar (fleet.py over journal.py + serving.py
+ chaos.py) on the 8-device virtual CPU mesh, single-process:

1. A FleetRouter over TWO journaled cells drains a seeded tick-aligned
   Poisson trace with session-affinity routing — the uninterrupted
   reference round.
2. The same trace replays under a seeded chaos schedule that PARTITIONS
   cell 0 mid-trace (it keeps executing — and journaling terminals — but
   its rows stop surfacing) and then hard-kills it (``cell_crash``) before
   the partition heals: the real-world failure sequence that leaves
   journaled-but-unreported completions behind. The router abandons the
   engine the way a process death would (unsealed .open segment, no
   close), ADOPTS the dead cell's journal, and drains it onto cell 1 —
   journaled terminals re-emit their cached rows without re-executing,
   in-flight requests resubmit by ``client_request_id``.
3. Exactly-once + bit-equality: every request ends ``ok`` exactly once
   across the cell loss, token rows bit-equal to the reference; the
   survivor EXECUTED exactly ``N - cached`` requests, and kept ONE decode
   executable with 0 steady recompiles through the drain.
4. The fleet stays operable after the loss: ``scale_up`` registers a
   replacement cell and a cell-granular ``publish`` canary promotes a new
   weights version fleet-wide on filler traffic.
5. A second seeded round replays bit-identically — rows, fleet counters,
   per-cell stats, and the publish decision (wall-clock fields excluded).

See docs/usage_guides/serving.md "Fleet serving".
"""

import os
import sys
import tempfile

import numpy as np

N_REQS = 12
MAX_NEW = 4
PARTITION_TICK = 12
CRASH_TICK = 14
CHAOS_SEED = 29
CHAOS_SCHEDULE = [
    # Unreachable first (terminals pile up journaled but unreported), dead
    # two ticks later — the drain must serve BOTH populations.
    {"point": "cell_partition", "kind": "delay", "tick": PARTITION_TICK,
     "unit": 0, "delay_ticks": 6},
    {"point": "cell_crash", "kind": "crash", "tick": CRASH_TICK, "unit": 0},
]
MAX_TICKS = 600
FILLER_TICKS = 300
PUBLISH_VERSION = 1

_ROW_KEYS = ("status", "new_tokens", "weights_version", "attempt",
             "recovered", "cell", "spilled", "drained_from")
_FLEET_KEYS = ("cells", "healthy", "degraded", "draining", "dead",
               "submitted", "deduped", "routed_affinity", "routed_spilled",
               "shed", "completed", "ok", "drains", "drained_cached",
               "drained_resubmitted", "publishes", "promoted", "rolled_back",
               "quarantined_versions", "scale_ups", "scale_downs")


def _trace(rng):
    """(arrival_tick, prompt) pairs — Poisson inter-arrivals, prompt
    lengths within one prefill chunk so each cell's ladder compiles once."""
    ticks = np.cumsum(1 + rng.poisson(1.0, N_REQS))
    out = []
    for t in ticks:
        n = int(rng.integers(3, 9))
        out.append((int(t), rng.integers(1, 256, (n,), dtype=np.int32)))
    return out


def _strip(row):
    out = {k: row[k] for k in _ROW_KEYS}
    out["tokens"] = np.asarray(row["tokens"]).tolist()
    return out


def _mk_cell(model, root, i):
    from accelerate_tpu import ServingConfig, ServingEngine

    return ServingEngine(model, ServingConfig(
        n_slots=4, max_len=64, prefill_chunks=[8],
        journal_dir=os.path.join(root, f"wal{i}")))


def _run_round(model, root, chaos_schedule=None):
    import jax

    from accelerate_tpu import FaultInjector, FleetRouter

    chaos = (FaultInjector(seed=CHAOS_SEED, schedule=chaos_schedule)
             if chaos_schedule else None)
    router = FleetRouter({f"c{i}": _mk_cell(model, root, i)
                          for i in range(2)}, chaos=chaos)

    arrivals = _trace(np.random.default_rng(7))
    rows, cids = {}, {}
    next_i = 0
    for _tick in range(MAX_TICKS):
        while arrivals and arrivals[0][0] <= _tick:
            _, prompt = arrivals.pop(0)
            cid = f"req-{next_i}"
            cids[cid] = router.submit(
                prompt, max_new_tokens=MAX_NEW, rng=jax.random.key(next_i),
                client_request_id=cid, session_id=f"sess-{next_i}")
            next_i += 1
        router.tick()  # the chaos round kills cell 0 inside this call
        for row in router.poll():
            rows[row["id"]] = row
        if not arrivals and len(rows) >= len(cids):
            break
    assert not arrivals and len(rows) == N_REQS, (
        f"trace never drained: {len(rows)}/{N_REQS} rows")
    trace_per_cell = {
        name: dict(block)
        for name, block in router.stats()["per_cell"].items()
    }

    # -- leg 4 after the loss: replace the capacity, publish fleet-wide ----
    surviving = [n for n, s in router.cell_states().items() if s == "healthy"]
    router.scale_up("c2", engine=_mk_cell(model, root, 2))
    params = model.params
    router.publish(params, weights_version=PUBLISH_VERSION)
    filler = np.random.default_rng(13)
    decided = False
    for i in range(FILLER_TICKS):
        router.submit(filler.integers(1, 256, (6,), dtype=np.int32),
                      max_new_tokens=2, rng=jax.random.key(1000 + i),
                      session_id=f"fill-{i}")
        router.tick()
        router.poll()
        s = router.stats()
        if s["promoted"] + s["rolled_back"] > 0:
            decided = True
            break
    assert decided, "the publish canary window never closed"
    while router.pending:
        router.tick()
        router.poll()

    s = router.stats()
    status = {
        "rows": {cid: _strip(rows[rid]) for cid, rid in sorted(cids.items())},
        "fleet": {k: s[k] for k in _FLEET_KEYS},
        "trace_per_cell": trace_per_cell,
        "per_cell": s["per_cell"],
        "drained": s["drained_cached"] + s["drained_resubmitted"],
    }
    router.close()
    return status


def main() -> int:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import Model
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.utils import set_seed

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8),
                                              dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), probe)

    tmp = tempfile.mkdtemp(prefix="fleet_smoke_")
    ref = _run_round(model, os.path.join(tmp, "ref"))
    g1 = _run_round(model, os.path.join(tmp, "fleet1"), CHAOS_SCHEDULE)
    g2 = _run_round(model, os.path.join(tmp, "fleet2"), CHAOS_SCHEDULE)

    # -- reference: both cells served, nothing shed, publish promoted ------
    all_cids = {f"req-{i}" for i in range(N_REQS)}
    for name, s in (("reference", ref), ("fleet", g1)):
        assert set(s["rows"]) == all_cids, (name, sorted(s["rows"]))
        assert all(r["status"] == "ok" for r in s["rows"].values()), name
        f = s["fleet"]
        assert f["shed"] == 0 and f["deduped"] == 0, (name, f)
        assert f["publishes"] == 1 and f["promoted"] == 1, (name, f)
        assert f["rolled_back"] == 0 and f["quarantined_versions"] == [], name
        assert f["scale_ups"] == 1 and f["cells"] == 3, (name, f)
    ref_cells = {r["cell"] for r in ref["rows"].values()}
    assert ref_cells == {"c0", "c1"}, ref_cells
    assert ref["fleet"]["dead"] == 0 and ref["fleet"]["drains"] == 0

    # -- the cell loss: hard-killed at CRASH_TICK, drained onto c1 ---------
    f = g1["fleet"]
    assert f["dead"] == 1 and f["drains"] == 1, f
    assert g1["per_cell"]["c0"]["state"] == "dead"
    assert f["drained_cached"] >= 1, f      # someone finished on c0 pre-kill
    assert f["drained_resubmitted"] >= 1, f  # someone was mid-flight on c0
    moved = [r for r in g1["rows"].values() if r["drained_from"] == "c0"]
    assert len(moved) == g1["drained"], (len(moved), g1["drained"])
    assert all(r["recovered"] for r in moved)

    # -- exactly-once: the survivor EXECUTED only what the dead cell had
    # not already executed — its pre-partition completions and its cached
    # (journaled-under-partition, never re-run) terminals both count -------
    ran_on_c0 = sum(1 for r in g1["rows"].values() if r["cell"] == "c0")
    executed = g1["trace_per_cell"]["c1"]["requests_completed"]
    assert ran_on_c0 >= f["drained_cached"] >= 1, (ran_on_c0, f)
    assert executed == N_REQS - ran_on_c0, (
        f"survivor executed {executed}, wanted {N_REQS} - {ran_on_c0} "
        "already executed on the dead cell — a cached terminal re-ran")

    # -- bit-equality: cell loss + drain == the uninterrupted reference ----
    for cid in sorted(all_cids):
        assert g1["rows"][cid]["tokens"] == ref["rows"][cid]["tokens"], cid
        assert (g1["rows"][cid]["weights_version"]
                == ref["rows"][cid]["weights_version"]), cid

    # -- the zero-recompile invariant held through drain + publish ---------
    for name, block in g1["per_cell"].items():
        if block["state"] == "dead":
            continue
        assert block["decode_executables"] == 1, (name, block)
        assert block["steady_recompiles"] == 0, (name, block)
        assert block["weights_version"] == PUBLISH_VERSION, (name, block)

    # -- the whole game day replays bit-identically ------------------------
    for key in ("rows", "fleet", "trace_per_cell", "per_cell", "drained"):
        assert g1[key] == g2[key], (
            f"fleet replay diverged on {key!r}:\n  {g1[key]}\n  {g2[key]}")

    print(
        "FLEET SMOKE OK — "
        f"cell c0 partitioned at tick {PARTITION_TICK} and hard-killed at "
        f"tick {CRASH_TICK} with {f['drained_resubmitted']} in flight; the "
        "router adopted its journal and drained onto c1 "
        f"({f['drained_cached']} cached, {f['drained_resubmitted']} "
        f"resubmitted), all {N_REQS} requests ok exactly once, rows "
        "bit-equal to the uninterrupted reference; survivor executed "
        f"{executed} == {N_REQS} - {ran_on_c0} already run on c0 with 1 "
        "decode executable and 0 steady recompiles; scale_up + "
        f"cell-granular publish promoted v{PUBLISH_VERSION} fleet-wide; "
        "replay bit-identical"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
