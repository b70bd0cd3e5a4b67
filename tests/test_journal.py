"""Crash-durable request journal (journal.py) + ServingEngine.recover().

Two layers:

- RequestJournal internals: the checksummed line codec, torn-tail
  truncation, corrupt-line skip-with-count, segment rotation, compaction
  (terminal rows survive, working records of finished requests retire,
  unfinished requests pass through verbatim), the fsync policy knobs, and
  the chaos torn_write hooks at journal_append / journal_compact.
- Engine integration: submit() journaling + client_request_id idempotency
  dedupe, exactly-once crash-restart recovery (cached terminal rows never
  re-executed, in-flight requests replayed bit-equal without spending the
  retry budget), monotonic deadline re-anchoring across the restart, and
  the attempt/recovered poll-row fields.

All CPU-only, tier-1 fast. The full-stack crash (a REAL os._exit mid-trace
plus supervisor relaunch) lives in `make gameday-smoke`
(test_utils/scripts/gameday_smoke.py); here the "crash" is an engine simply
abandoned without close() — same on-disk state, no subprocess.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import (
    FaultInjector,
    JournalAdoptionError,
    Model,
    RequestJournal,
    ServingConfig,
    ServingEngine,
)
from accelerate_tpu.journal import JOURNAL_FSYNC_POLICIES, _decode, _encode
from accelerate_tpu.utils import set_seed


@pytest.fixture(scope="module")
def llama():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8),
                                              dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), probe)
    return cfg, model


def _prompts(cfg, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32)
            for n in lengths]


def _drain(engine, guard=5000):
    results = {}
    ticks = 0
    while engine.pending:
        engine.tick()
        for r in engine.poll():
            results[r["id"]] = r
        ticks += 1
        assert ticks < guard, "drain guard tripped"
    for r in engine.poll():
        results[r["id"]] = r
    return results


# ---------------------------------------------------------------------------
# RequestJournal internals
# ---------------------------------------------------------------------------


def test_codec_roundtrip_and_corruption():
    rec = {"t": "admit", "rid": 3, "tokens": [1, 2, 3]}
    line = _encode(rec)
    assert line.endswith("\n")
    assert _decode(line.rstrip("\n")) == rec
    # Any byte flip fails the crc.
    assert _decode(line.rstrip("\n").replace("3", "4", 1)) is None
    assert _decode("nonsense") is None
    assert _decode("deadbeef not-json") is None


def test_append_replay_roundtrip(tmp_path):
    j = RequestJournal(str(tmp_path), fsync="os")
    recs = [{"t": "admit", "rid": i, "tokens": [i]} for i in range(5)]
    for r in recs:
        j.append(r)
    j.close()  # seals the active segment
    j2 = RequestJournal(str(tmp_path))
    out, scan = j2.replay()
    assert out == recs
    assert scan["records"] == 5 and scan["segments"] == 1
    assert scan["torn_tails"] == 0 and scan["corrupt_skipped"] == 0
    # New appends land in a FRESH segment index — no collision.
    j2.append({"t": "admit", "rid": 9})
    j2.close()
    out2, scan2 = RequestJournal(str(tmp_path)).replay()
    assert len(out2) == 6 and scan2["segments"] == 2


def test_torn_tail_truncated_and_repaired(tmp_path):
    j = RequestJournal(str(tmp_path), fsync="os")
    j.append({"t": "admit", "rid": 0})
    j.append({"t": "admit", "rid": 1})
    j.close()
    # Simulate the crash-interrupted write: a partial final line.
    path = [p for _, p in j._segments()][0]
    with open(path, "a", encoding="utf-8") as f:
        f.write(_encode({"t": "admit", "rid": 2})[:17])  # no newline
    j2 = RequestJournal(str(tmp_path))
    out, scan = j2.replay()
    assert [r["rid"] for r in out] == [0, 1]
    assert scan["torn_tails"] == 1 and scan["corrupt_skipped"] == 0
    # replay() repaired the file in place: clean on the next read.
    out2, scan2 = RequestJournal(str(tmp_path)).replay()
    assert [r["rid"] for r in out2] == [0, 1] and scan2["torn_tails"] == 0


def test_corrupt_line_skipped_with_count(tmp_path):
    j = RequestJournal(str(tmp_path), fsync="os")
    for i in range(3):
        j.append({"t": "admit", "rid": i})
    j.close()
    path = [p for _, p in j._segments()][0]
    lines = open(path, encoding="utf-8").read().splitlines(keepends=True)
    lines[1] = "0badc0de " + lines[1].split(" ", 1)[1]  # break the middle crc
    with open(path, "w", encoding="utf-8") as f:
        f.writelines(lines)
    out, scan = RequestJournal(str(tmp_path)).replay()
    assert [r["rid"] for r in out] == [0, 2]  # the neighbors survive
    assert scan["corrupt_skipped"] == 1 and scan["torn_tails"] == 0


def test_fsync_policy_knobs(tmp_path):
    with pytest.raises(ValueError):
        RequestJournal(str(tmp_path / "x"), fsync="sometimes")
    with pytest.raises(ValueError):
        RequestJournal(str(tmp_path / "x"), segment_records=0)
    assert JOURNAL_FSYNC_POLICIES == ("every_record", "every_tick", "os")

    j = RequestJournal(str(tmp_path / "rec"), fsync="every_record")
    j.append({"t": "admit", "rid": 0})
    j.append({"t": "admit", "rid": 1})
    assert j.stats()["syncs"] == 2  # one fsync per append
    j.tick_flush()
    assert j.stats()["syncs"] == 2  # nothing buffered

    j = RequestJournal(str(tmp_path / "tick"), fsync="every_tick")
    j.append({"t": "admit", "rid": 0})
    j.append({"t": "admit", "rid": 1})
    assert j.stats()["syncs"] == 0  # buffered
    j.tick_flush()
    assert j.stats()["syncs"] == 1  # one fsync per tick
    j.tick_flush()
    assert j.stats()["syncs"] == 1  # not dirty: no-op

    j = RequestJournal(str(tmp_path / "os"), fsync="os")
    j.append({"t": "admit", "rid": 0})
    j.tick_flush()
    assert j.stats()["syncs"] == 0  # flush to page cache, never fsync
    # The data still reached the OS: another process/object can read it.
    out, _ = RequestJournal(str(tmp_path / "os")).replay()
    assert out == [{"t": "admit", "rid": 0}]


def test_rotation_and_compaction_preserve_unfinished(tmp_path):
    # segment_records=4 forces rotation (+ compaction) mid-stream.
    j = RequestJournal(str(tmp_path), fsync="os", segment_records=4)
    # rid 0 finishes; rid 1 stays in flight.
    j.append({"t": "admit", "rid": 0, "cid": "a", "tokens": [1]})
    j.append({"t": "admit", "rid": 1, "cid": "b", "tokens": [2]})
    j.append({"t": "bind", "rid": 0, "weights_version": 0})
    j.append({"t": "bind", "rid": 1, "weights_version": 0})
    j.append({"t": "progress", "tick": 0, "toks": {"0": [7], "1": [8]}})
    j.append({"t": "terminal", "rid": 0, "cid": "a", "status": "ok",
              "row": [1, 7]})
    j.append({"t": "admit", "rid": 2, "tokens": [3]})
    j.append({"t": "admit", "rid": 3, "tokens": [4]})  # triggers 2nd seal
    j.close()
    st = j.stats()
    assert st["rotations"] >= 2 and st["compactions"] >= 1
    assert st["records_retired"] > 0
    out, _ = RequestJournal(str(tmp_path)).replay()
    by_type = {}
    for r in out:
        by_type.setdefault(r["t"], []).append(r)
    # rid 0 retired: its admit/bind gone, its TERMINAL row kept (dedupe +
    # cached replies must survive compaction).
    assert sorted(r["rid"] for r in by_type["admit"]) == [1, 2, 3]
    assert [r["rid"] for r in by_type["bind"]] == [1]
    assert [r["rid"] for r in by_type["terminal"]] == [0]
    assert by_type["terminal"][0]["row"] == [1, 7]
    # The progress record dropped only the retired rid's tokens.
    assert by_type["progress"][0]["toks"] == {"1": [8]}
    assert j.stats()["pending"] == 3


def test_chaos_torn_append_rewrites_record(tmp_path):
    chaos = FaultInjector(seed=1, schedule=[
        {"point": "journal_append", "kind": "torn_write", "tick": 0,
         "unit": 1}])
    j = RequestJournal(str(tmp_path), fsync="os", chaos=chaos)
    j.append({"t": "admit", "rid": 0}, unit=0)
    j.append({"t": "admit", "rid": 1}, unit=1)  # torn, then re-written whole
    j.append({"t": "admit", "rid": 2}, unit=2)
    j.close()
    assert j.stats()["torn_writes"] == 1
    out, scan = RequestJournal(str(tmp_path)).replay()
    # Durability holds — every record replays — and the garbage fragment
    # exercised the checksum-skip path.
    assert [r["rid"] for r in out] == [0, 1, 2]
    assert scan["corrupt_skipped"] == 1


def test_chaos_torn_compact_aborts_cleanly(tmp_path):
    chaos = FaultInjector(seed=1, schedule=[
        {"point": "journal_compact", "kind": "torn_write", "tick": 0}])
    j = RequestJournal(str(tmp_path), fsync="os", segment_records=3,
                       chaos=chaos)
    j.append({"t": "admit", "rid": 0, "tokens": [1]})
    j.append({"t": "terminal", "rid": 0, "status": "ok", "row": [1]})
    j.append({"t": "admit", "rid": 1, "tokens": [2]})  # seal -> compact(torn)
    j.close()
    st = j.stats()
    assert st["compact_aborts"] == 1 and st["compactions"] == 0
    assert not os.path.exists(os.path.join(str(tmp_path), "compact.jsonl.tmp"))
    # The sealed segments are untouched: everything still replays.
    out, _ = RequestJournal(str(tmp_path)).replay()
    assert [r["rid"] for r in out] == [0, 0, 1]
    # A later compaction (no fault scheduled) succeeds over the same dir.
    j2 = RequestJournal(str(tmp_path))
    j2.replay()
    assert j2.compact() > 0


# ---------------------------------------------------------------------------
# Cross-process adoption (PR 18): exactly one party drains a dead WAL
# ---------------------------------------------------------------------------


def test_adoption_sentinel_refuses_double_adoption(tmp_path):
    """The double-adoption refusal regression: a recovering fleet router
    and a restarting supervisor racing for the same dead engine's journal
    must resolve to exactly ONE adopter — double adoption is double
    execution."""
    d = str(tmp_path)
    j1 = RequestJournal.adopt(d, "fleet-router:tick=3:cell=cell0")
    assert j1.adopted
    with pytest.raises(JournalAdoptionError, match="already adopted"):
        RequestJournal.adopt(d, "supervisor:pid=999")
    # The sentinel names the holder for the loser's error path.
    assert RequestJournal(d).adoption_holder()["owner"].startswith(
        "fleet-router")
    # The sentinel is invisible to segment scans and replay.
    j1.append({"t": "admit", "rid": 0})
    j1.close()  # close releases the claim
    assert RequestJournal(d).adoption_holder() is None
    out, scan = RequestJournal(d).replay()
    assert [r["rid"] for r in out] == [0] and scan["segments"] == 1
    # Released: the next adopter wins; force= evicts a stale claim.
    j2 = RequestJournal.adopt(d, "supervisor:pid=999")
    j3 = RequestJournal.adopt(d, "forced", force=True)
    assert j3.adopted
    j2.release_adoption()  # holder already evicted: a no-op either way
    j3.release_adoption()


def test_recover_over_foreign_dir_takes_the_adoption_lock(llama, tmp_path):
    """``recover(journal_dir=)`` on a dir some DEAD engine owned claims the
    sentinel: a second engine trying the same dir refuses, and a restart
    over its own configured dir refuses while a router holds the claim."""
    cfg, model = llama
    wal = str(tmp_path / "wal")
    mk = lambda **kw: ServingConfig(  # noqa: E731
        n_slots=2, max_len=32, prefill_chunks=[4, 8], **kw)
    (p,) = _prompts(cfg, [5])
    e1 = ServingEngine(model, mk(journal_dir=wal))
    rid = e1.submit(p, max_new_tokens=3, client_request_id="req-0")
    e1.journal.tick_flush()
    del e1  # dead: unsealed .open segment, no sentinel

    # The router-style takeover: a journal-less engine adopts the dir.
    e2 = ServingEngine(model, mk())
    assert e2.recover(journal_dir=wal)["recovered_inflight"] == 1
    assert e2.journal.adopted
    # A second adopter — engine or raw journal — refuses while it's held.
    e3 = ServingEngine(model, mk())
    with pytest.raises(JournalAdoptionError, match="already adopted"):
        e3.recover(journal_dir=wal)
    # A restarting supervisor's engine over its OWN configured dir also
    # refuses: these requests are being drained elsewhere.
    e4 = ServingEngine(model, mk(journal_dir=wal))
    with pytest.raises(JournalAdoptionError, match="drained elsewhere"):
        e4.recover()
    # The adopter drains the replay bit-for-bit as usual...
    rows = _drain(e2)
    assert rows[rid]["status"] == "ok" and rows[rid]["recovered"] is True
    # ...and close() releases the claim for the next owner.
    e2.close()
    assert RequestJournal(wal).adoption_holder() is None


# ---------------------------------------------------------------------------
# Compaction racing a crash (PR 18): only the happy path was pinned before
# ---------------------------------------------------------------------------


def test_compaction_commit_crash_duplicates_replay_exactly_once(
        llama, tmp_path, monkeypatch):
    """A crash BETWEEN compaction's two commit steps (the merged segment
    has replaced sealed[0], the stale sealed[1:] not yet unlinked) leaves
    duplicate records on disk — journal.py documents them as idempotently
    re-read. Pin that: recovery over the duplicated WAL is still
    exactly-once, bit-equal."""
    cfg, model = llama
    wal = str(tmp_path / "wal")
    # segments of 3 records: where they fall depends on the ticks the
    # schedule takes, and at 3 a compaction lands while req-3's admit is live
    mk = lambda: ServingConfig(  # noqa: E731
        n_slots=2, max_len=32, prefill_chunks=[4, 8],
        journal_dir=wal, journal_segment_records=3)
    prompts = _prompts(cfg, [5, 7, 6, 8])

    real_remove = os.remove

    def crashy_remove(path):
        # The unlink step of compaction "crashes": stale sealed segments
        # stay on disk. compact() treats the OSError as best-effort.
        if os.path.basename(path).startswith("wal_") and wal in path:
            raise OSError("injected crash between commit steps")
        real_remove(path)

    monkeypatch.setattr(os, "remove", crashy_remove)
    e1 = ServingEngine(model, mk())
    ref = {}
    for i, p in enumerate(prompts[:3]):
        ref[i] = e1.submit(p, max_new_tokens=4, client_request_id=f"req-{i}")
    done = _drain(e1)
    assert e1.stats()["journal"]["compactions"] >= 1
    rid_inflight = e1.submit(prompts[3], max_new_tokens=4,
                             client_request_id="req-3")
    e1.journal.tick_flush()
    del e1  # crash: duplicates + an in-flight admit on disk

    # The duplicates are really there: more admit records than rids.
    recs, _ = RequestJournal(wal).replay()
    admit_rids = [r["rid"] for r in recs if r["t"] == "admit"]
    assert len(admit_rids) > len(set(admit_rids))

    e2 = ServingEngine(model, mk())
    summary = e2.recover()
    # Exactly-once despite the duplicated records: each terminal re-emits
    # ONE cached row, the in-flight request replays ONCE.
    assert summary["recovered_terminal"] == 3
    assert summary["recovered_inflight"] == 1
    rows = {r["id"]: r for r in e2.poll()}
    assert sorted(rows) == sorted(ref.values())
    for i in (0, 1, 2):
        np.testing.assert_array_equal(rows[ref[i]]["tokens"],
                                      done[ref[i]]["tokens"])
    rows.update(_drain(e2))
    assert rows[rid_inflight]["status"] == "ok"
    assert e2.stats()["requests_completed"] == 1  # only the replay ran


def test_segment_sealed_mid_compaction_replays_exactly_once(llama, tmp_path):
    """The other side of the race: segments keep SEALING while every
    compaction pass aborts mid-write (chaos torn_write at journal_compact),
    then the process dies. The accumulated sealed-but-never-compacted
    history must still recover exactly-once."""
    cfg, model = llama
    wal = str(tmp_path / "wal")
    chaos = FaultInjector(seed=2, rates={"journal_compact": {"torn_write": 1.0}})
    mk = lambda ch: ServingConfig(  # noqa: E731
        n_slots=2, max_len=32, prefill_chunks=[4, 8],
        journal_dir=wal, journal_segment_records=4)
    e1 = ServingEngine(model, mk(chaos), chaos=chaos)
    prompts = _prompts(cfg, [5, 7, 6, 8])
    ref = {}
    for i, p in enumerate(prompts[:3]):
        ref[i] = e1.submit(p, max_new_tokens=4, client_request_id=f"req-{i}")
    done = _drain(e1)
    js = e1.stats()["journal"]
    assert js["compact_aborts"] >= 1 and js["compactions"] == 0
    assert js["rotations"] >= 2  # segments sealed while compaction failed
    rid_inflight = e1.submit(prompts[3], max_new_tokens=4,
                             client_request_id="req-3")
    e1.journal.tick_flush()
    del e1  # crash mid-flight, un-compacted multi-segment history behind

    e2 = ServingEngine(model, mk(None))
    summary = e2.recover()
    assert summary["recovered_terminal"] == 3
    assert summary["recovered_inflight"] == 1
    assert summary["segments"] >= 3
    rows = {r["id"]: r for r in e2.poll()}
    for i in range(3):
        np.testing.assert_array_equal(rows[ref[i]]["tokens"],
                                      done[ref[i]]["tokens"])
    rows.update(_drain(e2))
    assert rows[rid_inflight]["status"] == "ok"
    assert e2.stats()["requests_completed"] == 1
    # The un-compacted history compacts fine under the new owner.
    e2.journal.replay()
    assert e2.journal.compact() > 0


# ---------------------------------------------------------------------------
# ServingEngine integration
# ---------------------------------------------------------------------------


def test_submit_dedupes_on_client_request_id(llama, tmp_path):
    cfg, model = llama
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8],
                             journal_dir=str(tmp_path / "wal")))
    (p,) = _prompts(cfg, [5])
    rid = engine.submit(p, max_new_tokens=3, client_request_id="req-0")
    assert engine.submit(p, max_new_tokens=3,
                         client_request_id="req-0") == rid  # queued: same id
    res = _drain(engine)
    assert len(res) == 1 and res[rid]["status"] == "ok"
    # Finished: the duplicate re-emits the CACHED row, nothing re-runs.
    completed = engine.stats()["requests_completed"]
    assert engine.submit(p, max_new_tokens=3,
                         client_request_id="req-0") == rid
    rows = engine.poll()
    assert len(rows) == 1 and rows[0]["id"] == rid
    np.testing.assert_array_equal(rows[0]["tokens"], res[rid]["tokens"])
    assert engine.stats()["requests_completed"] == completed
    assert engine.stats()["journal"]["deduped"] == 2


def test_recover_exactly_once_and_bit_equal(llama, tmp_path):
    cfg, model = llama
    prompts = _prompts(cfg, [5, 9, 7])
    mk = lambda sub: ServingConfig(  # noqa: E731
        n_slots=2, max_len=32, prefill_chunks=[4, 8],
        journal_dir=str(tmp_path / sub))

    # Reference: the same trace, never interrupted.
    ref_engine = ServingEngine(model, mk("ref"))
    ref = {}
    for i, p in enumerate(prompts):
        ref[i] = ref_engine.submit(p, max_new_tokens=4,
                                   client_request_id=f"req-{i}")
    ref_rows = _drain(ref_engine)

    # "Crashing" run: finish req-0, leave req-1/req-2 queued, then abandon
    # the engine without close() — exactly the state a process death leaves.
    e1 = ServingEngine(model, mk("wal"))
    r0 = e1.submit(prompts[0], max_new_tokens=4, client_request_id="req-0")
    ticks = 0
    done = {}
    while r0 not in done:
        e1.tick()
        done.update({r["id"]: r for r in e1.poll()})
        ticks += 1
        assert ticks < 500
    e1.submit(prompts[1], max_new_tokens=4, client_request_id="req-1")
    e1.submit(prompts[2], max_new_tokens=4, client_request_id="req-2")
    e1.journal.tick_flush()
    del e1  # no close(): the .open segment's torn state is the test

    e2 = ServingEngine(model, mk("wal"))
    summary = e2.recover()
    assert summary["recovered_terminal"] == 1
    assert summary["recovered_inflight"] == 2
    # The cached terminal row surfaces through poll(), flagged recovered,
    # and was NOT re-executed.
    rows = {r["id"]: r for r in e2.poll()}
    assert rows[r0]["status"] == "ok" and rows[r0]["recovered"] is True
    np.testing.assert_array_equal(rows[r0]["tokens"], done[r0]["tokens"])
    assert e2.stats()["requests_completed"] == 0
    # A duplicate submit for the completed request dedupes post-crash.
    assert e2.submit(prompts[0], max_new_tokens=4,
                     client_request_id="req-0") == r0
    assert e2.stats()["journal"]["deduped"] == 1
    # The in-flight requests replay BIT-EQUAL to the uninterrupted
    # reference, without spending the retry budget.
    rows.update(_drain(e2))
    for i in (1, 2):
        rec = rows[ref[i]]
        np.testing.assert_array_equal(rec["tokens"], ref_rows[ref[i]]["tokens"])
        assert rec["status"] == "ok"
        assert rec["recovered"] is True and rec["attempt"] == 2
    assert e2.stats()["requests_completed"] == 2  # only the replays ran
    # One decode executable, zero steady-state recompiles across recovery.
    assert e2.stats()["decode_executables"] == 1
    assert e2.stats()["steady_recompiles"] == 0
    # Fresh ids never collide with journaled ones.
    assert e2.submit(prompts[0], max_new_tokens=2) > max(ref.values())


def test_recover_replays_speculative_requests_bit_equal(llama, tmp_path):
    """Crash-restart with speculation on: recovered in-flight requests
    replay bit-equal to a NON-speculative reference (exact-distribution
    verification holds across the journal replay path too), and the
    terminal rows carry the drafted/accepted provenance."""
    cfg, model = llama
    prompts = _prompts(cfg, [5, 9, 7])

    ref_engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=48, prefill_chunks=[4, 8]))
    ref = {}
    for i, p in enumerate(prompts):
        ref[i] = ref_engine.submit(p, max_new_tokens=8)
    ref_rows = _drain(ref_engine)

    mk = lambda: ServingConfig(  # noqa: E731
        n_slots=2, max_len=48, prefill_chunks=[4, 8],
        speculate_k=2, speculate_ngram=8,
        journal_dir=str(tmp_path / "wal"))
    e1 = ServingEngine(model, mk())
    r0 = e1.submit(prompts[0], max_new_tokens=8, client_request_id="req-0")
    done = {}
    ticks = 0
    while r0 not in done:
        e1.tick()
        done.update({r["id"]: r for r in e1.poll()})
        ticks += 1
        assert ticks < 500
    r1 = e1.submit(prompts[1], max_new_tokens=8, client_request_id="req-1")
    r2 = e1.submit(prompts[2], max_new_tokens=8, client_request_id="req-2")
    e1.journal.tick_flush()
    del e1  # abandoned without close(): the crash

    e2 = ServingEngine(model, mk())
    summary = e2.recover()
    assert summary["recovered_terminal"] == 1
    assert summary["recovered_inflight"] == 2
    rows = {r["id"]: r for r in e2.poll()}
    np.testing.assert_array_equal(rows[r0]["tokens"], done[r0]["tokens"])
    rows.update(_drain(e2))
    for i, rid in ((1, r1), (2, r2)):
        rec = rows[rid]
        np.testing.assert_array_equal(rec["tokens"],
                                      ref_rows[ref[i]]["tokens"])
        assert rec["status"] == "ok" and rec["recovered"] is True
        assert rec["drafted"] > 0 and rec["drafted"] >= rec["accepted"]
    spec = e2.stats()["speculation"]
    assert spec["k"] == 2 and spec["drafted"] > 0
    assert e2.stats()["decode_executables"] == 1
    assert e2.stats()["steady_recompiles"] == 0


def test_recover_requires_a_journal(llama):
    cfg, model = llama
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8]))
    with pytest.raises(ValueError, match="needs a journal"):
        engine.recover()


def test_recover_deadline_rebased_on_monotonic_clock(llama, tmp_path):
    """Satellite regression: remaining deadline budget must survive the
    restart as a MONOTONIC delta — elapsed pre-crash runtime is charged,
    but absolute wall time never enters the journal, so a wall-clock step
    during the outage cannot expire (or extend) recovered requests."""
    cfg, model = llama
    wal = str(tmp_path / "wal")
    j = RequestJournal(wal, fsync="os")
    # Hand-written history in the dead process's own monotonic epoch:
    # admitted at t=1000 with a 100s budget, last journal activity at
    # t=1030 -> 30s were spent, 70s remain after however long the outage.
    j.append({"t": "admit", "rid": 0, "cid": None, "tokens": [1, 2, 3],
              "budget": 2, "rng": [0, 0], "deadline_s": 100.0,
              "t_mono": 1000.0, "weights_version": 0})
    j.append({"t": "progress", "tick": 5, "toks": {}, "t_mono": 1030.0})
    j.close()
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8],
                             journal_dir=wal))
    import time as _time

    engine.recover()
    (req,) = list(engine._queue)
    remaining = req.deadline - _time.perf_counter()
    assert 65.0 < remaining <= 70.0
    # An over-spent budget clamps to "due now", never negative chaos.
    j2 = RequestJournal(str(tmp_path / "wal2"), fsync="os")
    j2.append({"t": "admit", "rid": 0, "cid": None, "tokens": [1],
               "budget": 2, "rng": [0, 0], "deadline_s": 10.0,
               "t_mono": 1000.0, "weights_version": 0})
    j2.append({"t": "progress", "tick": 9, "toks": {}, "t_mono": 1500.0})
    j2.close()
    e2 = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8],
                             journal_dir=str(tmp_path / "wal2")))
    e2.recover()
    (req2,) = list(e2._queue)
    assert req2.deadline - _time.perf_counter() <= 0.5


def test_repeated_crashes_accumulate_attempts(llama, tmp_path):
    cfg, model = llama
    mk = lambda: ServingConfig(  # noqa: E731
        n_slots=2, max_len=32, prefill_chunks=[4, 8],
        journal_dir=str(tmp_path / "wal"))
    (p,) = _prompts(cfg, [5])
    e1 = ServingEngine(model, mk())
    rid = e1.submit(p, max_new_tokens=3, client_request_id="req-0")
    e1.journal.tick_flush()
    del e1
    e2 = ServingEngine(model, mk())
    assert e2.recover()["recovered_inflight"] == 1
    del e2  # second crash before the replay ran
    e3 = ServingEngine(model, mk())
    assert e3.recover()["recovered_inflight"] == 1
    rows = _drain(e3)
    # attempt = 1 + retries(0) + recoveries(2); the retry budget untouched.
    assert rows[rid]["attempt"] == 3 and rows[rid]["recovered"] is True
    assert e3.stats()["faults"]["retries"] == 0


def test_engine_crash_chaos_flushes_and_exits(llama, tmp_path, monkeypatch):
    """The injected engine_crash dies through os._exit AFTER pushing the
    telemetry crash event + the injector's full log — and the draw sits
    after the journal's tick flush, so what the fsync policy promised
    durable IS on disk when the process dies."""
    import accelerate_tpu.serving as serving_mod

    cfg, model = llama

    class _Tel:
        def __init__(self):
            self.events = []
            self.closed = False

        def record_event(self, event, **fields):
            self.events.append((event, fields))

        def close(self):
            self.closed = True

    class _Exit(BaseException):
        pass

    codes = []

    def fake_exit(code):
        codes.append(code)
        raise _Exit()

    monkeypatch.setattr(serving_mod.os, "_exit", fake_exit)
    tel = _Tel()
    chaos = FaultInjector(seed=1, schedule=[
        {"point": "engine_crash", "kind": "crash", "tick": 0}])
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8],
                             journal_dir=str(tmp_path / "wal")),
        telemetry=tel, chaos=chaos)
    (p,) = _prompts(cfg, [5])
    engine.submit(p, max_new_tokens=3, client_request_id="req-0")
    with pytest.raises(_Exit):
        engine.tick()
    assert codes == [78]  # SERVING_CRASH_EXIT_CODE
    names = [e for e, _ in tel.events]
    assert "serving_engine_crash" in names and "chaos_injected_log" in names
    assert tel.closed
    # The admission was durable: a fresh engine recovers the request.
    e2 = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8],
                             journal_dir=str(tmp_path / "wal")))
    assert e2.recover()["recovered_inflight"] == 1
