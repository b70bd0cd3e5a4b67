"""Big-model inference benchmark: load time + per-token decode latency on the
real chip — the shape of the reference's headline table
(/root/reference/benchmarks/big_model_inference/README.md:25-33: GPT-J-6B
fp16 loads in 8.7 s and generates at 0.05 s/token on 2x Titan RTX, etc.).

Three rows, one JSON line each:

- ``load``: sharded-safetensors checkpoint -> chip via
  load_checkpoint_and_dispatch (meta init, stream shards into placements) —
  the reference's "load time" column.
- ``resident``: KV-cache generate() with all params HBM-resident — prefill
  latency + steady-state per-token time.
- ``streamed``: params held in host RAM, layer-streamed forward
  (dispatch_model with transformer blocks on "cpu") — the reference's
  CPU-offload rows, where per-token cost is dominated by weight streaming.
- ``--serving`` adds two rows: mixed-length Poisson arrivals through the
  continuous-batching :class:`ServingEngine` vs the SAME request set through
  gang-scheduled static-batch ``generate()`` — aggregate tokens/s, p50/p95
  TTFT (static TTFT = batch completion minus arrival: requests wait for
  the gang), and recompile/executable counts per phase.
- ``--disagg`` (implies ``--serving``) adds a ``serving_disagg`` row: the
  same Poisson trace through the two-mesh
  :class:`~accelerate_tpu.disagg.DisaggServingEngine` (planner-sized
  prefill/decode slices, streamed KV-page handoff) with the telemetry
  ``disagg`` block embedded in the row.
- ``--chaos`` (implies ``--serving``) adds a ``serving_chaos`` row: the
  same trace under a seed-driven :class:`~accelerate_tpu.chaos.FaultInjector`
  (rate-driven handoff transfer errors + one dead lane when disaggregated,
  a poisoned KV page always) with the ``serving.faults`` telemetry block —
  status counts, retries, quarantines, injected-fault log size — embedded
  in the row, so robustness overhead shows up in the perf trajectory next
  to the fault-free rows.
- ``--publish`` (implies ``--serving``) adds a ``serving_publish`` row: a
  committed, manifest-verified checkpoint hot-swapped into the live engine
  mid-trace by the :class:`~accelerate_tpu.publish.WeightPublisher` —
  swap latency, BandwidthTable-priced redistribution bytes, the canary
  window (routed counts + decision), and the faults block, with the
  zero-recompile swap evidenced by the executable census.
- ``--journal`` (implies ``--serving``) adds one ``serving_journal`` row
  per write-ahead-journal fsync policy (``every_record`` / ``every_tick`` /
  ``os``) — the SAME trace with crash-durable request journaling on,
  priced as tokens/s overhead vs the journal-off ``serving`` row — plus a
  ``journal_recovery`` row: a journaled engine is abandoned mid-trace (a
  simulated crash) and a fresh engine's measured ``recover()`` wall time,
  recovered counts, and drained completions ride in the row.
- ``--sdc`` (implies ``--serving``) adds a ``serving_sdc`` row: the same
  trace with a :class:`~accelerate_tpu.sdc.DecodeCanary` re-running a
  known prompt through the live slot machinery every ``--sdc-every``
  ticks — the silent-data-corruption detection tax priced as tokens/s
  overhead vs the canary-off ``serving`` row (target < 1%), with the
  ``sdc`` stats block (probes / mismatches / quarantines) in the row.
- ``--trace diurnal`` swaps the flat Poisson arrivals for the seeded
  diurnal generator (:func:`accelerate_tpu.autoscale.make_diurnal_trace`:
  low / 10x-high / low plateaus with a shifting prompt:decode mix) — ONE
  request set shared by every serving row above, so static, continuous,
  disagg, chaos, and publish are priced on identical load.
- ``--autoscale`` (implies ``--serving`` and ``--trace diurnal``) adds a
  ``serving_autoscale`` row: the trace through a disagg engine that starts
  on HALF the mesh with an :class:`~accelerate_tpu.autoscale.
  AutoscaleController` closing the loop — resize count and decision
  counters, a per-plateau SLO block (p95 TTFT on the high vs low
  plateaus), and the executable census proving resizes did not recompile
  the steady state.

    python benchmarks/generate_bench.py [--params-b 1] [--new-tokens 64]
                                        [--serving] [--disagg] [--chaos]
                                        [--publish] [--autoscale]
                                        [--trace poisson|diurnal] [--qps 8]
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(params_b: float):
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig

    if params_b >= 1.0:
        # The bench.py 1.06B config.
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=18, num_attention_heads=16, num_key_value_heads=16,
            max_position_embeddings=2048, dtype=jnp.bfloat16,
        )
    elif params_b < 0.01:
        # CPU-verifiable tier (CI smoke of the bench plumbing itself).
        cfg = LlamaConfig.tiny(dtype=jnp.float32, max_position_embeddings=2048)
    else:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=4096,
            num_hidden_layers=16, num_attention_heads=8, num_key_value_heads=8,
            max_position_embeddings=2048, dtype=jnp.bfloat16,
        )
    return cfg


def _tracing_block(tr):
    """The bench-row ``tracing`` block: span counts by kind plus the
    critical-path breakdown of the p95-TTFT request — the one the SLO
    report would name when asked "why is tail latency what it is?"."""
    stats = tr.stats()
    block = {"spans": stats["spans"], "by_kind": stats["by_kind"]}
    pairs = []
    for rid in tr.request_ids():
        rep = tr.explain(rid)
        if rep["terms"] is not None:
            pairs.append((rep["ttft_s"], rid))
    if pairs:
        pairs.sort()
        p95 = float(np.percentile([p[0] for p in pairs], 95))
        ttft, rid = next((p for p in pairs if p[0] >= p95), pairs[-1])
        rep = tr.explain(rid)
        block["p95_request"] = {
            "request_id": rid, "ttft_s": round(ttft, 6),
            "dominant": rep["dominant"],
            "terms": {k: round(v, 6) for k, v in rep["terms"].items()},
        }
    return block


def _profile_block(prof):
    """The bench-row ``profile`` block (profiler.py DeviceTimeProfiler):
    per-tick device-time attribution means — where each engine tick's wall
    went (admit / prefill / decode / host fetch / bookkeeping residual).
    ``overlap_ratio_mean`` and ``bandwidth_residuals`` only fill in when a
    training plan priced the profiler; serving-only rows carry them empty
    rather than invented."""
    s = prof.summary()
    block = {k: s.get(k) for k in ("ticks", "overlap_ratio_mean",
                                   "bandwidth_residuals")}
    terms = s.get("tick_terms_mean_s") or {}
    block["tick_terms_mean_s"] = {k: round(v, 6) for k, v in terms.items()}
    return block


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--params-b", type=float, default=1.0)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--streamed-tokens", type=int, default=4)
    ap.add_argument("--int8", action="store_true",
                    help="add a resident_int8 row (DecodeQuant weight-only decode)")
    ap.add_argument("--serving", action="store_true",
                    help="add serving rows (continuous batching vs static gang)")
    ap.add_argument("--disagg", action="store_true",
                    help="add a disaggregated-serving row (two-mesh router on "
                         "the same Poisson trace; implies --serving)")
    ap.add_argument("--lanes", type=int, default=4,
                    help="prefill lanes for the --disagg row")
    ap.add_argument("--chaos", action="store_true",
                    help="add a serving_chaos row (same trace under a "
                         "deterministic FaultInjector; implies --serving)")
    ap.add_argument("--chaos-seed", type=int, default=7)
    ap.add_argument("--publish", action="store_true",
                    help="add a serving_publish row (hot-swap a committed "
                         "checkpoint into the live engine mid-trace through "
                         "a canary window; implies --serving)")
    ap.add_argument("--canary-fraction", type=float, default=0.25)
    ap.add_argument("--journal", action="store_true",
                    help="add serving_journal rows (WAL overhead per fsync "
                         "policy vs journal-off) and a journal_recovery row "
                         "(measured recover() time on a fresh engine after "
                         "a simulated crash; implies --serving)")
    ap.add_argument("--sdc", action="store_true",
                    help="add a serving_sdc row (same trace with a "
                         "DecodeCanary probing every few ticks; prices the "
                         "canary overhead against the canary-off serving "
                         "row — target < 1%% tokens/s; implies --serving)")
    ap.add_argument("--sdc-every", type=int, default=8,
                    help="canary probe cadence in engine ticks for --sdc")
    ap.add_argument("--fleet", action="store_true",
                    help="add a serving_fleet row (the request set through a "
                         "two-cell FleetRouter with a seeded cell_crash "
                         "killing cell 0 mid-trace: per-cell tokens/s, "
                         "spillover rate, measured drain time, executable "
                         "census per cell; implies --serving)")
    ap.add_argument("--autoscale", action="store_true",
                    help="add a serving_autoscale row (diurnal trace through "
                         "a half-mesh disagg engine with an "
                         "AutoscaleController closing the loop; implies "
                         "--serving and --trace diurnal)")
    ap.add_argument("--speculative", action="store_true",
                    help="add serving_speculative rows (n-gram self-draft "
                         "decode, acceptance-friendly vs adversarial "
                         "traffic, each priced against its non-speculative "
                         "baseline; implies --serving)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per slot per tick for --speculative")
    ap.add_argument("--spec-ngram", type=int, default=64,
                    help="n-gram history window for --speculative")
    ap.add_argument("--kv-dtype", choices=("model", "int8"), default="model",
                    help="KV-page dtype for the --disagg row; int8 "
                         "quantizes pages (QuantPages) and reports the "
                         "handoff bytes saved")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--qps", type=float, default=8.0,
                    help="Poisson arrival rate for the serving rows (the "
                         "diurnal trace's low-plateau rate)")
    ap.add_argument("--tracing", action="store_true",
                    help="attach a TraceRecorder to every serving row and "
                         "embed a tracing block (span counts + critical-path "
                         "breakdown of the p95-TTFT request)")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="dump the last traced serving row as Perfetto-"
                         "loadable Chrome trace JSON (implies --tracing)")
    ap.add_argument("--trace", choices=("poisson", "diurnal"),
                    default="poisson",
                    help="arrival process shared by every serving row")
    ap.add_argument("--trace-seed", type=int, default=1)
    args = ap.parse_args()
    if args.autoscale:
        args.trace = "diurnal"
    if args.trace_out:
        args.tracing = True
    if args.disagg or args.chaos or args.publish or args.autoscale \
            or args.journal or args.sdc or args.fleet or args.speculative:
        args.serving = True

    # Streaming-evidence rule (round-3 postmortem, same as bench.py): emit a
    # parseable row the moment anything is known, flushed — a driver timeout
    # must never leave an empty tail.
    print(json.dumps({"row": "start", "params_b": args.params_b}), flush=True)

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.compile_manager import place_compile_cache

    place_compile_cache()

    from accelerate_tpu import Model, dispatch_model, load_checkpoint_and_dispatch
    from accelerate_tpu.generation import generate
    from accelerate_tpu.models import LlamaForCausalLM
    from accelerate_tpu.utils.other import flatten_state_dict, save_sharded_safetensors

    cfg = build(args.params_b)
    module = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=(1, args.prompt_len), dtype=np.int32)

    # Build once on host, export a sharded checkpoint to load from.
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = Model.from_flax(module, jax.random.key(0), prompt)
        host_params = jax.tree.map(lambda x: np.asarray(x), model.params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(host_params))
    ckpt = tempfile.mkdtemp(prefix="gen_bench_ckpt_")
    save_sharded_safetensors(
        {k: np.asarray(v) for k, v in flatten_state_dict(host_params).items()},
        ckpt, max_shard_size=2 * 1024**3,
    )

    device_kind = getattr(jax.devices()[0], "device_kind", jax.devices()[0].platform)

    # --- Row 1: load time (disk -> chip, meta init + shard streaming) ------
    t0 = time.perf_counter()
    resident = load_checkpoint_and_dispatch(module, ckpt, prompt, device_map=None)
    # Materialize: a forward forces every param onto the chip.
    np.asarray(resident(prompt[:, :8]))
    load_s = time.perf_counter() - t0
    print(json.dumps({
        "row": "load", "seconds": round(load_s, 2),
        "params_b": round(n_params / 1e9, 3), "device_kind": device_kind,
    }), flush=True)

    # --- Row 2: resident KV-cache decode ----------------------------------
    # device_map=None placed every param on chip 0; reuse that tree directly.
    res_model = Model(module=module, params=resident.params)

    t0 = time.perf_counter()
    jax.block_until_ready(generate(res_model, prompt, max_new_tokens=args.new_tokens))
    first_s = time.perf_counter() - t0  # includes compile
    t0 = time.perf_counter()
    jax.block_until_ready(generate(res_model, prompt, max_new_tokens=args.new_tokens))
    warm_s = time.perf_counter() - t0
    per_token = warm_s / args.new_tokens
    print(json.dumps({
        "row": "resident", "s_per_token": round(per_token, 4),
        "tokens_per_s": round(1.0 / per_token, 1),
        "warm_generate_s": round(warm_s, 3),
        "first_call_s": round(first_s, 2),
        "new_tokens": args.new_tokens,
    }), flush=True)

    # --- Optional row: int8 weight-only resident decode --------------------
    if args.int8:
        from accelerate_tpu.utils.quantization import (
            quantize_model_for_decode, quantized_nbytes,
        )
        from accelerate_tpu.generation import clear_generation_cache

        qm = quantize_model_for_decode(res_model)
        clear_generation_cache()
        jax.block_until_ready(generate(qm, prompt, max_new_tokens=args.new_tokens))  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(generate(qm, prompt, max_new_tokens=args.new_tokens))
        warm_q = time.perf_counter() - t0
        print(json.dumps({
            "row": "resident_int8", "s_per_token": round(warm_q / args.new_tokens, 4),
            "tokens_per_s": round(args.new_tokens / warm_q, 1),
            "weight_bytes": int(quantized_nbytes(qm.params)),
            "weight_bytes_bf16": int(quantized_nbytes(res_model.params)),
            "new_tokens": args.new_tokens,
        }), flush=True)
        qm = None  # free the int8 copy + its executables before the
        clear_generation_cache()  # streamed row's per-layer buffers

    # --- Optional rows: continuous batching vs static gang -----------------
    if args.serving:
        from accelerate_tpu import ServingConfig, ServingEngine
        from accelerate_tpu import generation as G
        from accelerate_tpu.generation import clear_generation_cache

        def _recorder():
            if not args.tracing:
                return None
            from accelerate_tpu import TraceConfig, TraceRecorder

            return TraceRecorder(TraceConfig())

        export_tr = None  # the last traced row's recorder (--trace-out)

        srng = np.random.default_rng(1)
        n, slots = args.requests, args.slots
        phases = None
        if args.trace == "diurnal":
            # One seeded diurnal trace shared by EVERY serving row below:
            # low / high / low plateaus at a 10x rate swing with the
            # prompt:decode mix shifting against it (autoscale.py).
            from accelerate_tpu.autoscale import make_diurnal_trace

            dtrace = make_diurnal_trace(n, seed=args.trace_seed,
                                        base_rate=args.qps,
                                        vocab_size=cfg.vocab_size)
            reqs = dtrace["prompts"]
            lengths = np.asarray(dtrace["lengths"])
            budgets = np.asarray(dtrace["budgets"], dtype=int)
            arrivals = np.asarray(dtrace["arrivals"])
            phases = np.asarray(dtrace["phases"])
        else:
            lengths = srng.integers(4, max(9, args.prompt_len), n)
            budgets = np.where(
                srng.random(n) < 0.5,
                srng.integers(4, 12, n),
                srng.integers(max(2, args.new_tokens // 2),
                              args.new_tokens + 1, n),
            ).astype(int)
            reqs = [srng.integers(1, cfg.vocab_size, (int(L),),
                                  dtype=np.int32) for L in lengths]
            arrivals = np.cumsum(srng.exponential(1.0 / args.qps, n))
        useful = int(budgets.sum())

        # Static gang: batches of `slots` in arrival order, left-padded to
        # the batch max prompt, every row decoding the batch max budget. A
        # request's TTFT is its batch's completion minus its arrival — the
        # gang cannot release anything early.
        clear_generation_cache()
        t0 = time.perf_counter()
        batch_done = {}
        for i0 in range(0, n, slots):
            batch = list(range(i0, min(i0 + slots, n)))
            smax = max(len(reqs[i]) for i in batch)
            bmax = int(max(budgets[i] for i in batch))
            ids = np.zeros((len(batch), smax), np.int32)
            mask = np.zeros((len(batch), smax), np.int32)
            for r, i in enumerate(batch):
                p = reqs[i]
                ids[r, smax - len(p):] = p
                mask[r, smax - len(p):] = 1
            np.asarray(generate(res_model, ids, max_new_tokens=bmax,
                                attention_mask=mask))
            done = time.perf_counter() - t0
            for i in batch:
                batch_done[i] = done
        static_s = time.perf_counter() - t0
        ttft_static = np.asarray(
            [max(0.0, batch_done[i] - arrivals[i]) for i in range(n)]
        )
        static_execs = sum(
            int(fn._cache_size()) for fn in G._GEN_LOOP_CACHE.values()
            if callable(getattr(fn, "_cache_size", None))
        )
        print(json.dumps({
            "row": "serving_static", "seconds": round(static_s, 3),
            "useful_tokens": useful,
            "tokens_per_s": round(useful / static_s, 2),
            "ttft_p50_s": round(float(np.percentile(ttft_static, 50)), 4),
            "ttft_p95_s": round(float(np.percentile(ttft_static, 95)), 4),
            "compiled_executables": static_execs,
        }), flush=True)

        # Continuous batching: the SAME Poisson trace replayed open-loop
        # (arrival times fixed up front — offered load does not adapt to the
        # engine's drain rate). Warmup first so compiles stay out of TTFT.
        from accelerate_tpu.serving import replay_trace

        t_cap = int(max(lengths[i] + budgets[i] for i in range(n))) + 8
        scfg = ServingConfig(n_slots=slots, max_len=t_cap,
                             max_prefill_chunk=max(16, args.prompt_len))
        tr_serve = _recorder()
        # Standalone device-time profiler: lagged per-tick attribution
        # (host perf_counter sections, zero extra device syncs) rides the
        # row so WHERE each tick's wall went travels with the latencies.
        from accelerate_tpu.profiler import DeviceTimeProfiler

        prof_serve = DeviceTimeProfiler()
        engine = ServingEngine(res_model, scfg, tracing=tr_serve,
                               profiler=prof_serve)
        engine.warmup()
        _, serve_s = replay_trace(engine, reqs, arrivals=list(arrivals),
                                  max_new_tokens=[int(b) for b in budgets])
        st = engine.stats()
        row = {
            "row": "serving", "seconds": round(serve_s, 3),
            "useful_tokens": st["tokens_out"],
            "tokens_per_s": st["tokens_per_s"],
            "ttft_p50_s": round(st["ttft_p50_s"], 4),
            "ttft_p95_s": round(st["ttft_p95_s"], 4),
            "tpot_mean_s": round(st["tpot_mean_s"], 4),
            "mean_occupancy": st["mean_occupancy"],
            "decode_executables": st["decode_executables"],
            "prefill_executables": st["prefill_executables"],
            "steady_recompiles": st["steady_recompiles"],
            "faults": st["faults"],
            "speculation": st["speculation"],
        }
        prof_serve.flush()  # finalize the lagged last tick
        row["profile"] = _profile_block(prof_serve)
        if tr_serve is not None:
            row["tracing"] = _tracing_block(tr_serve)
            export_tr = tr_serve
        print(json.dumps(row), flush=True)

        # Speculative rows: raw decode throughput with the n-gram
        # self-draft on, against the non-speculative baseline on the SAME
        # mesh, model, and request set (everything submitted at t=0 so the
        # arrival process never caps the measured decode rate). Two traffic
        # classes: acceptance-friendly uses a Markov-collapsed model
        # variant (attention output projections zeroed, so continuations
        # settle into cycles — the repetitive-output regime where n-gram
        # drafts shine: boilerplate, JSON, copy-heavy completions);
        # adversarial uses the raw model, whose continuations stay chaotic
        # and acceptance sits near the floor — the honest worst case.
        if args.speculative:
            spec_budget = int(args.new_tokens)
            spec_cap = int(max(len(r) for r in reqs)) + spec_budget + 8

            def _collapsed_params(tree):
                new = jax.tree.map(lambda x: x, tree)
                mp = new["model"] if "model" in new else new
                blk = mp["layers"]["block"]
                blk["self_attn"]["o_proj"]["kernel"] = jnp.zeros_like(
                    blk["self_attn"]["o_proj"]["kernel"])
                return new

            friendly_model = Model(module=module,
                                   params=_collapsed_params(res_model.params))

            def _spec_run(mdl, k):
                ecfg = ServingConfig(
                    n_slots=slots, max_len=spec_cap,
                    max_prefill_chunk=max(16, args.prompt_len),
                    speculate_k=k, speculate_ngram=args.spec_ngram)
                eng = ServingEngine(mdl, ecfg)
                eng.warmup()
                t0 = time.perf_counter()
                eng.run([r.copy() for r in reqs],
                        max_new_tokens=spec_budget)
                wall = time.perf_counter() - t0
                est = eng.stats()
                eng.close()
                return est, wall

            for traffic, mdl in (("acceptance_friendly", friendly_model),
                                 ("adversarial", res_model)):
                clear_generation_cache()
                bst, b_wall = _spec_run(mdl, 0)
                sst, s_wall = _spec_run(mdl, args.spec_k)
                b_tps = bst["tokens_out"] / b_wall
                s_tps = sst["tokens_out"] / s_wall
                sp = sst["speculation"]
                print(json.dumps({
                    "row": "serving_speculative", "traffic": traffic,
                    "k": args.spec_k, "ngram": args.spec_ngram,
                    "tokens_per_s": round(s_tps, 2),
                    "tokens_per_s_baseline": round(b_tps, 2),
                    "speedup": round(s_tps / b_tps, 3) if b_tps else None,
                    "acceptance_rate": sp["acceptance_rate"],
                    "tokens_per_tick": sp["tokens_per_tick"],
                    "tokens_per_tick_baseline": (
                        round(bst["tokens_out"] / bst["decode_steps"], 6)
                        if bst["decode_steps"] else None),
                    "decode_steps": sst["decode_steps"],
                    "decode_steps_baseline": bst["decode_steps"],
                    "decode_executables": sst["decode_executables"],
                    "steady_recompiles": sst["steady_recompiles"],
                    "faults": sst["faults"],
                    "speculation": sp,
                }), flush=True)
            friendly_model = None
            clear_generation_cache()

        # Journal rows: the same trace with the crash-durable write-ahead
        # request journal on, one row per fsync policy — the durability tax
        # priced against the journal-off `serving` row above. every_record
        # pays an fsync per append, every_tick (the default) one per engine
        # tick, os only flushes to the page cache.
        if args.journal:
            from accelerate_tpu.journal import JOURNAL_FSYNC_POLICIES

            base_tps = st["tokens_per_s"]
            jroot = tempfile.mkdtemp(prefix="gen_bench_journal_")
            for pol in JOURNAL_FSYNC_POLICIES:
                jcfg = ServingConfig(
                    n_slots=slots, max_len=t_cap,
                    max_prefill_chunk=max(16, args.prompt_len),
                    journal_dir=os.path.join(jroot, pol), journal_fsync=pol)
                jengine = ServingEngine(res_model, jcfg)
                jengine.warmup()
                _, jour_s = replay_trace(
                    jengine, reqs, arrivals=list(arrivals),
                    max_new_tokens=[int(b) for b in budgets])
                jst = jengine.stats()
                jj = jst["journal"]
                print(json.dumps({
                    "row": "serving_journal", "fsync": pol,
                    "seconds": round(jour_s, 3),
                    "tokens_per_s": jst["tokens_per_s"],
                    "tokens_per_s_journal_off": base_tps,
                    "overhead_pct": (round(100.0 * (base_tps - jst[
                        "tokens_per_s"]) / base_tps, 2) if base_tps else None),
                    "appends": jj["appends"], "syncs": jj["syncs"],
                    "rotations": jj["rotations"],
                    "bytes_written": jj["bytes_written"],
                    "decode_executables": jst["decode_executables"],
                    "steady_recompiles": jst["steady_recompiles"],
                }), flush=True)
                jengine.close()

            # Measured recovery: feed the whole request set to a journaled
            # engine, abandon it after a handful of ticks WITHOUT close()
            # (a simulated crash — the WAL is the only survivor), then time
            # a fresh engine's recover() over the same directory and drain
            # the replayed queue to completion.
            rcfg = ServingConfig(
                n_slots=slots, max_len=t_cap,
                max_prefill_chunk=max(16, args.prompt_len),
                journal_dir=os.path.join(jroot, "recover"))
            crash_engine = ServingEngine(res_model, rcfg)
            crash_engine.warmup()
            for i in range(n):
                crash_engine.submit(reqs[i], max_new_tokens=int(budgets[i]),
                                    client_request_id=f"bench-{i}")
            for _ in range(16):
                if crash_engine.pending:
                    crash_engine.tick()
            crash_engine.poll()
            del crash_engine  # simulated crash: no close(), no flush
            fresh = ServingEngine(res_model, rcfg)
            fresh.warmup()
            t0 = time.perf_counter()
            rec = fresh.recover()
            recover_wall_s = time.perf_counter() - t0
            drained = 0
            while fresh.pending:
                fresh.tick()
                drained += sum(1 for r in fresh.poll()
                               if r["status"] == "ok")
            print(json.dumps({
                "row": "journal_recovery",
                "recover_s": round(recover_wall_s, 4),
                "recovered_inflight": rec["recovered_inflight"],
                "recovered_terminal": rec["recovered_terminal"],
                "records_scanned": rec["records"],
                "segments": rec["segments"],
                "torn_tails": rec["torn_tails"],
                "corrupt_skipped": rec["corrupt_skipped"],
                "drained_ok": drained,
                "requests": n,
            }), flush=True)
            fresh.close()

        # SDC-canary row: the same trace with a DecodeCanary re-running a
        # known prompt through the live slot machinery every --sdc-every
        # ticks — the silent-data-corruption detection tax priced against
        # the canary-off `serving` row above (target: < 1% tokens/s). The
        # probe rides the compiled decode ladder and is suppressed from
        # poll()/journal/stats, so the only cost is its slot occupancy.
        if args.sdc:
            from accelerate_tpu.sdc import DecodeCanary

            dcfg = ServingConfig(n_slots=slots, max_len=t_cap,
                                 max_prefill_chunk=max(16, args.prompt_len))
            dengine_sdc = ServingEngine(res_model, dcfg)
            dengine_sdc.warmup()
            canary = DecodeCanary(dengine_sdc, every=args.sdc_every)
            canary.warmup()
            dengine_sdc.reset_metrics()  # warmup probe out of the measurement
            _, sdc_s = replay_trace(dengine_sdc, reqs,
                                    arrivals=list(arrivals),
                                    max_new_tokens=[int(b) for b in budgets])
            dst_sdc = dengine_sdc.stats()
            base_tps = st["tokens_per_s"]
            print(json.dumps({
                "row": "serving_sdc", "seconds": round(sdc_s, 3),
                "canary_every": args.sdc_every,
                "useful_tokens": dst_sdc["tokens_out"],
                "tokens_per_s": dst_sdc["tokens_per_s"],
                "tokens_per_s_canary_off": base_tps,
                "overhead_pct": (round(100.0 * (base_tps - dst_sdc[
                    "tokens_per_s"]) / base_tps, 2) if base_tps else None),
                "ttft_p50_s": round(dst_sdc["ttft_p50_s"], 4),
                "ttft_p95_s": round(dst_sdc["ttft_p95_s"], 4),
                "steady_recompiles": dst_sdc["steady_recompiles"],
                "sdc": dst_sdc["sdc"],
            }), flush=True)

        # Fleet row: the same request set through a two-cell FleetRouter
        # (one journaled engine per cell) with a seeded cell_crash killing
        # cell 0 mid-trace — prices whole-cell failover: the router adopts
        # the dead cell's journal and drains it onto the survivor. Per-cell
        # tokens/s, spillover rate, the measured drain time, and the
        # executable census per surviving cell ride the row.
        if args.fleet:
            from accelerate_tpu import FaultInjector, FleetRouter

            froot = tempfile.mkdtemp(prefix="gen_bench_fleet_")
            fcells = {}
            for i in range(2):
                feng = ServingEngine(res_model, ServingConfig(
                    n_slots=slots, max_len=t_cap,
                    max_prefill_chunk=max(16, args.prompt_len),
                    journal_dir=os.path.join(froot, f"wal{i}")))
                feng.warmup()
                fcells[f"c{i}"] = feng
            crash_tick = max(2, n // 3)
            fchaos = FaultInjector(seed=args.chaos_seed, schedule=[
                {"point": "cell_crash", "kind": "crash",
                 "tick": crash_tick, "unit": 0}])
            frouter = FleetRouter(fcells, chaos=fchaos)
            fok = 0
            t0 = time.perf_counter()
            for i in range(n):  # tick-aligned arrivals: one per router tick
                frouter.submit(reqs[i], max_new_tokens=int(budgets[i]),
                               client_request_id=f"fleet-bench-{i}",
                               session_id=f"sess-{i}")
                frouter.tick()
                fok += sum(1 for r in frouter.poll()
                           if r["status"] == "ok")
            while frouter.pending:
                frouter.tick()
                fok += sum(1 for r in frouter.poll()
                           if r["status"] == "ok")
            fleet_s = time.perf_counter() - t0
            fs = frouter.stats()
            fper = {}
            for name, block in fs["per_cell"].items():
                cell = frouter._cells[name]
                fper[name] = {
                    "state": block["state"],
                    "tokens_per_s": (cell.engine.stats()["tokens_per_s"]
                                     if not cell.dead else None),
                    "requests_completed": block["requests_completed"],
                    "decode_executables": block["decode_executables"],
                    "steady_recompiles": block["steady_recompiles"],
                }
            print(json.dumps({
                "row": "serving_fleet", "seconds": round(fleet_s, 3),
                "cells": fs["cells"], "dead": fs["dead"],
                "crash_tick": crash_tick, "requests": n, "ok": fok,
                "spillover_rate": (round(
                    fs["routed_spilled"] / fs["submitted"], 4)
                    if fs["submitted"] else None),
                "shed": fs["shed"],
                "drain_s": fs["drain_last_s"],
                "drained_cached": fs["drained_cached"],
                "drained_resubmitted": fs["drained_resubmitted"],
                "per_cell": fper,
            }), flush=True)
            frouter.close()

        # Disaggregated row: the same trace through the two-mesh router —
        # planner-sized prefill/decode slices, streamed KV-page handoff. The
        # telemetry `disagg` block rides inside the row (slice plan, handoff
        # bytes/latency, measured FLOP ratio).
        if args.disagg and len(jax.devices()) < 2:
            print(json.dumps({
                "row": "serving_disagg", "skipped": "needs >= 2 devices",
            }), flush=True)
        elif args.disagg:
            from accelerate_tpu import DisaggConfig, DisaggServingEngine

            tr_dis = _recorder()
            prof_dis = DeviceTimeProfiler()
            dis_cfg = scfg
            if args.kv_dtype == "int8":
                dis_cfg = ServingConfig(
                    n_slots=slots, max_len=t_cap,
                    max_prefill_chunk=max(16, args.prompt_len),
                    cache_dtype=jnp.int8)
            dengine = DisaggServingEngine(
                res_model, dis_cfg,
                disagg=DisaggConfig(n_prefill_lanes=args.lanes),
                tracing=tr_dis, profiler=prof_dis,
            )
            dengine.warmup()
            _, dis_s = replay_trace(dengine, reqs, arrivals=list(arrivals),
                                    max_new_tokens=[int(b) for b in budgets])
            dst = dengine.stats()
            row = {
                "row": "serving_disagg", "seconds": round(dis_s, 3),
                "useful_tokens": dst["tokens_out"],
                "tokens_per_s": dst["tokens_per_s"],
                "ttft_p50_s": round(dst["ttft_p50_s"], 4),
                "ttft_p95_s": round(dst["ttft_p95_s"], 4),
                "tpot_mean_s": round(dst["tpot_mean_s"], 4),
                "decode_executables": dst["decode_executables"],
                "steady_recompiles": dst["steady_recompiles"],
                "disagg": dst["disagg"],
            }
            if args.kv_dtype == "int8":
                # Byte accounting: what the SAME trace would have moved in
                # the model's own cache dtype, per the planner's dtype-aware
                # per-token pricing — the saved fraction is the honest
                # "4x fewer handoff bytes" number.
                from accelerate_tpu.planner import kv_bytes_per_token

                moved = int(dst["disagg"]["handoff_bytes"])
                per_q = kv_bytes_per_token(cfg, dtype=jnp.int8)
                per_f = kv_bytes_per_token(cfg)
                unq = int(round(moved * per_f / per_q)) if per_q else None
                row["kv_dtype"] = "int8"
                row["handoff_bytes"] = moved
                row["handoff_bytes_unquantized_est"] = unq
                row["handoff_bytes_saved_pct"] = (
                    round(100.0 * (unq - moved) / unq, 2) if unq else None)
            prof_dis.flush()  # finalize the lagged last tick
            row["profile"] = _profile_block(prof_dis)
            if tr_dis is not None:
                row["tracing"] = _tracing_block(tr_dis)
                export_tr = tr_dis
            print(json.dumps(row), flush=True)

        # Chaos row: the same trace under a deterministic FaultInjector —
        # the robustness overhead (retries, quarantines, degraded fallback)
        # priced against the fault-free rows above. Disaggregated when
        # --disagg ran (handoff faults + a dead lane); colocated otherwise
        # (a poisoned KV page through the decode sentinel).
        if args.chaos:
            from accelerate_tpu import FaultInjector

            use_disagg = args.disagg and len(jax.devices()) >= 2
            rates = {"handoff_device_put": {"transfer_error": 0.05}} \
                if use_disagg else {}
            schedule = [{"point": "decode_tick", "kind": "poison", "tick": 25}]
            if use_disagg:
                schedule.append({"point": "lane_health", "kind": "dead_lane",
                                 "unit": 0})
            chaos = FaultInjector(seed=args.chaos_seed, rates=rates,
                                  schedule=schedule)
            ccfg = ServingConfig(n_slots=slots, max_len=t_cap,
                                 max_prefill_chunk=max(16, args.prompt_len),
                                 max_retries=3,
                                 max_idle_ticks=max(100, 4 * t_cap))
            tr_chaos = _recorder()
            if use_disagg:
                from accelerate_tpu import DisaggConfig, DisaggServingEngine

                cengine = DisaggServingEngine(
                    res_model, ccfg,
                    disagg=DisaggConfig(n_prefill_lanes=args.lanes),
                    tracing=tr_chaos)
            else:
                cengine = ServingEngine(res_model, ccfg, tracing=tr_chaos)
            cengine.warmup()   # compiles out of TTFT; the tick clock re-zeroes
            cengine.chaos = chaos  # attach after warmup: draws stay replayable
            _, cha_s = replay_trace(cengine, reqs, arrivals=list(arrivals),
                                    max_new_tokens=[int(b) for b in budgets])
            cst = cengine.stats()
            row = {
                "row": "serving_chaos", "seconds": round(cha_s, 3),
                "chaos_seed": args.chaos_seed,
                "useful_tokens": cst["tokens_out"],
                "tokens_per_s": cst["tokens_per_s"],
                "ttft_p50_s": round(cst["ttft_p50_s"], 4),
                "ttft_p95_s": round(cst["ttft_p95_s"], 4),
                "decode_executables": cst["decode_executables"],
                "steady_recompiles": cst["steady_recompiles"],
                "faults": cst["faults"],
                "speculation": cst["speculation"],
            }
            if use_disagg:
                row["degraded"] = cst["disagg"]["degraded"]
                row["healthy_lanes"] = cst["disagg"]["healthy_lanes"]
            if tr_chaos is not None:
                row["tracing"] = _tracing_block(tr_chaos)
                export_tr = tr_chaos
            print(json.dumps(row), flush=True)

        # Publish row: hot-swap a committed, manifest-verified checkpoint
        # into the live engine mid-trace. The publisher redistributes the
        # weights through the reshard executor (bytes priced against the
        # BandwidthTable), opens a canary window over `--canary-fraction`
        # of new admissions, and promotes on the loose SLO gates — the row
        # records swap latency, redistribution bytes, the canary window,
        # and the faults block next to the fault-free serving rows.
        if args.publish:
            from accelerate_tpu import PublishConfig, WeightPublisher
            from accelerate_tpu.fault_tolerance import write_manifest

            pub_root = tempfile.mkdtemp(prefix="gen_bench_publish_")
            pub_ckpt = os.path.join(pub_root, "checkpoint_0")
            os.makedirs(pub_ckpt)
            save_sharded_safetensors(
                {k: np.asarray(v)
                 for k, v in flatten_state_dict(host_params).items()},
                pub_ckpt, max_shard_size=2 * 1024**3,
            )
            write_manifest(pub_ckpt, step=1, world_size=1)

            pengine = ServingEngine(res_model, scfg)
            pengine.warmup()
            pub = WeightPublisher(pengine, PublishConfig(
                checkpoint_dir=pub_root,
                canary_fraction=args.canary_fraction,
                canary_warmup=1, min_cohort=3,
                max_ttft_ratio=100.0, max_tpot_ratio=100.0,
                max_rate_increase=1.0,
            ))
            order = sorted(range(n), key=lambda i: float(arrivals[i]))
            filler = srng.integers(1, cfg.vocab_size, (8,), dtype=np.int32)
            fillers_left = 64
            t0 = time.perf_counter()
            nxt = 0
            decision = None
            while nxt < n or pengine.pending or (
                    decision is None and fillers_left > 0):
                now = time.perf_counter() - t0
                while nxt < n and float(arrivals[order[nxt]]) <= now:
                    i = order[nxt]
                    pengine.submit(reqs[i], max_new_tokens=int(budgets[i]))
                    nxt += 1
                if nxt >= n and decision is None and not pengine.pending \
                        and fillers_left > 0:
                    # The trace drained before the canary window filled:
                    # keep the cohorts fed so the decision lands.
                    pengine.submit(filler, max_new_tokens=8)
                    fillers_left -= 1
                if pengine.pending:
                    pengine.tick()
                    pengine.poll()
                rec = pub.poll()
                if rec is not None and rec["action"] in ("promoted",
                                                         "rolled_back"):
                    decision = rec
            pub_s = time.perf_counter() - t0
            pst = pengine.stats()
            ps = pub.stats()
            published = next((r for r in pub.history
                              if r["action"] == "published"), {})
            print(json.dumps({
                "row": "serving_publish", "seconds": round(pub_s, 3),
                "weights_version": pst["weights_version"],
                "swap_s": published.get("swap_s"),
                "planned_bytes": ps["bytes_planned"],
                "redistributed_bytes": ps["bytes_moved"],
                "predicted_transfer_s": ps["predicted_transfer_s"],
                "transfer_wall_s": ps["transfer_wall_s"],
                "n_devices": published.get("n_devices"),
                "canary_fraction": args.canary_fraction,
                "decision": (decision or {}).get("action"),
                "canary_window": (decision or {}).get("routed"),
                "tokens_per_s": pst["tokens_per_s"],
                "decode_executables": pst["decode_executables"],
                "steady_recompiles": pst["steady_recompiles"],
                "faults": pst["faults"],
            }), flush=True)

        # Autoscale row: the diurnal trace through a disagg engine that
        # starts on HALF the mesh with an AutoscaleController closing the
        # telemetry -> planner -> live-resize loop. The row prices
        # elasticity next to the fixed-topology rows: resize count and
        # decision counters, a per-plateau SLO block (p95 TTFT on the high
        # vs low plateaus), and the executable census (a resize must not
        # recompile the steady state).
        if args.autoscale and len(jax.devices()) < 2:
            print(json.dumps({
                "row": "serving_autoscale", "skipped": "needs >= 2 devices",
            }), flush=True)
        elif args.autoscale:
            from accelerate_tpu import (
                AutoscaleConfig,
                AutoscaleController,
                DisaggConfig,
                DisaggServingEngine,
            )

            pool = jax.devices()
            start = max(2, len(pool) // 2)
            acfg = ServingConfig(n_slots=slots, max_len=t_cap,
                                 max_prefill_chunk=max(16, args.prompt_len),
                                 max_retries=3,
                                 max_idle_ticks=max(100, 4 * t_cap))
            aengine = DisaggServingEngine(
                res_model, acfg,
                disagg=DisaggConfig(n_prefill_lanes=min(args.lanes, start)),
                devices=pool[:start])
            aengine.warmup()
            auto = AutoscaleController(
                aengine,
                AutoscaleConfig(poll_ticks=8, window_min_requests=4,
                                queue_depth_high=3.0, queue_depth_low=0.5,
                                breach_samples=2, cooldown_ticks=40),
                device_pool=pool)
            ids, results = {}, {}
            t0 = time.perf_counter()
            nxt = 0
            while nxt < n or aengine.pending:
                now = time.perf_counter() - t0
                while nxt < n and float(arrivals[nxt]) <= now:
                    ids[nxt] = aengine.submit(reqs[nxt],
                                              max_new_tokens=int(budgets[nxt]))
                    nxt += 1
                if aengine.pending:
                    aengine.tick()
                    auto.poll()
                    for r in aengine.poll():
                        results[r["id"]] = r
            auto_s = time.perf_counter() - t0
            ast = aengine.stats()
            a = auto.stats()

            def _plateau_p95(want_high):
                sel = (phases == 1) if want_high else (phases != 1)
                v = [results[ids[i]]["ttft_s"] for i in range(n)
                     if sel[i] and i in ids
                     and results[ids[i]]["status"] == "ok"
                     and results[ids[i]]["ttft_s"] is not None]
                return (round(float(np.percentile(np.asarray(v), 95)), 4)
                        if v else None)

            print(json.dumps({
                "row": "serving_autoscale", "seconds": round(auto_s, 3),
                "useful_tokens": ast["tokens_out"],
                "tokens_per_s": ast["tokens_per_s"],
                "ttft_p50_s": round(ast["ttft_p50_s"], 4),
                "ttft_p95_s": round(ast["ttft_p95_s"], 4),
                "slo_plateaus": {"ttft_p95_high_s": _plateau_p95(True),
                                 "ttft_p95_low_s": _plateau_p95(False)},
                "autoscale": {k: a[k] for k in (
                    "samples", "decisions", "holds", "grows", "shrinks",
                    "resplits", "dead_device_shrinks", "resizes", "aborts",
                    "flap_damped", "active_devices", "pool_devices")},
                "resize": ast["disagg"]["resize"],
                "decode_executables": ast["decode_executables"],
                "prefill_executables": ast["prefill_executables"],
                "steady_recompiles": ast["steady_recompiles"],
            }), flush=True)

        if args.trace_out and export_tr is not None:
            export_tr.export_chrome_trace(args.trace_out)
            print(json.dumps({"row": "trace_out", "path": args.trace_out,
                              "spans": export_tr.stats()["spans"]}),
                  flush=True)

    # --- Row 3: streamed (blocks in host RAM, layer streaming) -------------
    base = Model(module=module, params=host_params)
    block_map = {"model/layers": "cpu", "": jax.devices()[0]}
    streamed = dispatch_model(base, block_map)
    seq = prompt.copy()
    np.asarray(streamed(seq))  # warm the compile for the prompt shape
    times = []
    for _ in range(args.streamed_tokens):
        t0 = time.perf_counter()
        logits = np.asarray(streamed(seq))
        times.append(time.perf_counter() - t0)
        nxt = logits[:, -1].argmax(-1).astype(np.int32)[:, None]
        seq = np.concatenate([seq, nxt], axis=1)
    print(json.dumps({
        "row": "streamed", "s_per_token": round(float(np.mean(times[1:] or times)), 3),
        "hbm_resident_bytes": int(streamed.hbm_resident_bytes()),
        "tokens": args.streamed_tokens,
    }), flush=True)


if __name__ == "__main__":
    main()
