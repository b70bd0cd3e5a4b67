"""Proof that the system starts on the chip: one process, normal entry points.

    python chip_smoke.py            # on a TPU host (one chip or several)
    python chip_smoke.py --rehearse # same control flow, tiny sizes, any backend

Four phases, each fatal; exit 0 only if all passed:

1. device   - what JAX sees; anything but a TPU is a nonzero exit.
2. kernels  - the Pallas flash forward, dQ and dK/dV kernels, compiled,
              against ``blockwise_attention`` in float32 at "highest" matmul
              precision, at the trainer's shape and one GQA shape.
3. trainer  - ``Accelerator`` -> ``Model.from_flax`` -> ``prepare`` ->
              ``prepare_train_step`` on a 1.06B Llama (:func:`llama_1b`)
              (full width, seq 2048, bf16, FSDP over every chip): two warm-up
              steps then five. On four or more chips a second, shorter pass
              runs it under dp_shard x tp=2.
4. server   - the trained bf16 params in a ``ServingEngine``: eight requests,
              prompts of 128-1024 tokens, 64 new tokens each.

It never sets ``JAX_PLATFORMS``, never substitutes a smaller model on its
own, and starts no other process. ``--rehearse`` is for debugging the command
where there is no chip: every line it prints says which platform ran, and no
line is a device metric. Wall times printed per phase are observations for
PERF.md, not metrics.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import contextlib
import json
import sys
import time

import numpy as np

# bf16 keeps 8 bits of mantissa (one rounding is 2^-9, about 0.2%). The
# kernels round P and dS to bf16 before the MXU and each output once more, so
# a correct kernel lands within about 1% of its tensor's largest value of the
# float32 reference; a wrong mask, offset or GQA head map is off by O(100%).
KERNEL_TOL = 2e-2
# Logits of the flax module (training path, flash kernel) against the cached
# decode path (generation.py), both bf16 over 18 layers of random weights:
# rounding differs at every matmul, so compare by relative RMS, where a wrong
# rope, mask or cache write shows as O(1).
LOGIT_RMS_TOL = 5e-2

PROMPT_LENS = (128, 200, 333, 512, 640, 777, 900, 1024)
NEW_TOKENS = 64
SEQ = 2048
# What fits one 16 GB v5e at that length beside bf16 params, bf16 Adam moments
# and gradients (PERF.md, PR 21).
PER_CHIP_BATCH, REMAT_POLICY = 2, "dots"


def require_tpu() -> None:
    """A nonzero exit naming what was found, unless the backend is a TPU."""
    import jax

    from accelerate_tpu.utils import is_tpu_available

    if not is_tpu_available():
        sys.exit(
            f"no TPU: JAX's default backend is {jax.default_backend()!r} "
            f"({jax.devices()[0].device_kind}). This program measures the "
            "chip and does not fall back to another backend."
        )


def llama_1b(seq: int, remat_policy: str):
    """The ~1.06B-param Llama the trainer phase runs. Width is fixed; only
    batch and remat policy move to make it fit."""
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=5632,
        num_hidden_layers=18,
        num_attention_heads=16,
        num_key_value_heads=16,
        max_position_embeddings=seq,
        dtype=jnp.bfloat16,
        remat=True,
        remat_policy=remat_policy,
        attention_impl="flash",
    )


def build_trainer(cfg, per_chip_batch: int, seq: int, *, parallelism_config=None,
                  kwargs_handlers=None):
    """The normal training path for ``cfg``: ``Accelerator`` (bf16, FSDP) ->
    ``Model.from_flax`` -> ``prepare`` -> ``prepare_train_step``, on one fixed
    seeded batch of ``per_chip_batch`` sequences per chip.

    Params and Adam moments are bf16: 1B of fp32 masters + fp32 moments +
    gradients does not fit a 16 GB chip. Returns ``(acc, model, step,
    batch)``; the state is ``acc.train_state``."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import LlamaForCausalLM, cross_entropy_loss
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import FullyShardedDataParallelPlugin, set_seed

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    set_seed(0)

    module = LlamaForCausalLM(cfg)
    batch = per_chip_batch * jax.device_count()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32)

    acc = Accelerator(
        mixed_precision="bf16",
        fsdp_plugin=FullyShardedDataParallelPlugin(),
        parallelism_config=parallelism_config,
        kwargs_handlers=kwargs_handlers,
    )
    model = Model.from_flax(module, jax.random.key(0), ids[:, :-1])
    model.params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), model.params)
    tx = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    model, _ = acc.prepare(model, tx)

    def loss_fn(params, b):
        logits = module.apply({"params": params}, b["x"])
        return cross_entropy_loss(logits, b["y"])

    step = acc.prepare_train_step(loss_fn, max_grad_norm=1.0)
    sharding = NamedSharding(acc.mesh, PartitionSpec(("dp_replicate", "dp_shard")))
    b = {
        "x": jax.device_put(ids[:, :-1], sharding),
        "y": jax.device_put(ids[:, 1:], sharding),
    }
    return acc, model, step, b


_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
# One per executable built: XLA's compile, or its load from the persistent cache.
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Counts executables built or loaded, and the seconds spent on them."""

    def __init__(self):
        import jax.monitoring

        self.executables = 0
        self.cache_hits = 0
        self.trace_s = 0.0    # tracing and lowering: never cached
        self.backend_s = 0.0  # what a warm persistent cache saves
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _TRACE_EVENTS:
            self.trace_s += duration
        elif event == _BACKEND_EVENT:
            self.backend_s += duration
            self.executables += 1

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


class Smoke:
    def __init__(self, rehearse: bool):
        import jax

        self.rehearse = rehearse
        self.device = jax.devices()[0]
        self.n = jax.device_count()
        self.tag = f"platform={self.device.platform}" + (" rehearsal" if rehearse else "")
        self.log = CompileLog()

    def say(self, msg: str) -> None:
        print(f"[{self.tag}] {msg}", flush=True)

    def check(self, ok: bool, msg: str) -> None:
        if not ok:
            raise SystemExit(f"[{self.tag}] FAILED: {msg}")

    @contextlib.contextmanager
    def phase(self, name: str):
        log = self.log
        before = (log.executables, log.cache_hits, log.trace_s, log.backend_s)
        t0 = time.perf_counter()
        self.say(f"phase {name}: start")
        yield
        wall = time.perf_counter() - t0
        n, hits, trace, backend = (
            now - was for now, was in
            zip((log.executables, log.cache_hits, log.trace_s, log.backend_s), before))
        self.say(
            f"phase {name}: PASS wall={wall:.1f}s = compile {trace + backend:.1f}s "
            f"(trace+lower {trace:.1f}s, XLA {backend:.1f}s for {n} executables, {hits} "
            f"of them from the persistent cache) + run {max(0.0, wall - trace - backend):.1f}s"
        )

    def memory(self, where: str) -> None:
        import jax

        for d in jax.devices():
            stats = d.memory_stats()
            peak = stats["peak_bytes_in_use"] / 2**30 if stats else float("nan")
            self.say(f"memory after {where}: device {d.id} peak_bytes_in_use={peak:.2f} GiB")


# -- phase 1 ----------------------------------------------------------------


def device_phase(s: Smoke, cache_dir: str) -> None:
    import jax
    import jaxlib
    from importlib.metadata import version

    from accelerate_tpu import native

    d = s.device
    s.say(f"device_kind={d.device_kind} device_count={s.n} "
          f"process_count={jax.process_count()}")
    s.say(f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={version('libtpu')}")
    s.say(f"compile_cache={cache_dir}")
    s.say(f"native={'built' if native.get_lib() is not None else 'numpy'}")


# -- phase 2 ----------------------------------------------------------------


def kernels_phase(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.flash_attention import blockwise_attention
    from accelerate_tpu.ops.pallas_flash import pallas_flash_attention
    from accelerate_tpu.utils import is_tpu_available

    interpret = not is_tpu_available()  # compiled on the chip, always
    if s.rehearse:
        shapes = [("trainer", 1, 256, 4, 4, 128), ("gqa-d64", 1, 256, 4, 2, 64)]
    else:
        shapes = [("trainer", PER_CHIP_BATCH, SEQ, 16, 16, 128),
                  ("gqa-d64", 2, 1024, 8, 2, 64)]
    for name, b, seq, hq, hkv, d in shapes:
        rng = np.random.default_rng(0)
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((b, seq, h, d)), jnp.bfloat16)
            for h in (hq, hkv, hkv, hq)
        )

        def kernel_loss(q, k, v):
            out = pallas_flash_attention(q, k, v, causal=True, interpret=interpret)
            return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

        def ref_loss(q, k, v):
            out = blockwise_attention(q, k, v, causal=True)
            return jnp.sum(out * g.astype(jnp.float32)), out

        (_, out), grads = jax.jit(
            jax.value_and_grad(kernel_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, want), want_grads = jax.jit(
                jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
            )(*(x.astype(jnp.float32) for x in (q, k, v)))
        for label, got, ref in zip(
            ("fwd", "dQ", "dK", "dV"), (out, *grads), (want, *want_grads)
        ):
            got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
            s.check(np.isfinite(got).all(), f"kernel {name} {label}: non-finite values")
            err = float(np.abs(got - ref).max() / np.abs(ref).max())
            s.say(f"kernel {name} (b{b} s{seq} hq{hq} hkv{hkv} d{d} bf16, "
                  f"interpret={interpret}) {label}: max err / max ref = {err:.4f}")
            s.check(err < KERNEL_TOL, f"kernel {name} {label}: {err:.4f} >= {KERNEL_TOL}")


# -- phase 3 ----------------------------------------------------------------


def _bytes_per_device(tree) -> dict:
    import jax

    held = {d.id: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            held[shard.device.id] += shard.data.nbytes
    return held


def trainer_phase(s: Smoke, name: str, parallelism_config=None, steps: int = 5):
    """Two warm-up steps, then ``steps`` more; returns ``(acc, model)``."""
    import jax

    if s.rehearse:
        import jax.numpy as jnp

        from accelerate_tpu.models import LlamaConfig

        cfg = LlamaConfig.tiny(dtype=jnp.bfloat16, remat=True, remat_policy=REMAT_POLICY,
                               attention_impl="flash", num_key_value_heads=4)
        per_chip_batch, seq = 2, 128
    else:
        cfg, per_chip_batch, seq = llama_1b(SEQ, REMAT_POLICY), PER_CHIP_BATCH, SEQ
    acc, model, step, batch = build_trainer(
        cfg, per_chip_batch, seq, parallelism_config=parallelism_config)
    s.say(f"{name}: {model.num_parameters() / 1e9:.3f}B params, hidden {cfg.hidden_size} x "
          f"{cfg.num_hidden_layers} layers, seq {seq}, per-chip batch {per_chip_batch} "
          f"(global {per_chip_batch * s.n}), remat {cfg.remat_policy}, "
          f"mesh {dict(acc.mesh.shape)}")

    state, losses, built = acc.train_state, [], []
    for i in range(2 + steps):
        n0, t0 = s.log.executables, time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        built.append(s.log.executables - n0)
        s.say(f"{name}: step {i} ({'warm-up' if i < 2 else 'steady'}) "
              f"loss={losses[-1]:.4f} wall={time.perf_counter() - t0:.2f}s "
              f"executables_built={built[-1]}")
    s.check(bool(np.isfinite(losses).all()), f"{name}: non-finite loss {losses}")
    s.check(losses[-1] < losses[0], f"{name}: loss did not fall on the fixed batch {losses}")
    s.check(sum(built[2:]) == 0, f"{name}: compiles after warm-up: {built}")
    s.say(f"{name}: executables built per step {built} "
          f"(step 1 {'DID' if built[1] else 'did not'} compile again)")

    hlo = step.jitted.lower(state, batch).compile().as_text()
    mosaic = hlo.count('custom_call_target="tpu_custom_call"')
    s.say(f"{name}: Mosaic custom calls in the compiled step: {mosaic}")
    if s.device.platform == "tpu":
        # forward, dQ and dK/dV at least: the kernel did not give way to
        # blockwise_attention anywhere in the step.
        s.check(mosaic >= 3, f"{name}: {mosaic} Mosaic custom calls in the step's HLO")

    for what, tree in (("params", state.params), ("optimizer state", state.opt_state)):
        held = _bytes_per_device(tree)
        s.say(f"{name}: {what} bytes per device: "
              + ", ".join(f"{d}: {b / 2**20:.0f} MiB" for d, b in held.items()))
        s.check(all(b > 0 for b in held.values()),
                f"{name}: a device holds no share of the {what}: {held}")
        if s.n > 1:
            s.check(max(held.values()) < 0.75 * sum(held.values()),
                    f"{name}: {what} are not sharded: {held}")
    s.memory(name)
    return acc, model


# -- phase 4 ----------------------------------------------------------------


def server_phase(s: Smoke, acc, trained_model) -> None:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import Model, generate, init_cache
    from accelerate_tpu.generation import GENERATION_PLANS
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.utils import ServingConfig

    module, cfg = trained_model.module, trained_model.module.config
    trained = acc.train_state.params
    # The colocated engine keeps its cache and slot state on one device and
    # takes params where they are: on several chips it serves from chip 0,
    # from a gathered copy of the FSDP-sharded params.
    params = trained if s.n == 1 else jax.device_put(trained, jax.devices()[0])
    model = Model(module=module, params=params)

    lens = [n // 4 for n in PROMPT_LENS] if s.rehearse else list(PROMPT_LENS)
    new_tokens = 8 if s.rehearse else NEW_TOKENS
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,), dtype=np.int32) for n in lens]

    engine = ServingEngine(model, ServingConfig(n_slots=8, max_new_tokens=new_tokens))
    leaf = jax.tree.leaves(params)[0]
    s.say(f"server: params on devices {sorted(d.id for d in leaf.sharding.device_set)}, "
          f"cache on devices {sorted(d.id for d in engine._cache.k.sharding.device_set)}, "
          f"{engine.n_slots} slots x {engine.t_max} rows, prefill ladder {engine.ladder}")
    t0 = time.perf_counter()
    engine.warmup()
    s.say(f"server: warmup (every prefill rung + decode) wall={time.perf_counter() - t0:.1f}s")

    n0, t0 = s.log.executables, time.perf_counter()
    ids = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    rows = {}
    while engine.pending:
        engine.tick()
        rows.update((r["id"], r) for r in engine.poll())
    s.say(f"server: {len(ids)} requests drained wall={time.perf_counter() - t0:.1f}s "
          f"executables_built={s.log.executables - n0}")
    for rid, prompt in zip(ids, prompts):
        r = rows[rid]
        s.check(r["status"] == "ok", f"server: request {rid} finished {r['status']}")
        s.check(r["new_tokens"] == new_tokens,
                f"server: request {rid} got {r['new_tokens']} of {new_tokens} tokens")
        toks = np.asarray(r["tokens"])
        s.check(toks.shape == (len(prompt) + new_tokens,) and
                bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
                f"server: request {rid} row malformed: shape {toks.shape}")
    st = engine.stats()
    s.say(f"server: statuses ok={len(ids)} decode_executables={st['decode_executables']} "
          f"prefill_executables={st['prefill_executables']} "
          f"steady_recompiles={st['steady_recompiles']} faults={st['faults']}")
    s.check(st["decode_executables"] == 1, "server: more than one decode executable")
    s.check(st["steady_recompiles"] == 0, "server: recompiled in steady state")
    s.check(s.log.executables == n0, "server: built executables while serving")
    f = st["faults"]
    s.check(f["slot_quarantines"] == 0 and f["retries"] == 0 and f["failed"] == 0,
            f"server: the nonfinite sentinel or a retry fired: {f}")

    # Logit parity, prefill: flax module (training path) vs the cached plan.
    prompt = prompts[0][None]
    fwd = GENERATION_PLANS[type(module).__name__]
    got, _ = jax.jit(lambda p, x, c: fwd(cfg, p, x, c))(
        params, prompt, init_cache(cfg, 1, len(prompts[0]) + 1, dtype=jnp.bfloat16))
    # n copies of the prompt: a batch the dp axes divide keeps the kernel in.
    want = jax.jit(lambda p, x: module.apply({"params": p}, x)[:, -1])(
        trained, np.repeat(prompt, s.n, axis=0))
    got, want = np.asarray(got, np.float32)[0], np.asarray(want, np.float32)[0]
    rms = float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))
    s.say(f"server: prefill logits, cached plan vs flax module: relative RMS {rms:.4f}, "
          f"argmax {'equal' if got.argmax() == want.argmax() else 'differs'}")
    s.check(np.isfinite(got).all() and rms < LOGIT_RMS_TOL,
            f"server: cached-plan logits off by relative RMS {rms:.4f}")

    # Reported, not gated: with random weights the argmax flips on rounding
    # between the engine's (n_slots, 1) program and generate()'s (1, 1) one.
    ref = np.asarray(generate(model, prompt, max_new_tokens=new_tokens))[0]
    same = float(np.mean(ref[len(prompts[0]):] == rows[ids[0]]["tokens"][len(prompts[0]):]))
    s.say(f"server: request 0 tokens equal to generate(): {same:.2f} (reported, not gated)")
    engine.close()
    s.memory("server")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX finds (debugging aid)")
    args = ap.parse_args()

    from accelerate_tpu.compile_manager import place_compile_cache

    cache_dir = place_compile_cache()
    if not args.rehearse:
        require_tpu()
    s = Smoke(args.rehearse)

    with s.phase("device"):
        device_phase(s, cache_dir)
    with s.phase("kernels"):
        kernels_phase(s)
    with s.phase("trainer"):
        acc, model = trainer_phase(s, "trainer")
    with s.phase("server"):
        server_phase(s, acc, model)
    if s.n >= 4 and s.n % 2 == 0:
        from accelerate_tpu.parallelism_config import ParallelismConfig

        del acc, model
        with s.phase("trainer dp_shard x tp=2"):
            trainer_phase(s, "trainer-tp2", steps=2, parallelism_config=ParallelismConfig(
                dp_shard_size=s.n // 2, tp_size=2))

    d = s.device
    s.say("all phases passed")
    result = {"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                     "count": s.n}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
