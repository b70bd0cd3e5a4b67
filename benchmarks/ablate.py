"""Ablation harness for the bench workload (real chip).

Measures tokens/s/chip for the FSDP Llama train step across remat policies
and loss implementations, to pick bench.py's default configuration.

    python benchmarks/ablate.py [--seq 2048] [--iters 20]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.compile_manager import place_compile_cache

place_compile_cache()


def measure(seq, iters, *, remat, remat_policy, fused_loss, batch=None, fp8=False):
    import jax
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import (
        LlamaConfig, LlamaForCausalLM, cross_entropy_loss, fused_cross_entropy_loss,
    )
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import FullyShardedDataParallelPlugin, set_seed

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    set_seed(0)
    import jax.numpy as jnp

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=4096,
        num_hidden_layers=16, num_attention_heads=8, num_key_value_heads=8,
        max_position_embeddings=seq, dtype=jnp.bfloat16,
        remat=remat, remat_policy=remat_policy, attention_impl="flash",
        fp8=fp8,
    )
    if batch is None:
        batch = 8 if seq <= 2048 else 2
    module = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32)

    acc = Accelerator(mixed_precision="bf16", fsdp_plugin=FullyShardedDataParallelPlugin())
    model = Model.from_flax(module, jax.random.key(0), ids[:, :-1])
    model, _ = acc.prepare(model, optax.adamw(3e-4, weight_decay=0.1))

    if fused_loss:
        def loss_fn(params, b):
            return fused_cross_entropy_loss(cfg, params, b["x"], b["y"])
    else:
        def loss_fn(params, b):
            logits = module.apply({"params": params}, b["x"])
            return cross_entropy_loss(logits, b["y"])

    step = acc.prepare_train_step(loss_fn, max_grad_norm=1.0)
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(acc.mesh, PartitionSpec(("dp_replicate", "dp_shard")))
    b = {
        "x": jax.device_put(ids[:, :-1], sharding),
        "y": jax.device_put(ids[:, 1:], sharding),
    }
    state = acc.train_state
    for _ in range(2):
        state, metrics = step(state, b)
        jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, b)
    loss = float(jax.block_until_ready(metrics["loss"]))
    dt = (time.perf_counter() - t0) / iters
    assert np.isfinite(loss), loss
    return batch * seq / dt / len(jax.devices()), loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--variants", type=str, default="")
    args = ap.parse_args()

    variants = {
        "remat-flash+naive-ce": dict(remat=True, remat_policy="flash", fused_loss=False),
        "remat-flash+fused-ce": dict(remat=True, remat_policy="flash", fused_loss=True),
        "remat-dots+naive-ce": dict(remat=True, remat_policy="dots", fused_loss=False),
        "remat-dots+fused-ce": dict(remat=True, remat_policy="dots", fused_loss=True),
        "no-remat+fused-ce": dict(remat=False, remat_policy="flash", fused_loss=True),
        "no-remat+naive-ce": dict(remat=False, remat_policy="flash", fused_loss=False),
        # fp8 (QDQ e4m3/e5m2 HYBRID) vs its bf16 twin — the reference's
        # headline fp8 claim is +25% tok/s at loss parity
        # (examples/torch_native_parallelism/README.md); this row either
        # reproduces that on TPU or documents that the XLA fp8 rewriter
        # does not pay off on this generation (docs/performance.md).
        "fp8+remat-dots+naive-ce": dict(remat=True, remat_policy="dots", fused_loss=False, fp8=True),
    }
    if args.variants:
        keep = args.variants.split(",")
        variants = {k: v for k, v in variants.items() if k in keep}
    failed = []
    for name, kw in variants.items():
        # One row per variant, flushed: a variant that does not fit must not
        # cost the rows of those that do. Any failure is a nonzero exit.
        try:
            tok, loss = measure(args.seq, args.iters, **kw)
            print(f"{name:28s} {tok:10.1f} tok/s/chip   loss {loss:.4f}", flush=True)
        except Exception as e:
            failed.append(name)
            print(f"{name:28s} FAILED: {type(e).__name__}: {str(e)[:200]}", flush=True)
    if failed:
        sys.exit(f"{len(failed)} variant(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
