"""Compile-time benchmark: scan-over-layers vs unrolled blocks.

The reference's regional-compilation headline is 5-9x faster compiles at
inference parity (/root/reference/benchmarks/torch.compile/README.md —
Llama-3.1-8B: 2.9 s regional vs 20.4 s full). The TPU-native analog is
``scan_layers=True``: ``nn.scan`` compiles ONE block and iterates it, so
compile time is O(1) in depth instead of O(L). This bench measures wall-time
to trace+compile a forward step both ways at two depths and prints one JSON
row per configuration (streamed, driver-kill-proof).

    python benchmarks/compile_bench.py [--layers 18 --hidden 2048]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(layers: int, hidden: int, scan: bool, seq: int = 256) -> dict:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=hidden * 11 // 4,
        num_hidden_layers=layers, num_attention_heads=max(1, hidden // 128),
        num_key_value_heads=max(1, hidden // 128), max_position_embeddings=seq,
        dtype=jnp.bfloat16, scan_layers=scan,
    )
    module = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, seq), dtype=np.int32))
    params = jax.eval_shape(lambda k: module.init(k, ids), jax.random.key(0))["params"]

    def fwd(p, x):
        return module.apply({"params": p}, x)

    t0 = time.perf_counter()
    lowered = jax.jit(fwd).lower(params, ids)
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    del compiled
    return {
        "row": "compile", "scan_layers": scan, "layers": layers,
        "hidden": hidden, "seconds": round(dt, 2),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=18)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    import jax

    # This script times compilation itself: a persistent-cache hit would be
    # timed as a compile, so the cache is off here whatever the environment.
    jax.config.update("jax_enable_compilation_cache", False)
    print(json.dumps({"row": "start", "platform": jax.devices()[0].platform}), flush=True)

    rows = []
    for scan in (True, False):
        rows.append(measure(args.layers, args.hidden, scan, args.seq))
        print(json.dumps(rows[-1]), flush=True)
    speedup = rows[1]["seconds"] / max(rows[0]["seconds"], 1e-9)
    print(json.dumps({
        "row": "summary", "layers": args.layers,
        "scan_compile_s": rows[0]["seconds"], "unrolled_compile_s": rows[1]["seconds"],
        "speedup": round(speedup, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
