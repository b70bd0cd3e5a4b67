"""One compiled train step on the real chip (TPU tier).

The cheap end-to-end canary: a ~125M Llama fused train step (bf16 compute,
Pallas flash attention, remat) must compile and produce a finite decreasing
loss on hardware. Catches on-chip-only failures (Mosaic lowering inside the
full model, compile-time OOM, donation) in about a minute. The 1B model is
``chip_smoke.py``'s job.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax


def test_train_step_125m_smoke():
    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, cross_entropy_loss
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import set_seed

    for cls in (AcceleratorState, GradientState, PartialState):
        cls._reset_state()
    set_seed(0)
    cfg = LlamaConfig(
        vocab_size=8192, hidden_size=768, intermediate_size=2048,
        num_hidden_layers=12, num_attention_heads=12, num_key_value_heads=12,
        max_position_embeddings=1024, dtype=jnp.bfloat16,
    )
    module = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    # Batch derives from the mesh: the default Accelerator shards batches
    # over every attached device.
    bsz = max(4, jax.device_count())
    ids = rng.integers(0, cfg.vocab_size, size=(bsz, 513), dtype=np.int32)

    acc = Accelerator(mixed_precision="bf16")
    model = Model.from_flax(module, jax.random.key(0), ids[:, :-1])
    model, _ = acc.prepare(model, optax.adamw(1e-3))

    def loss_fn(params, batch):
        logits = module.apply({"params": params}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    step = acc.prepare_train_step(loss_fn)
    batch = {"x": jnp.asarray(ids[:, :-1]), "y": jnp.asarray(ids[:, 1:])}
    state = acc.train_state
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(np.asarray(metrics["loss"])))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
