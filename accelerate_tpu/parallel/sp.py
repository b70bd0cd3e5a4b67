"""Ulysses/ALST sequence parallelism over the ``sp`` mesh axis.

The reference delegates to DeepSpeed's ``UlyssesSPAttentionHF`` (head-sharded
attention via all-to-all) + a sequence-sharding dataloader adapter
(reference: accelerator.py:2386-2437, docs/concept_guides/sequence_parallelism.md).
TPU-native: inputs arrive sequence-sharded over ``sp`` (the batch
PartitionSpec already shards the seq dim); inside ``shard_map`` an
``all_to_all`` reshards seq→heads, full-sequence flash attention runs on each
head group, and a second ``all_to_all`` reshards back. Collectives ride ICI.

Requires num_heads % sp == 0 (kv heads are repeated up to q heads first when
GQA would not divide)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.flash_attention import auto_flash_attention, flash_attention, _repeat_kv


def _mesh():
    from ..state import AcceleratorState

    return AcceleratorState().mesh


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mesh=None,
    axis_name: str = "sp",
):
    """q/k/v: (B, S, H, D) with S sharded over ``sp``. Returns same layout."""
    if mesh is None:
        mesh = _mesh()
    sp = mesh.shape[axis_name]
    if sp == 1:
        return auto_flash_attention(q, k, v, causal=causal, mesh=mesh)

    hq = q.shape[2]
    if hq % sp != 0:
        raise ValueError(f"num_attention_heads {hq} must divide by sp_size {sp}")
    # GQA: repeat kv heads up front so the head all-to-all is uniform.
    k, v = _repeat_kv(k, v, hq)

    spec = P(("dp_replicate", "dp_shard"), axis_name, "tp", None)

    def _local(q_c, k_c, v_c):
        # (B, S/sp, H, D) → all_to_all → (B, S, H/sp, D)
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

        qh, kh, vh = seq_to_heads(q_c), seq_to_heads(k_c), seq_to_heads(v_c)
        out = flash_attention(qh, kh, vh, causal=causal)
        return heads_to_seq(out)

    shard = jax.shard_map(
        _local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )
    return shard(q, k, v)
