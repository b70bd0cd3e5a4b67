"""Needed work of a Llama-shaped decoder, shared by the families: FLOPs of a
served request and bytes of a decode step. Of the algorithm, not of the
implementation: live cache rows, not the buffer; the experts a token is routed
to, not all of them."""

from __future__ import annotations


def request_flops(cfg: dict, active_layer_matmul_params: int, prompt_len: int,
                  new_tokens: int) -> float:
    """Forward FLOPs the algorithm needs to serve one request: every prompt
    and output token but the last through the layers' matmuls (2 per weight),
    causal attention over the rows live at each position (QK and PV, 2 each),
    and the head for the positions that produce a token. No embedding lookup."""
    n = prompt_len + new_tokens - 1               # positions run through the layers
    rows = n * (n + 1) // 2                       # sum of the rows each attends
    layers = cfg["num_hidden_layers"]
    attn = 4 * layers * cfg["num_attention_heads"] * cfg["head_dim"] * rows
    mats = 2 * layers * active_layer_matmul_params * n
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] * new_tokens
    return float(attn + mats + head)


def cache_bytes_per_row(cfg: dict) -> int:
    """bf16 keys and values of one token over all layers."""
    return 2 * 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"]


def decode_step_bytes(cfg: dict, read_layer_matmul_params: int, live_rows: float) -> float:
    """Bytes one decode step has to read: every matmul weight it uses, once,
    in bf16, and the cache rows that are live."""
    weights = 2 * (cfg["num_hidden_layers"] * read_layer_matmul_params
                   + cfg["hidden_size"] * cfg["vocab_size"])
    return float(weights + cache_bytes_per_row(cfg) * live_rows)
