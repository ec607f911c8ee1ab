"""Bytes a decode step of a dense GQA model must read, from its shapes
alone: what the algorithm requires, not what an implementation happens to
read (no padding slots, no cache beyond each row's valid prefix)."""
from __future__ import annotations

from .work import dense_lm_matmul_params

BF16_BYTES = 2


def decode_weight_bytes(sizes: dict) -> int:
    """The weights one decode step multiplies through: every layer's
    matmuls and the output head (the embedding is a lookup), in bf16."""
    head = sizes["hidden_size"] * sizes["vocab_size"]
    return BF16_BYTES * (dense_lm_matmul_params(sizes) + head)


def kv_bytes_per_token(sizes: dict) -> int:
    """Keys and values of one cached position across the layers, in bf16."""
    H = sizes["num_attention_heads"]
    hd = sizes.get("head_dim", sizes["hidden_size"] // H)
    return (BF16_BYTES * 2 * sizes["num_hidden_layers"]
            * sizes["num_key_value_heads"] * hd)


def mean_kv_tokens_per_step(prompt_tokens: int, requests: int, batches: int,
                            new_tokens: int) -> float:
    """Valid cached positions a decode step reads, summed over its rows and
    averaged over the steps of ``batches`` batches: step ``j`` (from 0) of a
    row with an ``n``-token prompt reads ``n + j + 1`` positions, so a
    batch's ``new_tokens`` steps read ``new_tokens * sum(n) + rows *
    new_tokens * (new_tokens + 1) / 2``."""
    T = new_tokens
    total = T * prompt_tokens + requests * T * (T + 1) / 2
    return total / (batches * T)


def decode_step_bytes(sizes: dict, kv_tokens: float) -> float:
    """Least bytes of one decode step that reads ``kv_tokens`` valid cached
    positions over its rows."""
    return decode_weight_bytes(sizes) + kv_tokens * kv_bytes_per_token(sizes)
