"""Operations and bytes that the work needs, from its shapes alone.

These count what the algorithm requires, not what an implementation
happens to do: no padding, no recomputation, no wasted step.
"""
from __future__ import annotations


def event_join_work(n_events: int, n_triggers: int) -> tuple:
    """``(ops, bytes)`` of one event-join call over ``n_events`` routed
    events and ``n_triggers`` trigger rows: one add per event; the event
    ids read (4 bytes each), counts and thresholds read and new counts and
    fire flags written (int32 each)."""
    return float(n_events), float(4 * n_events + 4 * 2 * n_triggers
                                  + 4 * 2 * n_triggers)


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak_ops, nbytes / peak_bw)


def dense_lm_matmul_params(sizes: dict) -> int:
    """Weights a token multiplies through, the output head included and the
    embedding (a lookup) left out."""
    L, D = sizes["num_hidden_layers"], sizes["hidden_size"]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes.get("head_dim", D // H)
    F = sizes["intermediate_size"]
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return L * per_layer


def dense_lm_request_flops(sizes: dict, prompt_len: int, new_tokens: int) -> float:
    """Model FLOPs that one request needs: its prompt through every layer,
    then each generated token after the first through every layer; the
    output head once per generated token; and attention over the causal
    prefix (``4 * heads * head_dim`` per query-key pair per layer)."""
    L, D = sizes["num_hidden_layers"], sizes["hidden_size"]
    H = sizes["num_attention_heads"]
    hd = sizes.get("head_dim", D // H)
    V = sizes["vocab_size"]
    body = 2 * dense_lm_matmul_params(sizes)
    positions = prompt_len + new_tokens - 1      # tokens run through the body
    # query at position p attends p + 1 keys
    pairs = positions * (positions + 1) // 2
    return float(body * positions + 4 * H * hd * L * pairs
                 + 2 * D * V * new_tokens)
