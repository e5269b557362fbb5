"""Model FLOPs of the benchmark's configurations, from their sizes alone.

Counts are of the work the model requires, not of what a program happens
to execute: a multiply-add is 2 FLOPs, capacity padding and recomputed
activations are not counted, causal attention counts each query against
the keys at or before it. The embedding lookup is a gather and counts 0.
"""

from __future__ import annotations


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def attn_params(m: dict) -> int:
    d, hd = m["d_model"], head_dim(m)
    q = m["n_heads"] * hd
    kv = m["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d


def layer_active_params(m: dict) -> int:
    """Weights one token multiplies through in one layer: attention
    projections, the router and its top_k experts' SwiGLU (gate, up,
    down)."""
    d, f = m["d_model"], m["d_ff_expert"]
    return (attn_params(m) + d * m["n_experts"]
            + m["top_k"] * 3 * d * f)


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["d_model"]


def active_params(m: dict) -> int:
    """Active parameters per token: every layer plus the LM head."""
    return m["n_layers"] * layer_active_params(m) + head_params(m)


def causal_attn_flops_fwd(m: dict, seq: int) -> float:
    """Forward score and value FLOPs of one causal sequence over all layers:
    query i attends to i + 1 keys, QK^T and PV each 2 FLOPs per
    multiply-add over n_heads * head_dim."""
    width = m["n_heads"] * head_dim(m)
    pairs = seq * (seq + 1) / 2
    return m["n_layers"] * 4.0 * width * pairs


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """Forward plus backward of one step: 6 x active params x tokens, and
    3 x the causal attention forward (backward is twice the forward)."""
    tokens = batch * seq
    return (6.0 * active_params(m) * tokens
            + 3.0 * batch * causal_attn_flops_fwd(m, seq))


def serve_token_flops(m: dict, n_ctx: int, logits: bool) -> float:
    """Forward FLOPs of one served token that sees ``n_ctx`` cached or
    fresh positions (itself included). ``logits``: whether the LM head runs
    for it (every decode token; only the last token of a prefill chunk)."""
    width = m["n_heads"] * head_dim(m)
    flops = 2.0 * m["n_layers"] * layer_active_params(m)
    flops += m["n_layers"] * 4.0 * width * n_ctx
    if logits:
        flops += 2.0 * head_params(m)
    return flops


def prefill_chunk_flops(m: dict, start: int, length: int) -> float:
    """A prompt chunk of ``length`` tokens at positions [start, start +
    length): token p sees p + 1 positions; the head runs for the last."""
    width = m["n_heads"] * head_dim(m)
    ctx = length * start + length * (length + 1) / 2
    return (2.0 * m["n_layers"] * layer_active_params(m) * length
            + m["n_layers"] * 4.0 * width * ctx
            + 2.0 * head_params(m))
