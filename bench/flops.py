"""Operations and bytes that the algorithms need, from shapes alone.

These count what the mathematics requires, not what a program happens to
execute: no recomputation, no capacity padding, no masked upper triangle.
A utilization or roofline share built on them can therefore not pass 100%
unless a time leaves out part of the work. A multiply-add is 2 FLOP.

``cfg`` is a configuration file's dict (``bench/configs/*.json``).
"""

from __future__ import annotations


def _d(cfg):
    return cfg["hidden_size"]


def _heads(cfg):
    hd = cfg.get("head_dim") or _d(cfg) // cfg["num_attention_heads"]
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], hd


def attn_proj_params(cfg) -> int:
    """q, k, v and o projection weights of one layer."""
    h, kv, hd = _heads(cfg)
    d = _d(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def ffn_params_per_token(cfg) -> int:
    """Feed-forward weights one token multiplies by in one layer: the
    router and the top-k experts for a MoE layer, the MLP otherwise."""
    d, f = _d(cfg), cfg["intermediate_size"]
    if cfg.get("num_local_experts"):
        return d * cfg["num_local_experts"] + cfg["num_experts_per_tok"] * 3 * d * f
    return 3 * d * f


def decoder_token_flops(cfg) -> float:
    """Forward FLOP per token outside the attention scores: projections,
    feed-forward (top-k experts only) and the unembedding."""
    per_layer = attn_proj_params(cfg) + ffn_params_per_token(cfg)
    return 2.0 * (cfg["num_hidden_layers"] * per_layer
                  + _d(cfg) * cfg["vocab_size"])


def attn_score_flops(cfg, context: float) -> float:
    """Forward FLOP of q·k and p·v for ``context`` attended positions
    summed over queries (a query at position p attends p + 1)."""
    h, _, hd = _heads(cfg)
    return 4.0 * h * hd * context * cfg["num_hidden_layers"]


def causal_context(seq: int) -> float:
    """Positions attended, summed over the queries of one causal row."""
    return seq * (seq + 1) / 2.0


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward and backward (3x forward) FLOP per token of a causal row of
    ``seq`` tokens. Recomputation (remat) is not counted."""
    fwd = decoder_token_flops(cfg) + attn_score_flops(cfg, causal_context(seq)) / seq
    return 3.0 * fwd


def flash_fwd_flops(bh: int, seq: int, head_dim: int, causal: bool = True) -> float:
    """One flash-attention forward call over ``bh`` (batch x heads) rows."""
    ctx = causal_context(seq) if causal else float(seq * seq)
    return 4.0 * bh * head_dim * ctx


def flash_fwd_bytes(bh: int, seq: int, head_dim: int, itemsize: int = 2) -> float:
    """q, k, v read and o written once."""
    return 4.0 * bh * seq * head_dim * itemsize


def decode_step_bytes(cfg, tokens: int, context: float, itemsize: int = 2) -> float:
    """HBM bytes one decode step needs: every weight it multiplies by, the
    experts that ``tokens`` top-k assignments reach (expected under
    uniform routing), and the K/V of the ``context`` positions in use, all
    at the configured ``itemsize``. Embedding rows and activations are
    left out (under 0.1% here)."""
    d, L = _d(cfg), cfg["num_hidden_layers"]
    E = cfg.get("num_local_experts")
    f = cfg["intermediate_size"]
    if E:
        k = cfg["num_experts_per_tok"]
        reached = E * (1.0 - (1.0 - k / E) ** tokens)
        ffn = d * E + reached * 3 * d * f
    else:
        ffn = 3 * d * f
    weights = L * (attn_proj_params(cfg) + ffn) + d * cfg["vocab_size"]
    _, kv, hd = _heads(cfg)
    kv_bytes = context * L * 2 * kv * hd
    return itemsize * (weights + kv_bytes)
