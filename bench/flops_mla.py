"""Operations and bytes that a DeepSeek-V2 (multi-head latent attention,
shared experts, a dense prefix) decode step needs, from shapes alone, as
``bench/flops.py`` counts them for the plain decoders: what the
mathematics requires, not what a program happens to execute; no
capacity padding, no lane padding of the latent rows. A multiply-add is 2
FLOP.

``cfg`` is a configuration file's dict of the ``deepseek`` family
(``bench/configs/deepseek-*.json``).
"""

from __future__ import annotations


def latent_width(cfg) -> int:
    """Columns of one cached latent row: ``c_kv`` and the rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attn_params(cfg) -> int:
    """One layer's attention weights: the query, the latent down-projection,
    the key and value up-projections (absorbed, still read once a step) and
    the output."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * H * qk + d * latent_width(cfg)
            + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * d)


def moe_params_reached(cfg, tokens: float) -> float:
    """One MoE layer's weights a step multiplies by: the router, the held
    experts that ``tokens`` top-k assignments over all the published
    experts reach (expected under uniform routing), and the shared
    experts."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E, k = cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"]
    reached = cfg["n_routed_experts"] * (1.0 - (1.0 - k / E) ** tokens)
    return d * E + reached * 3 * d * f + 3 * d * f * cfg["n_shared_experts"]


def decode_step_bytes(cfg, tokens: float, context: float, itemsize: int = 2) -> float:
    """HBM bytes one decode step of ``tokens`` slots needs: every weight it
    multiplies by (the dense layers, the MoE layers' router, held experts
    reached and shared experts, every layer's attention, the unembedding)
    and the latent rows of the ``context`` positions in use, all at
    ``itemsize``. Embedding rows and activations are left out."""
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    d = cfg["hidden_size"]
    weights = (L * attn_params(cfg) + dense * 3 * d * cfg["intermediate_size"]
               + (L - dense) * moe_params_reached(cfg, tokens)
               + d * cfg["vocab_size"])
    return itemsize * (weights + context * L * latent_width(cfg))


def mla_decode_flops(cfg, context: float) -> float:
    """FLOP of the absorbed attention over ``context`` attended positions
    (summed over slots and steps) in every layer: each head scores its
    query against a latent row and sums the rows' ``c_kv`` parts."""
    return (2.0 * cfg["num_attention_heads"]
            * (latent_width(cfg) + cfg["kv_lora_rank"])
            * context * cfg["num_hidden_layers"])


def mla_decode_bytes(cfg, context: float, itemsize: int = 2) -> float:
    """Latent rows the attention reads for ``context`` attended positions
    in every layer, at ``itemsize``."""
    return itemsize * latent_width(cfg) * context * cfg["num_hidden_layers"]
