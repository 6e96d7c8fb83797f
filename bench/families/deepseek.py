"""DeepSeek-V2 language models (DeepSeek-V2-Lite): the program's
configuration and its parameters, made on the device from the seed.

Every weight matrix is ``bench.weights.tensor`` at the configuration's
``initializer_range``, one layer (and one expert) at a time under the path
that ``bench/reference/deepseek.py`` regenerates; norm scales are ones.
Layers keep their published index: layer 0 is the dense one, and the
experts of a MoE layer keep their global index, so a chip holding experts
``first_held_expert`` onwards builds exactly those. The whole tree is made
in one jitted call, in the dtype it is served in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as W


def program_config(cfg: dict):
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

    if (cfg["q_lora_rank"] is not None or cfg["scoring_func"] != "softmax"
            or cfg["topk_method"] != "greedy" or cfg["routed_scaling_factor"] != 1
            or cfg["moe_layer_freq"] != 1):
        raise NotImplementedError("the program runs DeepSeek-V2 with a direct query "
                                  "projection and greedy softmax routing, unscaled")
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["model_type"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        attention="mla", rope="rope", rope_theta=float(cfg["rope_theta"]),
        yarn=YaRNConfig(factor=float(rs["factor"]),
                        original_max_position=rs["original_max_position_embeddings"],
                        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
                        mscale=float(rs["mscale"]),
                        mscale_all_dim=float(rs["mscale_all_dim"])),
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(num_experts=cfg["published"]["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      shared_experts=cfg["n_shared_experts"],
                      norm_topk_probs=cfg["norm_topk_prob"],
                      capacity_factor=cfg["capacity_factor"],
                      held_experts=(cfg["first_held_expert"], cfg["n_routed_experts"])),
        first_dense_layers=cfg["first_k_dense_replace"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def _ffn(t, d, f):
    return {"w_in": t("w_in", (d, f)), "w_gate": t("w_gate", (d, f)),
            "w_out": t("w_out", (f, d))}


def layer_tensors(cfg: dict, layer_key, dtype, dense: bool) -> dict:
    """One layer's parameters, keyed as the program's member tree;
    ``layer_key`` is the layer's key (``("layer", i)`` folded in)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    std = cfg["initializer_range"]

    def t(name, shape, *extra):
        return W.tensor(layer_key, (*extra, name), shape, std, dtype)

    ones = lambda n: {"scale": jnp.ones((n,), dtype)}
    mixer = {"wq": t("wq", (d, H * (nope + rope))), "wkv_a": t("wkv_a", (d, r + rope)),
             "wkv_b": t("wkv_b", (r, H * (nope + v))), "wo": t("wo", (H * v, d)),
             "kv_norm": ones(r)}
    if dense:
        ffn = _ffn(t, d, cfg["intermediate_size"])
    else:
        f = cfg["moe_intermediate_size"]
        first = cfg["first_held_expert"]
        experts = [_ffn(lambda name, shape: t(name, shape, "expert", e), d, f)
                   for e in range(first, first + cfg["n_routed_experts"])]
        ffn = {"router": t("router", (d, cfg["published"]["n_routed_experts"])),
               **jax.tree.map(lambda *xs: jnp.stack(xs), *experts),
               "shared": _ffn(lambda name, shape: t(name, shape, "shared"),
                              d, f * cfg["n_shared_experts"])}
    return {"norm1": ones(d), "mixer": mixer, "norm2": ones(d), "ffn": ffn}


def make_params(cfg: dict, seed: int):
    """The program's parameter tree (``repro.models.model.init_params``'s
    structure and shapes, checked) in one jitted call; the MoE layers are
    made in a loop over their index, one layer's tensors at a time."""
    mcfg = program_config(cfg)  # first: a program without it fails here, at once
    from repro.models import model as M

    dtype = jnp.dtype(cfg["param_dtype"])
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    n_dense, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    std = cfg["initializer_range"]

    def build(key):
        stack = lambda layers: jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
        prefix = stack([layer_tensors(cfg, W.key_for(key, "layer", i), dtype, True)
                        for i in range(n_dense)])
        layers_key = W.key_for(key, "layer")
        moe = jax.lax.map(
            lambda i: layer_tensors(cfg, jax.random.fold_in(layers_key, i), dtype, False),
            jnp.arange(n_dense, L, dtype=jnp.uint32))
        return {"embed": {"table": W.tensor(key, ("embed",), (V, d), std, dtype)},
                "final_norm": {"scale": jnp.ones((d,), dtype)},
                "unembed": {"w": W.tensor(key, ("unembed",), (d, V), std, dtype)},
                "prefix": (prefix,), "stack": (moe,)}

    want = jax.eval_shape(lambda: M.init_params(jax.random.key(0), mcfg))
    key = W.seed_key(seed)
    got = jax.eval_shape(build, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"parameter tree differs from the program's: {got} vs {want}")
    return jax.jit(build)(key)
