"""Decoder language models (Mixtral, OLMo): the program's configuration and
its parameters, made on the device from the seed.

Every weight matrix is ``bench.weights.tensor`` at the configuration's
``initializer_range``, one layer (and one expert) at a time under the path
that ``bench/reference/decoder.py`` regenerates; norm scales are ones. The
whole tree is made in one jitted call, in the dtype it is served in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as W


def program_config(cfg: dict):
    from repro.configs.base import ModelConfig, MoEConfig

    E = cfg.get("num_local_experts")
    moe = None
    if E:
        moe = MoEConfig(num_experts=E, top_k=cfg["num_experts_per_tok"],
                        d_ff_expert=cfg["intermediate_size"],
                        capacity_factor=cfg["capacity_factor"])
    return ModelConfig(
        name=cfg["model_type"], family="moe" if E else "dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg["head_dim"],
        sliding_window=cfg.get("sliding_window"), rope="rope",
        rope_theta=cfg["rope_theta"], norm=cfg["norm"], moe=moe,
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def layer_tensors(cfg: dict, key, layer: int, dtype) -> dict:
    """One layer's weight matrices, keyed as the program's member tree."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    std = cfg["initializer_range"]

    def t(name, shape, *extra):
        return W.tensor(key, ("layer", layer, *extra, name), shape, std, dtype)

    mixer = {"wq": t("wq", (d, H * hd)), "wk": t("wk", (d, KV * hd)),
             "wv": t("wv", (d, KV * hd)), "wo": t("wo", (H * hd, d))}
    E = cfg.get("num_local_experts")
    if E:
        ffn = {"router": t("router", (d, E))}
        for name, shape in (("w_in", (d, f)), ("w_gate", (d, f)),
                            ("w_out", (f, d))):
            ffn[name] = jnp.stack([t(name, shape, "expert", e) for e in range(E)])
    else:
        ffn = {"w_in": t("w_in", (d, f)), "w_gate": t("w_gate", (d, f)),
               "w_out": t("w_out", (f, d))}
    return {"mixer": mixer, "ffn": ffn}


def _norm(cfg, dtype):
    if cfg["norm"] == "nonparametric":
        return {}
    return {"scale": jnp.ones((cfg["hidden_size"],), dtype)}


def make_params(cfg: dict, seed: int):
    """The program's parameter tree (``repro.models.model.init_params``'s
    structure and shapes, checked) in one jitted call."""
    from repro.models import model as M

    mcfg = program_config(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    std = cfg["initializer_range"]

    def build(key):
        layers = [layer_tensors(cfg, key, i, dtype) for i in range(L)]
        member = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
        norm1 = _norm(cfg, dtype)
        member["norm1"] = jax.tree.map(lambda a: jnp.stack([a] * L), norm1)
        member["norm2"] = jax.tree.map(lambda a: jnp.stack([a] * L), norm1)
        p = {"embed": {"table": W.tensor(key, ("embed",), (V, d), std, dtype)},
             "final_norm": _norm(cfg, dtype), "stack": (member,)}
        if not cfg["tie_word_embeddings"]:
            p["unembed"] = {"w": W.tensor(key, ("unembed",), (d, V), std, dtype)}
        return p

    want = jax.eval_shape(lambda: M.init_params(jax.random.key(0), mcfg))
    key = W.seed_key(seed)
    got = jax.eval_shape(build, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"parameter tree differs from the program's: {got} vs {want}")
    return jax.jit(build)(key)
