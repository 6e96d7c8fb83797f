"""Shared layers: norms, embeddings, RoPE/M-RoPE, gated MLPs.

Functional style: each layer is (init(key, cfg) -> params, apply(params, x))
plus specs(cfg, rules) -> PartitionSpec tree mirroring params.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def truncated_normal(key, shape, dtype, scale):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# ----------------------------------------------------------------- norms
def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype, elementwise=True):
    if not elementwise:  # olmo's non-parametric LN
        return {}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if "scale" in params:
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def make_norm(kind: str, d: int, dtype):
    if kind == "rmsnorm":
        return rmsnorm_init(d, dtype), rmsnorm
    if kind == "layernorm":
        return layernorm_init(d, dtype), layernorm
    if kind == "nonparametric":  # olmo
        return layernorm_init(d, dtype, elementwise=False), layernorm
    raise ValueError(kind)


def norm_specs(kind: str):
    if kind == "rmsnorm":
        return {"scale": P(None)}
    if kind == "layernorm":
        return {"scale": P(None), "bias": P(None)}
    return {}


# ------------------------------------------------------------------ RoPE
def yarn_range(head_dim: int, theta: float, yarn) -> tuple[int, int]:
    """The rotary pairs over which YaRN ramps from the original frequency
    (pairs up to ``low``) to the frequency divided by the factor (pairs
    from ``high``): the pairs that turn ``beta_fast`` and ``beta_slow``
    times over the original context, as DeepSeek-V2's
    ``yarn_find_correction_range`` computes them."""
    def dim(turns):
        return (head_dim * math.log(yarn.original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(dim(yarn.beta_fast)), 0),
            min(math.ceil(dim(yarn.beta_slow)), head_dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(head_dim: int, theta: float = 10000.0, yarn=None):
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is None:
        return freqs
    low, high = yarn_range(head_dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               yarn=None, interleaved: bool = False):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int.

    Pair ``i`` turns by ``position * rope_freqs(...)[i]``. The pairs are
    the two halves of the head, or with ``interleaved`` neighbouring
    columns (2i, 2i + 1), returned as DeepSeek's published code returns
    them: the rotated first members, then the second. ``yarn`` scales the
    frequencies and, by ``mscale / mscale_all_dim``, cos and sin."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, yarn)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    ang = ang[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None and yarn.mscale != yarn.mscale_all_dim:
        m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        cos, sin = cos * m, sin * m
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions3: jax.Array, sections: tuple[int, int, int],
    theta: float = 10000.0,
):
    """Qwen2-VL multimodal RoPE. positions3: (3, ..., seq) — temporal,
    height, width position ids; sections: per-axis frequency-pair counts
    summing to head_dim/2 (e.g. (16, 24, 24) for head_dim 128)."""
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # split frequency pairs among the three position streams
    sec_ids = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )  # (hd/2,)
    pos = jnp.take(positions3, sec_ids, axis=0)  # (hd/2, ..., seq)
    pos = jnp.moveaxis(pos, 0, -1)  # (..., seq, hd/2)
    ang = pos.astype(jnp.float32) * freqs
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- MLP
def mlp_init(key, d_model, d_ff, dtype, gated=True):
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d_model ** -0.5
    scale_out = d_ff ** -0.5
    p = {
        "w_in": truncated_normal(k1, (d_model, d_ff), dtype, scale_in),
        "w_out": truncated_normal(k2, (d_ff, d_model), dtype, scale_out),
    }
    if gated:
        p["w_gate"] = truncated_normal(k3, (d_model, d_ff), dtype, scale_in)
    return p


def mlp(params, x, act=jax.nn.silu):
    """``mlp_apply`` outside its named scope, for callers that name it."""
    h = x @ params["w_in"]
    if "w_gate" in params:
        h = act(x @ params["w_gate"]) * h
    else:
        h = act(h)
    return h @ params["w_out"]


@jax.named_scope("mlp.ffn")
def mlp_apply(params, x, act=jax.nn.silu):
    return mlp(params, x, act)


def mlp_specs(rules, gated=True):
    p = {"w_in": rules.mlp_in((0, 0)), "w_out": rules.mlp_out((0, 0))}
    if gated:
        p["w_gate"] = rules.mlp_in((0, 0))
    return p


# ------------------------------------------------------------- embedding
def embed_init(key, vocab, d_model, dtype):
    return {"table": truncated_normal(key, (vocab, d_model), dtype, 1.0)}


@jax.named_scope("embed.lookup")
def embed_apply(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed_apply(params, x):
    return x @ params["table"].T
