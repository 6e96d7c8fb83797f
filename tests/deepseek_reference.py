"""Plain float32 forward of DeepSeek-V2's published block (DeepSeek-V2-Lite's
``modeling_deepseek.py``), for the tests: one causal sequence, no cache, no
kernel, no capacity, every matrix product at ``"highest"`` precision.

It takes its weights as the published model names them (``weights_of`` in
the tests maps the program's parameters onto them) and its sizes from a
``ModelConfig``; it uses nothing else of the program. Multi-head latent
attention with a direct query projection and the per-head keys and values
expanded from the latent; YaRN rotary embedding of de-interleaved pairs;
the softmax scale with YaRN's mscale squared; dense SwiGLU layers, then a
softmax router over all experts with greedy top-k (renormalised only where
the configuration says so), the held experts' SwiGLUs and the shared
experts as one SwiGLU. ``bench/reference/deepseek.py`` is its copy for the
benchmark, which makes its weights from a seed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """The published ``DeepseekV2YarnRotaryEmbedding`` frequencies."""
    def corr(turns):
        return (dim * math.log(yarn.original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    mask = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    return (extra / yarn.factor * (1 - mask) + extra * mask).astype(np.float32)


def rope(x, cfg):
    """x (S, heads, dim): de-interleave each head's pairs, then rotate the
    halves by position times the YaRN frequencies."""
    S, h, dim = x.shape
    y = cfg.yarn
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * yarn_inv_freq(dim, cfg.rope_theta, y)
    emb = jnp.concatenate([ang, ang], -1)[:, None]
    m = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
    x = x.reshape(S, h, dim // 2, 2).transpose(0, 1, 3, 2).reshape(S, h, dim)
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., : dim // 2]], -1)
    return x * jnp.cos(emb) * m + rot * jnp.sin(emb) * m


def attention(h, w, cfg):
    S, H, m = h.shape[0], cfg.n_heads, cfg.mla
    nope, dr, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.kv_lora_rank
    q = (h @ w["wq"]).reshape(S, H, nope + dr)
    kv_a = h @ w["wkv_a"]
    c = rmsnorm(kv_a[:, :r], w["kv_norm"], cfg.norm_eps)
    kv = (c @ w["wkv_b"]).reshape(S, H, nope + m.v_head_dim)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], -1)
    k_pe = jnp.broadcast_to(rope(kv_a[:, None, r:], cfg), (S, H, dr))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    scale = (nope + dr) ** -0.5 * yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, kv[..., nope:])
    return o.reshape(S, -1) @ w["wo"]


def swiglu(h, w):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_in"])) @ w["w_out"]


def moe(h, w, cfg):
    """The held experts' part of the MoE layer plus the shared experts."""
    mo = cfg.moe
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_probs:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, mo.num_experts) * top[..., None], 1)
    y = swiglu(h, w["shared"])
    for e, we in w["experts"].items():
        y = y + gates[:, e:e + 1] * swiglu(h, we)
    return y


def forward(w, tokens, cfg) -> jax.Array:
    """(S, vocab) logits of one causal sequence ``tokens`` (S,)."""
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for lw in w["layers"]:
            x = x + attention(rmsnorm(x, lw["norm1"], cfg.norm_eps), lw, cfg)
            h = rmsnorm(x, lw["norm2"], cfg.norm_eps)
            x = x + (moe(h, lw, cfg) if "router" in lw else swiglu(h, lw["mlp"]))
        return rmsnorm(x, w["final_norm"], cfg.norm_eps) @ w["unembed"]
