"""Plain float32 reference of DeepSeek-V2 (DeepSeek-V2-Lite) for serving:
the forward pass of one causal sequence, no cache, no kernel, no capacity.

It imports nothing of the program. Its weights are the configuration's
bfloat16 grid values (``bench.weights``) regenerated from the seed, one
layer or one expert at a time, and widened to float32; every matrix
product runs at ``Precision.HIGHEST``. It follows the published modelling
code (``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2-Lite):

- pre-norm blocks with RMSNorm (``rms_norm_eps``; scales of 1, assumed);
- multi-head latent attention with a direct query projection: ``q = x
  W_q`` split into ``q_nope`` and ``q_pe`` per head; ``[c_kv | k_pe] = x
  W_kv_a``, ``c_kv`` normed; per-head ``k_nope`` and ``v`` expanded from
  ``c_kv`` by ``W_kv_b``; ``k_pe`` one head shared by all;
- YaRN rotary embedding of ``q_pe`` and ``k_pe``: frequencies ramped from
  ``theta**(-2i/d)`` to it over ``factor`` between the correction pairs
  of ``beta_fast`` and ``beta_slow``; cos and sin times ``mscale /
  mscale_all_dim``; the rotation de-interleaves the pairs, then rotates
  the halves;
- the softmax scale ``(qk_nope + qk_rope)**-0.5`` times YaRN's mscale of
  ``mscale_all_dim`` squared;
- ``first_k_dense_replace`` dense SwiGLU layers, then MoE layers: softmax
  router over all the published experts, greedy top-k, weights not
  renormalised, times ``routed_scaling_factor``; SwiGLU experts and the
  shared experts as one SwiGLU of ``n_shared_experts`` times the expert
  width; untied unembedding.

The expert share: only the experts the configuration holds
(``first_held_expert`` and the ``n_routed_experts`` after it) add their
part, as in the program; what the absent experts would add is left out.

``quant="fp8"`` is the lower-precision control: both operands of every
matrix product rounded to float8 e4m3 with a scale per tensor.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    vocab: int
    layers: int
    dense_layers: int
    heads: int
    r: int
    nope: int
    rope: int
    v: int
    dense_ff: int
    expert_ff: int
    router: int       # experts the router scores (the published count)
    held: tuple       # global indices of the experts held here
    top_k: int
    shared: int
    norm_topk: bool
    routed_scale: float
    theta: float
    yarn: tuple       # factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim
    eps: float
    std: float
    dtype: str

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        if cfg["q_lora_rank"] is not None or cfg["scoring_func"] != "softmax":
            raise NotImplementedError("the reference covers DeepSeek-V2-Lite's "
                                      "direct query projection and softmax router")
        rs = cfg["rope_scaling"]
        first = cfg["first_held_expert"]
        return cls(cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"],
                   cfg["first_k_dense_replace"], cfg["num_attention_heads"],
                   cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"], cfg["intermediate_size"],
                   cfg["moe_intermediate_size"], cfg["published"]["n_routed_experts"],
                   tuple(range(first, first + cfg["n_routed_experts"])),
                   cfg["num_experts_per_tok"], cfg["n_shared_experts"],
                   bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]),
                   float(cfg["rope_theta"]),
                   (float(rs["factor"]), rs["original_max_position_embeddings"],
                    float(rs["beta_fast"]), float(rs["beta_slow"]), float(rs["mscale"]),
                    float(rs["mscale_all_dim"])),
                   float(cfg["rms_norm_eps"]), float(cfg["initializer_range"]),
                   cfg["param_dtype"])


# ---------------------------------------------------------------- pieces
def _round(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


def _q(x, quant):
    if quant is None:
        return x.astype(F32)
    if quant != "fp8":
        raise ValueError(quant)
    return _round(x.astype(F32), jnp.float8_e4m3fn, 448.0)


def mm(a, b, quant=None):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=HIGHEST)


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: tuple) -> np.ndarray:
    """The published ``DeepseekV2YarnRotaryEmbedding`` frequencies."""
    factor, orig, beta_fast, beta_slow, _, _ = yarn

    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / factor
    mask = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope(x, dm: Dims):
    """x (S, heads, rope): de-interleave each head's pairs, then rotate the
    halves by position times the YaRN frequencies."""
    S, h, dim = x.shape
    inv = jnp.asarray(yarn_inv_freq(dim, dm.theta, dm.yarn))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], -1)[:, None]
    factor, _, _, _, ms, ms_all = dm.yarn
    m = yarn_mscale(factor, ms) / yarn_mscale(factor, ms_all)
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    x = x.reshape(S, h, dim // 2, 2).transpose(0, 1, 3, 2).reshape(S, h, dim)
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., : dim // 2]], -1)
    return x * cos + rot * sin


def softmax_scale(dm: Dims) -> float:
    factor, _, _, _, _, ms_all = dm.yarn
    scale = (dm.nope + dm.rope) ** -0.5
    if ms_all:
        scale *= yarn_mscale(factor, ms_all) ** 2
    return scale


def attention(h, w, dm: Dims, quant):
    S, H = h.shape[0], dm.heads
    q = mm(h, w["wq"], quant).reshape(S, H, dm.nope + dm.rope)
    q_nope, q_pe = q[..., : dm.nope], q[..., dm.nope:]
    kv_a = mm(h, w["wkv_a"], quant)
    c, k_pe = rmsnorm(kv_a[:, : dm.r], dm.eps), kv_a[:, dm.r:]
    kv = mm(c, w["wkv_b"], quant).reshape(S, H, dm.nope + dm.v)
    k_nope, val = kv[..., : dm.nope], kv[..., dm.nope:]
    q = jnp.concatenate([q_nope, rope(q_pe, dm)], -1)
    k_pe = jnp.broadcast_to(rope(k_pe[:, None], dm), (S, H, dm.rope))
    k = jnp.concatenate([k_nope, k_pe], -1)
    s = jnp.einsum("qhd,khd->hqk", _q(q, quant), _q(k, quant),
                   precision=HIGHEST) * softmax_scale(dm)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _q(p, quant), _q(val, quant), precision=HIGHEST)
    return mm(o.reshape(S, H * dm.v), w["wo"], quant)


def swiglu(h, w, quant):
    return mm(jax.nn.silu(mm(h, w["w_gate"], quant)) * mm(h, w["w_in"], quant),
              w["w_out"], quant)


def route(h, router, dm: Dims, quant):
    """(S, router) weights: the greedy top-k softmax scores (renormalised
    only where the configuration says so, times the routed scaling
    factor), zero for the experts not chosen."""
    probs = jax.nn.softmax(mm(h, router, quant), axis=-1)
    top, idx = jax.lax.top_k(probs, dm.top_k)
    if dm.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * dm.routed_scale
    return jnp.sum(jax.nn.one_hot(idx, dm.router, dtype=F32) * top[..., None], 1)


# ------------------------------------------------------------- weights
def _w(key, path, shape, dm: Dims):
    return W.tensor(key, path, shape, dm.std, jnp.dtype(dm.dtype)).astype(F32)


def attn_weights(layer_key, dm: Dims):
    d, H = dm.d, dm.heads
    return {"wq": _w(layer_key, ("wq",), (d, H * (dm.nope + dm.rope)), dm),
            "wkv_a": _w(layer_key, ("wkv_a",), (d, dm.r + dm.rope), dm),
            "wkv_b": _w(layer_key, ("wkv_b",), (dm.r, H * (dm.nope + dm.v)), dm),
            "wo": _w(layer_key, ("wo",), (H * dm.v, d), dm)}


def ffn_weights(layer_key, prefix: tuple, f: int, dm: Dims):
    return {name: _w(layer_key, (*prefix, name), shape, dm)
            for name, shape in (("w_in", (dm.d, f)), ("w_gate", (dm.d, f)),
                                ("w_out", (f, dm.d)))}


# ------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("dm",))
def _embed(key, tokens, dm: Dims):
    return _w(key, ("embed",), (dm.vocab, dm.d), dm)[tokens]


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _attn_block(layer_key, x, dm: Dims, quant):
    return x + attention(rmsnorm(x, dm.eps), attn_weights(layer_key, dm), dm, quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _dense_block(layer_key, x, dm: Dims, quant):
    return x + swiglu(rmsnorm(x, dm.eps), ffn_weights(layer_key, (), dm.dense_ff, dm), quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _router(layer_key, x, dm: Dims, quant):
    return route(rmsnorm(x, dm.eps), _w(layer_key, ("router",), (dm.d, dm.router), dm),
                 dm, quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _shared(layer_key, x, dm: Dims, quant):
    return swiglu(rmsnorm(x, dm.eps),
                  ffn_weights(layer_key, ("shared",), dm.expert_ff * dm.shared, dm), quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _expert_add(expert_key, x, y, gate, dm: Dims, quant):
    """y + gate[:, None] * expert(norm(x)); ``expert_key`` is the layer's
    key with ``("expert", e)`` folded in."""
    w = ffn_weights(expert_key, (), dm.expert_ff, dm)
    return y + gate[:, None] * swiglu(rmsnorm(x, dm.eps), w, quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _head(key, x, dm: Dims, quant):
    return mm(rmsnorm(x, dm.eps), _w(key, ("unembed",), (dm.d, dm.vocab), dm), quant)


def logits(cfg: dict, seed: int, tokens, quant=None) -> jax.Array:
    """(S, vocab) float32 logits of one causal sequence ``tokens`` (S,),
    computed layer by layer and expert by expert."""
    dm = Dims.of(cfg)
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), dm)
    for i in range(dm.layers):
        lk = W.key_for(key, "layer", i)
        x = _attn_block(lk, x, dm, quant)
        if i < dm.dense_layers:
            x = _dense_block(lk, x, dm, quant)
            continue
        gates = _router(lk, x, dm, quant)
        y = _shared(lk, x, dm, quant)
        for e in dm.held:
            y = _expert_add(W.key_for(lk, "expert", e), x, y, gates[:, e], dm, quant)
        x = x + y
    return _head(key, x, dm, quant)
