"""Attention variants: GQA (+ sliding window), MLA (DeepSeek latent
attention), M-RoPE (Qwen2-VL). Train path (full sequence, flash kernel)
and decode path (single token, KV/latent cache).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.kernels.flash_attention.ops import gqa_attention
from repro.kernels.flash_attention.ref import attention_ref


# =========================================================== GQA / SWA
def gqa_init(key, cfg, dtype):
    d = cfg.d_model
    hd = cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": L.truncated_normal(kq, (d, cfg.n_heads * hd), dtype, s),
        "wk": L.truncated_normal(kk, (d, cfg.n_kv_heads * hd), dtype, s),
        "wv": L.truncated_normal(kv, (d, cfg.n_kv_heads * hd), dtype, s),
        "wo": L.truncated_normal(ko, (cfg.n_heads * hd, d), dtype, (cfg.n_heads * hd) ** -0.5),
    }


def gqa_specs(cfg, rules):
    return {
        "wq": rules.attn_in((0, 0)),
        "wk": rules.attn_in((0, 0)),
        "wv": rules.attn_in((0, 0)),
        "wo": rules.attn_out((0, 0)),
    }


def _project_qkv(params, x, cfg, positions, mrope_positions=None):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ params["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope == "mrope":
        q = L.apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = L.apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_train(params, x, cfg, positions, mrope_positions=None, use_kernel=True):
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(params, x, cfg, positions, mrope_positions)
    o = gqa_attention(
        q, k, v, causal=True, window=cfg.sliding_window, use_kernel=use_kernel
    )
    B, S = x.shape[:2]
    with jax.named_scope("attn.out"):
        return o.reshape(B, S, -1) @ params["wo"]


def gqa_decode(params, x, cache, cfg, position, mrope_positions=None):
    """x: (B, 1, d); cache: {'k','v'}: (B, kv_heads, max_seq, hd); position
    scalar int OR (B,) array (per-slot positions — continuous batching)."""
    B = x.shape[0]
    hd = cfg.head_dim
    pos_b = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (B,))
    positions = pos_b[:, None]
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(
            params, x, cfg,
            positions=positions,
            mrope_positions=mrope_positions,
        )
    with jax.named_scope("attn.kv_update"):
        bidx = jnp.arange(B)
        ck = cache["k"].at[bidx, :, pos_b].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, :, pos_b].set(v[:, 0].astype(cache["v"].dtype))
    # masked single-query attention over the cache (memory-bound: jnp path)
    with jax.named_scope("attn.decode"):
        G = cfg.n_heads // cfg.n_kv_heads
        qh = q.reshape(B, 1, cfg.n_kv_heads, G, hd)
        s = jnp.einsum("bqhgd,bhkd->bhgk", qh.astype(jnp.float32), ck.astype(jnp.float32))
        s = s * (hd ** -0.5)
        kpos = jnp.arange(ck.shape[2])
        valid = kpos[None, :] <= pos_b[:, None]  # (B, S)
        if cfg.sliding_window is not None:
            valid &= kpos[None, :] > pos_b[:, None] - cfg.sliding_window
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgk,bhkd->bhgd", p, cv.astype(jnp.float32))
        o = o.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype)
    with jax.named_scope("attn.out"):
        o = o @ params["wo"]
    return o, {"k": ck, "v": cv}


def gqa_cache_init(cfg, batch, max_seq, dtype):
    hd = cfg.head_dim
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, max_seq, hd), dtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, max_seq, hd), dtype),
    }


# ================================================================= MLA
# DeepSeek-V3 Multi-head Latent Attention: queries via a low-rank path,
# keys/values reconstructed from a compressed latent c_kv (cached) plus a
# shared rotary key k_rope. Decode caches ONLY (c_kv, k_rope).
def mla_init(key, cfg, dtype):
    d = cfg.d_model
    m = cfg.mla
    ks = jax.random.split(key, 7)
    s = d ** -0.5
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": L.truncated_normal(ks[0], (d, m.q_lora_rank), dtype, s),
        "wq_b": L.truncated_normal(
            ks[1], (m.q_lora_rank, cfg.n_heads * qh), dtype, m.q_lora_rank ** -0.5
        ),
        "wkv_a": L.truncated_normal(
            ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype, s
        ),
        "wkv_b": L.truncated_normal(
            ks[3],
            (m.kv_lora_rank, cfg.n_heads * (m.qk_nope_head_dim + m.v_head_dim)),
            dtype,
            m.kv_lora_rank ** -0.5,
        ),
        "wo": L.truncated_normal(
            ks[4], (cfg.n_heads * m.v_head_dim, d), dtype, (cfg.n_heads * m.v_head_dim) ** -0.5
        ),
        "q_norm": L.rmsnorm_init(m.q_lora_rank, dtype),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, dtype),
    }


def mla_specs(cfg, rules):
    return {
        "wq_a": P(None, None),
        "wq_b": rules.attn_in((0, 0)),
        "wkv_a": P(None, None),
        "wkv_b": rules.attn_in((0, 0)),
        "wo": rules.attn_out((0, 0)),
        "q_norm": {"scale": P(None)},
        "kv_norm": {"scale": P(None)},
    }


def _mla_qkv(params, x, cfg, positions):
    B, S, _ = x.shape
    m = cfg.mla
    H = cfg.n_heads
    q_lat = L.rmsnorm(params["q_norm"], x @ params["wq_a"])
    q = (q_lat @ params["wq_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    kv_a = x @ params["wkv_a"]
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = L.rmsnorm(params["kv_norm"], c_kv)
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # 1 shared head
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def _mla_expand_kv(params, c_kv, cfg):
    m = cfg.mla
    H = cfg.n_heads
    B, S, _ = c_kv.shape
    kv = (c_kv @ params["wkv_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    return k_nope, v


def mla_train(params, x, cfg, positions, use_kernel=True):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, cfg, positions)
    k_nope, v = _mla_expand_kv(params, c_kv, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim))],
        axis=-1,
    )
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    # v head dim differs from qk head dim -> pad v for the kernel path
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.v_head_dim == qk_hd and use_kernel:
        o = gqa_attention(q, k, v, causal=True, use_kernel=True)
    else:
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, qk_hd)
        kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, qk_hd)
        vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, m.v_head_dim)
        if use_kernel and m.v_head_dim < qk_hd:
            vf = jnp.pad(vf, ((0, 0), (0, 0), (0, qk_hd - m.v_head_dim)))
            o = gqa_attention(
                qf.reshape(B, H, S, qk_hd).transpose(0, 2, 1, 3),
                kf.reshape(B, H, S, qk_hd).transpose(0, 2, 1, 3),
                vf.reshape(B, H, S, qk_hd).transpose(0, 2, 1, 3),
                causal=True, use_kernel=True,
            )[..., : m.v_head_dim].reshape(B, S, H, m.v_head_dim)
        else:
            o = attention_ref(qf, kf, vf, causal=True, scale=scale)
            o = o.reshape(B, H, S, m.v_head_dim).transpose(0, 2, 1, 3)
    return o.reshape(B, S, H * m.v_head_dim) @ params["wo"]


def mla_decode(params, x, cache, cfg, position):
    """Latent cache: {'c_kv': (B, max_seq, r), 'k_rope': (B, max_seq, dr)}."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    pos_b = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (B,))
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, x, cfg, pos_b[:, None])
    bidx = jnp.arange(B)
    c = cache["c_kv"].at[bidx, pos_b].set(c_kv_new[:, 0].astype(cache["c_kv"].dtype))
    kr = cache["k_rope"].at[bidx, pos_b].set(
        k_rope_new[:, 0].astype(cache["k_rope"].dtype)
    )
    # absorbed-matmul decode: reconstruct k_nope/v from latent (memory-bound)
    k_nope, v = _mla_expand_kv(params, c, cfg)  # (B, S, H, ·)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
    s += jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32), kr.astype(jnp.float32))
    s *= scale
    valid = jnp.arange(c.shape[1])[None, :] <= pos_b[:, None]  # (B, S)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype)
    return o @ params["wo"], {"c_kv": c, "k_rope": kr}


def mla_cache_init(cfg, batch, max_seq, dtype):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, m.qk_rope_head_dim), dtype),
    }
