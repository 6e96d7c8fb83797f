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
from repro.kernels.flash_attention.decode import decode_attention
from repro.kernels.flash_attention.mla_decode import mla_decode_attention
from repro.kernels.flash_attention.ops import default_impl, gqa_attention
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


def write_rows(cache, layer, pos_b, rows):
    """Write the new token's rows into every layer's cache, in place:
    ``cache`` (L, B, max_seq, ...), ``rows`` (B, ...) at layer ``layer``
    and each slot's position ``pos_b`` (B,). The indexed axes lead, so
    XLA's scatter writes into the buffer as it lies."""
    return cache.at[layer, jnp.arange(rows.shape[0]), pos_b].set(rows.astype(cache.dtype))


def gqa_decode(params, x, cache, layer, cfg, position, mrope_positions=None):
    """x: (B, 1, d); cache: {'k','v'}: (L, B, max_seq, kv_heads, hd), every
    layer's; ``layer`` the one this call writes and reads; position scalar
    int OR (B,) array (per-slot positions — continuous batching). Writes
    the new token's K/V at ``position``, then attends over the layer's
    positions up to it. Returns (out, cache)."""
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
        ck = write_rows(cache["k"], layer, pos_b, k[:, 0])
        cv = write_rows(cache["v"], layer, pos_b, v[:, 0])
    with jax.named_scope("attn.decode"):
        if default_impl() == "pallas":
            # reads the layer's blocks where they lie (XLA copies a layer out)
            o = decode_attention(q[:, 0], ck, cv, layer, pos_b, window=cfg.sliding_window)
        else:
            o = _gqa_decode_xla(q, ck[layer], cv[layer], pos_b, cfg)
        o = o.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype)
    with jax.named_scope("attn.out"):
        o = o @ params["wo"]
    return o, {"k": ck, "v": cv}


def _gqa_decode_xla(q, ck, cv, pos_b, cfg):
    """Masked single-query attention over one layer's cache (B, S, kv, hd)
    in jnp (memory-bound): ``decode_attention``'s math, (B, kv, G, hd)."""
    B, hd = q.shape[0], cfg.head_dim
    G = cfg.n_heads // cfg.n_kv_heads
    qh = q.reshape(B, 1, cfg.n_kv_heads, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgk", qh.astype(jnp.float32), ck.astype(jnp.float32))
    s = s * (hd ** -0.5)
    kpos = jnp.arange(ck.shape[1])
    valid = kpos[None, :] <= pos_b[:, None]  # (B, S)
    if cfg.sliding_window is not None:
        valid &= kpos[None, :] > pos_b[:, None] - cfg.sliding_window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgk,bkhd->bhgd", p, cv.astype(jnp.float32))


def gqa_cache_init(cfg, batch, max_seq, dtype):
    """(B, max_seq, kv_heads, hd): a position's K (or V) rows lie together."""
    hd = cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
    }


# ================================================================= MLA
# DeepSeek Multi-head Latent Attention: queries through a low-rank path
# (DeepSeek-V3) or one projection (DeepSeek-V2-Lite); keys and values
# reconstructed from a compressed latent c_kv plus a rotary key k_rope that
# every head shares. Decode caches only the latent row [c_kv | k_rope].
def mla_init(key, cfg, dtype):
    d = cfg.d_model
    m = cfg.mla
    ks = jax.random.split(key, 7)
    s = d ** -0.5
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
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
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, dtype),
    }
    if m.q_lora_rank is None:
        p["wq"] = L.truncated_normal(ks[0], (d, cfg.n_heads * qh), dtype, s)
    else:
        p["wq_a"] = L.truncated_normal(ks[0], (d, m.q_lora_rank), dtype, s)
        p["wq_b"] = L.truncated_normal(
            ks[1], (m.q_lora_rank, cfg.n_heads * qh), dtype, m.q_lora_rank ** -0.5
        )
        p["q_norm"] = L.rmsnorm_init(m.q_lora_rank, dtype)
    return p


def mla_specs(cfg, rules):
    s = {
        "wkv_a": P(None, None),
        "wkv_b": rules.attn_in((0, 0)),
        "wo": rules.attn_out((0, 0)),
        "kv_norm": {"scale": P(None)},
    }
    if cfg.mla.q_lora_rank is None:
        s["wq"] = rules.attn_in((0, 0))
    else:
        s.update(wq_a=P(None, None), wq_b=rules.attn_in((0, 0)), q_norm={"scale": P(None)})
    return s


def mla_scale(cfg) -> float:
    """The softmax scale: 1/sqrt(qk head dim), times YaRN's mscale squared
    where the configuration sets ``mscale_all_dim`` (DeepSeek-V2)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_rope(x, positions, cfg):
    """DeepSeek's rotary embedding: interleaved pairs, YaRN where set."""
    return L.apply_rope(x, positions, cfg.rope_theta, cfg.yarn, interleaved=True)


def _mla_qkv(params, x, cfg, positions):
    B, S, _ = x.shape
    m = cfg.mla
    H = cfg.n_heads
    if m.q_lora_rank is None:
        q = x @ params["wq"]
    else:
        q_lat = L.rmsnorm(params["q_norm"], x @ params["wq_a"], cfg.norm_eps)
        q = q_lat @ params["wq_b"]
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = _mla_rope(q_rope, positions, cfg)
    kv_a = x @ params["wkv_a"]
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = L.rmsnorm(params["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = _mla_rope(k_rope[:, :, None, :], positions, cfg)  # 1 shared head
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
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    scale = qk_hd ** -0.5
    gain = mla_scale(cfg) / scale  # the attention paths below scale by 1/sqrt(qk_hd)
    if gain != 1.0:
        q = q * jnp.asarray(gain, q.dtype)
    # v head dim differs from qk head dim -> pad v for the kernel path
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


def mla_decode(params, x, cache, layer, cfg, position, live=None):
    """Absorbed MLA decode. x: (B, 1, d); cache: {'latent': (L, B, max_seq,
    w)}, every layer's rows ``[c_kv | k_rope | 0]`` (``mla_cache_init``),
    written and read as ``gqa_decode``'s; ``live`` (B,) bool, the rows
    that hold a request (None: all), the only ones the kernel reads. The
    key up-projection W_UK is absorbed into the query (``q_nope . W_UK``),
    so scores are taken against the latent rows themselves, and the value
    up-projection W_UV applies to the attention's latent output: no cached
    position is expanded to per-head keys or values. Returns (out,
    cache)."""
    m = cfg.mla
    B = x.shape[0]
    H, r, nope = cfg.n_heads, m.kv_lora_rank, m.qk_nope_head_dim
    pos_b = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (B,))
    with jax.named_scope("attn.qkv"):
        q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, x, cfg, pos_b[:, None])
    wkv_b = params["wkv_b"].reshape(r, H, nope + m.v_head_dim)
    pad = cache["latent"].shape[-1] - r - m.qk_rope_head_dim
    with jax.named_scope("attn.absorb"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wkv_b[..., :nope],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_rope[:, 0].astype(jnp.float32),
                             jnp.zeros((B, H, pad), jnp.float32)], axis=-1)
    with jax.named_scope("attn.kv_update"):
        row = jnp.concatenate([c_kv_new[:, 0], k_rope_new[:, 0],
                               jnp.zeros((B, pad), c_kv_new.dtype)], axis=-1)
        latent = write_rows(cache["latent"], layer, pos_b, row)
    with jax.named_scope("attn.decode"):
        if default_impl() == "pallas":
            # reads the layer's blocks where they lie (XLA copies a layer out)
            o = mla_decode_attention(q, latent, layer, pos_b, live, r=r, scale=mla_scale(cfg))
        else:
            o = _mla_decode_xla(q, latent[layer], pos_b, r, mla_scale(cfg))
    with jax.named_scope("attn.absorb"):
        o = jnp.einsum("bhr,rhv->bhv", o.astype(x.dtype), wkv_b[..., nope:])
    with jax.named_scope("attn.out"):
        o = o.reshape(B, 1, H * m.v_head_dim) @ params["wo"]
    return o, {"latent": latent}


def _mla_decode_xla(q, latent, pos_b, r, scale):
    """``mla_decode_attention``'s math in jnp: absorbed queries q (B, H, w)
    over one layer's latent rows (B, S, w); (B, H, r). Scores
    and outputs are products summed: a dot would copy the layer's rows out
    of the stacked cache to read them."""
    lat = latent[:, None].astype(jnp.float32)  # (B, 1, S, r + dr)
    s = (q[:, :, None, :] * lat).sum(-1) * scale  # (B, H, S)
    valid = jnp.arange(latent.shape[1])[None, :] <= pos_b[:, None]  # (B, S)
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return (p[..., None] * lat[..., :r]).sum(2)


def mla_cache_init(cfg, batch, max_seq, dtype):
    """(B, max_seq, w): a position's latent row ``[c_kv | k_rope]``, padded
    with zeros to ``w``, the next multiple of 128. A TPU stores the
    unpadded rows' two minor dims transposed, which a kernel reading rows
    would have to copy every step; padded, they lie row by row in the
    same bytes as the unpadded rows would in that order."""
    m = cfg.mla
    w = -(-(m.kv_lora_rank + m.qk_rope_head_dim) // 128) * 128
    return {"latent": jnp.zeros((batch, max_seq, w), dtype)}
