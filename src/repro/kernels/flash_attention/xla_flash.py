"""Chunked online-softmax attention in pure XLA (nested lax.scan).

Same recurrence as the Pallas kernel but expressed as loops XLA compiles
on any backend — the fallback used when the Mosaic kernel is unavailable
(CPU dry-run) and the memory-bounded path for giant sequence lengths:
peak score tile is (B, H, bq, bk) instead of (B, H, Sq, Sk).

Operates on the 4-D (B, H, S, D) layout so batch/head shardings propagate
through the loop (flattening B·H forces an SPMD resharding — see
EXPERIMENTS.md §Perf iteration 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


NEG_INF = -1e30


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "bq", "bk")
)
def flash_attention_xla(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, H, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 512,
    bk: int = 1024,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, ((Sq, Sk), (bq, bk))
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    nq, nk = Sq // bq, Sk // bk

    kc = jnp.moveaxis(k.reshape(B, H, nk, bk, D), 2, 0)  # (nk, B, H, bk, D)
    vc = jnp.moveaxis(v.reshape(B, H, nk, bk, D), 2, 0)

    def q_block(qi, q_tile):
        # q_tile: (B, H, bq, D)
        q_pos = qi * bq + jnp.arange(bq)

        def kv_step(carry, inp):
            m, l, acc = carry
            ki, k_tile, v_tile = inp
            s = jnp.einsum(
                "bhqd,bhkd->bhqk",
                q_tile.astype(jnp.float32),
                k_tile.astype(jnp.float32),
            ) * scale
            k_pos = ki * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_tile.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, H, bq), NEG_INF, jnp.float32),
            jnp.zeros((B, H, bq), jnp.float32),
            jnp.zeros((B, H, bq, D), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), init, (jnp.arange(nk), kc, vc))
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).astype(q.dtype)

    qc = jnp.moveaxis(q.reshape(B, H, nq, bq, D), 2, 0)  # (nq, B, H, bq, D)
    out = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), qc))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, Sq, D)
