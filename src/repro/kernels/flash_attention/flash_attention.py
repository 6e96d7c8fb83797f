"""Flash attention Pallas kernel (TPU): online-softmax tiled attention.

Used by the prefill/train paths (the dominant compute of the 32k-prefill
shapes). Supports causal masking, sliding-window attention (mixtral) and
GQA via q-head grouping done by the wrapper (ops.py) — the kernel itself
sees one KV head per q-block.

Layout: q (B*H, Sq, D), k/v (B*H, Sk, D). Grid (B*H, Sq/bq); the kernel
loop walks kv tiles of size bk with running max/denominator (the
standard flash recurrence), skipping fully-masked tiles (causal upper
triangle / outside the sliding window) via the grid mask, all in VMEM:
q tile (bq, D) + k/v tiles (bk, D) + acc (bq, D) — a few hundred KB.

The kernel is forward-only. ``flash_attention`` carries a ``custom_vjp``
whose backward recomputes the same attention with the pure-XLA
``flash_attention_xla`` and takes its VJP, so training steps can use the
kernel in the forward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.xla_flash import flash_attention_xla

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, n_k: int, causal: bool, window: int | None, scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # (bq, D)
    k = k_ref[0]  # (bk, D)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _flash_call(q, k, v, causal, window, scale, bq, bk, interpret):
    BH, Sq, D = q.shape
    _, Sk, _ = k.shape
    n_k = Sk // bk
    grid = (BH, Sq // bq, n_k)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, bq=bq, bk=bk, n_k=n_k, causal=causal,
            window=window, scale=scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, bq, bk, interpret):
    return _flash_call(q, k, v, causal, window, scale, bq, bk, interpret)


def _flash_fwd(q, k, v, causal, window, scale, bq, bk, interpret):
    return _flash_call(q, k, v, causal, window, scale, bq, bk, interpret), (q, k, v)


def _flash_bwd(causal, window, scale, bq, bk, interpret, res, g):
    """Backward: recompute the attention with ``flash_attention_xla`` (same
    mask and scale; (BH, S, D) viewed as one batch of BH heads) and apply
    its VJP to the incoming cotangent."""

    def xla(q, k, v):
        return flash_attention_xla(
            q[None], k[None], v[None], causal=causal, window=window, scale=scale,
        )[0]

    with jax.named_scope("attn.flash_bwd"):
        _, vjp = jax.vjp(xla, *res)
        return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret", "scale"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: (BH, Sq, D), k/v: (BH, Sk, D) -> (BH, Sq, D). Differentiable."""
    _, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, ((Sq, Sk), (bq, bk))
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    return _flash(q, k, v, causal, window, scale, bq, bk, interpret)
