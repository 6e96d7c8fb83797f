"""Single-token multi-head latent attention (MLA) over a stacked latent
cache (Pallas, TPU): the latent analogue of ``decode.py``.

The decode step caches, per layer, slot and position, one latent row
``[c_kv | k_rope | 0]`` that every head shares (``r + dr`` wide, padded
with zeros to a multiple of 128 lanes), in one stacked buffer ``(L, B, S,
w)`` that the serving engine donates and the step updates in place. With
the key up-projection absorbed into the query and the value up-projection
applied after the attention, each head scores its absorbed query
``[q_nope . W_UK | q_rope | 0]`` against the latent rows themselves, and
its output is the softmax-weighted sum of their first ``r`` columns
(``c_kv``), which the caller multiplies by ``W_UV``. No position is ever
expanded to per-head keys or values.

As in ``decode.py``, the layer index and the slots' positions are
prefetched scalars that the block index maps use, so the kernel reads the
layer's blocks where they lie in the stacked buffer, and blocks past a
slot's position are not fetched (the index map repeats the last needed
block, which the pipeline does not copy again). Per slot and block of
``bs`` positions, all heads are scored against the block in one matmul,
then the online softmax runs over the blocks. MXU operands are bf16 with
f32 accumulation; softmax and accumulators are f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mla_kernel(layer_ref, pos_ref, q_ref, c_ref, o_ref, m_scr, l_scr, acc_scr,
                *, bs: int, r: int, n_s: int, scale: float):
    del layer_ref  # used by the index maps only
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= pos // bs)
    def _block():
        q = q_ref[0].astype(jnp.bfloat16)  # (H, w)
        c = c_ref[0, 0].astype(jnp.bfloat16)  # (bs, w)
        s = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (H, bs)
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(jnp.bfloat16), c[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == n_s - 1)
    def _out():
        o_ref[0] = acc_scr[...] / l_scr[...]


@functools.partial(jax.jit, static_argnames=("r", "scale", "block", "interpret"))
def mla_decode_attention(q, latent, layer, pos, *, r: int, scale: float,
                         block: int = 512, interpret: bool | None = None):
    """Attention of one absorbed query per slot over layer ``layer`` of a
    stacked latent cache that already holds the token's own row.

    q (B, H, w): each head's ``[q_nope . W_UK | q_rope | 0]``; latent
    (L, B, S, w); layer an int scalar; pos (B,) int32: a slot attends
    to its positions up to ``pos``. Scores are ``scale * q . row``; the
    values are the rows' first ``r`` columns. Returns (B, H, r) float32.
    """
    L, B, S, w = latent.shape
    H = q.shape[1]
    bs = min(block, S)
    while S % bs:  # blocks tile the positions
        bs //= 2
    n_s = S // bs
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def latent_map(b, j, layer_ref, pos_ref):
        return layer_ref[0], b, jnp.minimum(j, pos_ref[b] // bs), 0

    def slot_map(b, j, layer_ref, pos_ref):
        return b, 0, 0

    kernel = functools.partial(_mla_kernel, bs=bs, r=r, n_s=n_s, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_s),
            in_specs=[
                pl.BlockSpec((1, H, w), slot_map),
                pl.BlockSpec((1, 1, bs, w), latent_map),
            ],
            out_specs=pl.BlockSpec((1, H, r), slot_map),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, r), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), pos.astype(jnp.int32),
      q.astype(jnp.float32), latent)
