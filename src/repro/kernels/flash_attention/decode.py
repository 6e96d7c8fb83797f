"""Single-token GQA attention over a stacked K/V cache (Pallas, TPU).

The decode step keeps every layer's cache in one stacked buffer
``(L, B, S, kv, hd)``, which the serving engine donates and the step
updates in place. Read through XLA, a layer of it is a dynamic slice that
the TPU compiler materializes as a new buffer each layer and step (together
with the f32 -> bf16 conversion of the default matmul precision). This
kernel reads the layer's blocks straight from the stacked buffer: the layer
index and the slots' positions are prefetched scalars that the block index
maps use, and blocks that hold no position a slot attends to are not
fetched (the index map repeats the last needed block, which the pipeline
does not copy again).

Per slot and block of ``bs`` positions, the kv heads' rows form one
``(bs * kv, hd)`` tile (a bitcast of the cache's layout); every q head is
scored against the whole tile in one matmul and the pairs whose heads
differ are masked (kv times the FLOPs, on an MXU that decode leaves idle),
then the online softmax runs over the blocks. MXU operands are bf16 with
f32 accumulation, as XLA's default precision computes the f32 einsum;
softmax and accumulators are f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _blocks(pos, bs: int, window: int | None):
    """First and last block holding a position that ``pos`` attends to:
    positions up to it, within the window."""
    last = pos // bs
    if window is None:
        return 0, last
    return jnp.maximum(pos - window + 1, 0) // bs, last


def _decode_kernel(layer_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, bs: int, kv: int, group: int, n_s: int, window: int | None,
                   scale: float):
    del layer_ref  # used by the index maps only
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last = _blocks(pos, bs, window)

    @pl.when((j >= first) & (j <= last))
    def _block():
        q = q_ref[0].astype(jnp.bfloat16)  # (H, hd)
        k = k_ref[0, 0].astype(jnp.bfloat16)  # (bs * kv, hd): position-major
        v = v_ref[0, 0].astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (H, bs * kv)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row_head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        kpos = j * bs + col // kv
        valid = (col % kv == row_head) & (kpos <= pos)
        if window is not None:
            valid &= kpos > pos - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == n_s - 1)
    def _out():
        o_ref[0] = acc_scr[...] / l_scr[...]


@functools.partial(jax.jit, static_argnames=("window", "block", "interpret"))
def decode_attention(q, k_cache, v_cache, layer, pos, *, window: int | None = None,
                     block: int = 256, interpret: bool | None = None):
    """Attention of one token per slot over layer ``layer`` of a stacked
    cache that already holds the token's own K/V.

    q (B, H, hd); k_cache, v_cache (L, B, S, kv, hd); layer an int scalar;
    pos (B,) int32: a slot attends to its positions up to ``pos`` (and
    above ``pos - window``). Returns (B, H, hd) float32.
    """
    L, B, S, kv, hd = k_cache.shape
    H = q.shape[1]
    assert H % kv == 0, (H, kv)
    bs = min(block, S)
    while S % bs:  # blocks tile the positions
        bs //= 2
    n_s = S // bs
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # (S, kv, hd) -> (S * kv, hd): a bitcast where kv fills the sublanes
    kf = k_cache.reshape(L, B, S * kv, hd)
    vf = v_cache.reshape(L, B, S * kv, hd)

    def kv_map(b, j, layer_ref, pos_ref):
        first, last = _blocks(pos_ref[b], bs, window)
        return layer_ref[0], b, jnp.minimum(jnp.maximum(j, first), last), 0

    def slot_map(b, j, layer_ref, pos_ref):
        return b, 0, 0

    kernel = functools.partial(_decode_kernel, bs=bs, kv=kv, group=H // kv, n_s=n_s,
                               window=window, scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_s),
            in_specs=[
                pl.BlockSpec((1, H, hd), slot_map),
                pl.BlockSpec((1, 1, bs * kv, hd), kv_map),
                pl.BlockSpec((1, 1, bs * kv, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, H, hd), slot_map),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), pos.astype(jnp.int32),
      q.astype(jnp.float32), kf, vf)
