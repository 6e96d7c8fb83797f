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

The stacked buffer stays where it lies in HBM, and the kernel copies in
only the rows a slot attends to. The layer index, the slots' positions
and which slots hold a request are prefetched scalars. The grid runs over
the slots, one step each, in order. A live slot loops over the blocks of
``bs`` rows up to its position (``_n_blocks``), copying each into one of
two VMEM buffers while it scores the other; its last block starts the
copy of the next live slot's first block, so no slot waits for a cold
copy. A free slot copies nothing and returns zeros. Per block, all heads
are scored against it in one matmul, then the online softmax runs over
the blocks. MXU operands are bf16 with f32 accumulation; softmax and
accumulators are f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# rows a copy brings in: 128 f32 rows of 640 are 320 KiB, and two buffers
# of them leave VMEM to spare; a slot reads at most bs - 1 rows past its
# position
BLOCK = 128


def _block_rows(max_seq: int, block: int) -> int:
    """Rows of a block: ``block`` or fewer, halved until blocks tile the
    positions."""
    bs = min(block, max_seq)
    while max_seq % bs:
        bs //= 2
    return bs


def _n_blocks(pos, live, bs: int):
    """Blocks a slot reads: those up to its position if it holds a request,
    none if it is free."""
    return jnp.where(live, pos // bs + 1, 0)


def rows_read(pos, live, max_seq: int, block: int = BLOCK):
    """Latent rows ``mla_decode_attention`` copies in, per slot and layer,
    for positions ``pos`` (B,) and live mask ``live`` (B,) (None: every
    slot) over a cache of ``max_seq`` positions."""
    bs = _block_rows(max_seq, block)
    pos = jnp.asarray(pos, jnp.int32)
    live = jnp.ones(pos.shape, bool) if live is None else jnp.asarray(live, bool)
    return _n_blocks(pos, live, bs) * bs


def _mla_kernel(layer_ref, pos_ref, live_ref, q_ref, c_hbm, o_ref, buf, sem, first_ref,
                *, bs: int, r: int, n_slots: int, scale: float):
    b = pl.program_id(0)

    def n_blocks(s):
        return _n_blocks(pos_ref[s], live_ref[s] != 0, bs)

    def next_live(s):
        """The first slot from ``s`` on with a block to read; ``n_slots`` if
        there is none."""
        return jax.lax.while_loop(
            lambda t: (t < n_slots) & (n_blocks(jnp.minimum(t, n_slots - 1)) == 0),
            lambda t: t + 1, s)

    def copy(s, j, k):
        """Block ``j`` of slot ``s`` into buffer ``k``."""
        return pltpu.make_async_copy(
            c_hbm.at[layer_ref[0], s, pl.ds(j * bs, bs)], buf.at[k], sem.at[k])

    @pl.when(b == 0)
    def _start():
        first_ref[0] = 0
        s = next_live(0)

        @pl.when(s < n_slots)
        def _():
            copy(s, 0, 0).start()

    n = n_blocks(b)

    @pl.when(n == 0)
    def _free():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _live():
        pos = pos_ref[b]
        k0 = first_ref[0]
        q = q_ref[0].astype(jnp.bfloat16)  # (H, w)

        def block(j, carry):
            m_prev, l_prev, acc = carry
            k = (k0 + j) % 2

            @pl.when(j + 1 < n)
            def _():
                copy(b, j + 1, 1 - k).start()

            @pl.when(j + 1 == n)
            def _():
                s = next_live(b + 1)
                first_ref[0] = 1 - k

                @pl.when(s < n_slots)
                def _():
                    copy(s, 0, 1 - k).start()

            copy(b, j, k).wait()
            c = buf[k].astype(jnp.bfloat16)  # (bs, w)
            s = jax.lax.dot_general(
                q, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (H, bs)
            kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= pos, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(jnp.bfloat16), c[:, :r], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc

        H = q.shape[0]
        init = (jnp.full((H, 1), NEG_INF, jnp.float32), jnp.zeros((H, 1), jnp.float32),
                jnp.zeros((H, r), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, n, block, init)
        o_ref[0] = acc / l


@functools.partial(jax.jit, static_argnames=("r", "scale", "block", "interpret"))
def mla_decode_attention(q, latent, layer, pos, live=None, *, r: int, scale: float,
                         block: int = BLOCK, interpret: bool | None = None):
    """Attention of one absorbed query per slot over layer ``layer`` of a
    stacked latent cache that already holds the token's own row.

    q (B, H, w): each head's ``[q_nope . W_UK | q_rope | 0]``; latent
    (L, B, S, w); layer an int scalar; pos (B,) int32: a slot attends
    to its positions up to ``pos``; live (B,) bool, the slots that hold a
    request (None: every slot); a free slot reads nothing and returns
    zeros. Scores are ``scale * q . row``; the values are the rows' first
    ``r`` columns. Returns (B, H, r) float32.
    """
    L, B, S, w = latent.shape
    H = q.shape[1]
    bs = _block_rows(S, block)
    if live is None:
        live = jnp.ones((B,), bool)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def slot_map(b, layer_ref, pos_ref, live_ref):
        return b, 0, 0

    kernel = functools.partial(_mla_kernel, bs=bs, r=r, n_slots=B, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, w), slot_map),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, r), slot_map),
            scratch_shapes=[
                pltpu.VMEM((2, bs, w), latent.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # one slot's last block starts the next live slot's first copy
            dimension_semantics=(pltpu.ARBITRARY,),
        ),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), pos.astype(jnp.int32),
      live.astype(jnp.int32), q.astype(jnp.float32), latent)
