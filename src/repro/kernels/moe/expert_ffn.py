"""The experts' SwiGLU FFN over capacity buffers for the experts a step
reaches only (Pallas, TPU).

A decode step's MoE layer gathers its few tokens into per-expert buffers
``(E, C, d)`` and applies each expert's ``w_out . (silu(x . w_gate) *
(x . w_in))``. At decode sizes the work is bound by the weight bytes, and
most experts receive no token, so this kernel runs only the experts the
caller lists: ``experts[:n_run]`` (prefetched scalars) are the experts to
run, in order. The grid is (expert i, d_ff tile j). For ``i >= n_run`` the
block index maps repeat the last block run (last expert, last tile),
which the pipeline does not fetch again, and the body computes nothing,
so no weight byte of an expert not listed is read.

The weights are read where they lie in the decode loop's stacks ``(L, E,
d, f)``, with the layer index a prefetched scalar (as ``decode.py`` reads
the stacked cache): a layer sliced out of the stack for a custom call
would be copied. The d_ff tile is the largest multiple of 128 dividing
``f`` whose three weight blocks, double-buffered, fit ``VMEM_BUDGET``.

MXU operands keep their dtype and accumulate in f32; the two input
projections are rounded to the einsums' result dtype before the gate, and
the output accumulates in f32 over the tiles, as XLA computes the three
einsums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM for the double-buffered weight blocks (of a v5e core's 128 MiB):
# Mixtral's d 4096 x f 14336 takes tiles of 512 (24 MiB), DeepSeek-V2's
# d 2048 x f 1408 its whole expert in one step (33 MiB)
VMEM_BUDGET = 40 * 2 ** 20


def ffn_tile(d: int, f: int, itemsize: int, budget: int = VMEM_BUDGET) -> int:
    """The largest multiple of 128 dividing ``f`` whose ``w_gate``,
    ``w_in`` and ``w_out`` blocks, double-buffered, take at most
    ``budget`` bytes (128 where none fits); ``f`` itself where it is no
    multiple of 128."""
    if f % 128:
        return f
    fits = [t for t in range(128, f + 1, 128) if f % t == 0 and 6 * d * t * itemsize <= budget]
    return max(fits, default=128)


def _ffn_kernel(layer_ref, experts_ref, n_run_ref, x_ref, wg_ref, wi_ref, wo_ref, o_ref,
                acc_scr, *, n_f: int):
    del layer_ref, experts_ref  # used by the index maps only
    i, j = pl.program_id(0), pl.program_id(1)
    run = i < n_run_ref[0]
    dt = o_ref.dtype
    mm = functools.partial(jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)

    @pl.when(run & (j == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(run)
    def _tile():
        x = x_ref[0]  # (C, d)
        g = mm(x, wg_ref[0, 0]).astype(dt).astype(jnp.float32)  # (C, tf)
        h = mm(x, wi_ref[0, 0]).astype(dt).astype(jnp.float32)
        h = (g / (1.0 + jnp.exp(-g)) * h).astype(dt)
        acc_scr[...] += mm(h, wo_ref[0, 0])  # (C, d)

    # past n_run the accumulator still holds the last expert run, whose
    # block the output is: rewriting it changes nothing
    @pl.when(j == n_f - 1)
    def _out():
        o_ref[0] = acc_scr[...].astype(dt)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def expert_ffn(x, w_gate, w_in, w_out, layer, experts, n_run, *, tile: int | None = None,
               interpret: bool | None = None):
    """``w_out . (silu(x . w_gate) * (x . w_in))`` for the experts
    ``experts[:n_run]`` of layer ``layer``.

    x (E, C, d): each expert's buffer rows; w_gate, w_in (L, E, d, f) and
    w_out (L, E, f, d): every layer's expert weights; layer an int
    scalar; experts (E,) int32, the experts to run first, in order; n_run
    an int scalar. Returns (E, C, d) in the einsums' result dtype; the
    rows of experts not run are undefined.
    """
    E, C, d = x.shape
    f = w_gate.shape[-1]
    dt = jnp.result_type(x.dtype, w_gate.dtype)
    tf = tile or ffn_tile(d, f, w_gate.dtype.itemsize)
    assert f % tf == 0, (f, tf)
    n_f = f // tf
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def pick(i, j, experts_ref, n_run_ref):
        """The expert and tile step (i, j) reads: past ``n_run``, the last
        block run."""
        n = n_run_ref[0]
        e = experts_ref[jnp.clip(i, 0, jnp.maximum(n - 1, 0))]
        return e, jnp.where(i < n, j, n_f - 1)

    def w_in_map(i, j, layer_ref, experts_ref, n_run_ref):
        e, jj = pick(i, j, experts_ref, n_run_ref)
        return layer_ref[0], e, 0, jj

    def w_out_map(i, j, layer_ref, experts_ref, n_run_ref):
        e, jj = pick(i, j, experts_ref, n_run_ref)
        return layer_ref[0], e, jj, 0

    def rows_map(i, j, layer_ref, experts_ref, n_run_ref):
        return pick(i, j, experts_ref, n_run_ref)[0], 0, 0

    w_bytes = 6 * d * tf * w_gate.dtype.itemsize  # three blocks, double-buffered
    rows_bytes = 2 * C * d * (x.dtype.itemsize + jnp.dtype(dt).itemsize) + C * d * 4
    kernel = functools.partial(_ffn_kernel, n_f=n_f)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, n_f),
            in_specs=[
                pl.BlockSpec((1, C, d), rows_map),
                pl.BlockSpec((1, 1, d, tf), w_in_map),
                pl.BlockSpec((1, 1, d, tf), w_in_map),
                pl.BlockSpec((1, 1, tf, d), w_out_map),
            ],
            out_specs=pl.BlockSpec((1, C, d), rows_map),
            scratch_shapes=[pltpu.VMEM((C, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, d), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
            vmem_limit_bytes=w_bytes + rows_bytes + 8 * 2 ** 20,
        ),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), experts.astype(jnp.int32),
      jnp.reshape(jnp.asarray(n_run, jnp.int32), (1,)), x, w_gate, w_in, w_out)
