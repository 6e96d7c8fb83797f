"""Tiled MXU matmul Pallas kernel — the "off-and-on" local product of the
D3(K², M) distributed matmul (§2, Theorem 2's X×X block product).

TPU adaptation: the paper's per-router block product maps to an MXU-tiled
kernel. BlockSpecs stage (bm, bk) × (bk, bn) operand tiles HBM→VMEM; the
grid is (M/bm, N/bn, K/bk) with the contraction dimension innermost
(ARBITRARY semantics) accumulating into a VMEM scratch tile in fp32,
flushed to the output tile on the last k-step. Tile sides are multiples
of the MXU's 128-lane systolic shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    """One (i, j, k) grid step: acc += A[i,k] @ B[k,j]; flush at k == n_k-1."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype")
)
def block_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.

    Default tiles: (256, 512) A-tile + (512, 256) B-tile + (256, 256) fp32
    acc = 256·512·2·2 + 256·256·4 ≈ 0.8 MB in VMEM (bf16 operands) — well
    inside the ~16 MB/core budget with double buffering, and every matmul
    dim is a multiple of the 128-wide MXU.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, ((m, n, k), (bm, bn, bk))
    if out_dtype is None:
        out_dtype = a.dtype
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(a, b)
