"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.block_matmul.block_matmul import block_matmul
from repro.kernels.block_matmul.ref import block_matmul_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention.ops import gqa_attention


# ---------------------------------------------------------------- matmul
@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 128, 512), (128, 384, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_matmul_shapes(m, n, k, dtype):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    got = block_matmul(a, b, bm=128, bn=128, bk=128, interpret=True)
    want = block_matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("tiles", [(64, 64, 64), (128, 64, 256)])
def test_block_matmul_tile_sweep(tiles):
    bm, bn, bk = tiles
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    got = block_matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- attention
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk", [(128, 128), (128, 256)])
def test_flash_vs_ref(causal, sq, sk):
    rng = np.random.default_rng(2)
    BH, D = 4, 64
    q = jnp.asarray(rng.standard_normal((BH, sq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, sk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, sk, D)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_sliding_window():
    rng = np.random.default_rng(3)
    BH, S, D, W = 2, 256, 64, 64
    q = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=W, bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dtype):
    rng = np.random.default_rng(4)
    BH, S, D = 2, 128, 64
    q = jnp.asarray(rng.standard_normal((BH, S, D)), dtype)
    k = jnp.asarray(rng.standard_normal((BH, S, D)), dtype)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), dtype)
    got = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (4, 1)])
def test_gqa_grouping(hq, hkv):
    rng = np.random.default_rng(5)
    B, S, D = 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, S, hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, hkv, D)), jnp.float32)
    got = gqa_attention(q, k, v, use_kernel=True, interpret=True)
    want = gqa_attention(q, k, v, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_matches_big_kv_tiling():
    """Property: result independent of kv tile size (online softmax)."""
    rng = np.random.default_rng(6)
    BH, S, D = 2, 256, 64
    q = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    a = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    b = flash_attention(q, k, v, bq=64, bk=256, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
def test_flash_grad_vs_ref(causal, window):
    """The kernel's custom VJP (XLA recompute backward) gives the
    gradients of the materialized oracle: f32, atol/rtol 1e-4."""
    rng = np.random.default_rng(7)
    BH, S, D = 2, 256, 64
    q, k, v, ct = (jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
                   for _ in range(4))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * ct)

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window, bq=64, bk=64, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: attention_ref(
        q, k, v, causal=causal, window=window)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window,block", [(None, 16), (None, 64), (7, 16)])
def test_decode_attention_vs_ref(window, block):
    """The decode kernel (interpret mode) attends from each slot's query
    over one layer of a stacked cache, up to the slot's position and
    within the window, as the materialized softmax does. Inputs lie on
    the bf16 grid, so only the kernel's bf16 probabilities round:
    |v| <= 1 bounds the difference by 2**-8."""
    from repro.kernels.flash_attention.decode import decode_attention

    rng = np.random.default_rng(3)
    L, B, S, kv, hd, H = 3, 4, 64, 2, 16, 8
    grid = lambda *shape: jnp.asarray(rng.uniform(-1, 1, shape), jnp.bfloat16).astype(jnp.float32)
    kc, vc, q = grid(L, B, S, kv, hd), grid(L, B, S, kv, hd), grid(B, H, hd)
    pos = jnp.asarray([0, 5, 33, 63], jnp.int32)
    got = decode_attention(q, kc, vc, 1, pos, window=window, block=block, interpret=True)

    qh = q.reshape(B, kv, H // kv, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qh, kc[1], precision="highest") * hd ** -0.5
    kpos = jnp.arange(S)[None]
    valid = kpos <= pos[:, None]
    if window is not None:
        valid &= kpos > pos[:, None] - window
    p = jax.nn.softmax(jnp.where(valid[:, None, None], s, -1e30), axis=-1)
    want = jnp.einsum("bhgk,bkhd->bhgd", p, vc[1], precision="highest").reshape(B, H, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2 ** -8, rtol=0)


# (block, positions, live) over 6 slots of 2048 positions: every slot live
# at the edges of blocks of 512 and of 128 (the default); edges of 128 and
# 256; free slots whose stale positions sit at 2047 and at block edges,
# first, last and between live ones; every slot free
_MLA_POS = [0, 511, 512, 1023, 1024, 2047]
MLA_DECODE_CASES = {
    "512": (512, _MLA_POS, None),
    "128": (128, _MLA_POS, None),
    "block_edges": (None, [127, 128, 255, 256, 1, 2046], None),
    "free_stale": (None, [2047, 127, 300, 128, 511, 5], [0, 0, 1, 0, 1, 1]),
    "free_between": (512, [40, 2047, 1023, 512, 700, 2047], [1, 0, 1, 0, 1, 0]),
    "all_free": (None, [2047, 127, 128, 511, 512, 0], [0, 0, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", list(MLA_DECODE_CASES))
def test_mla_decode_attention_vs_jnp(case):
    """The MLA decode kernel (interpret mode) against the jnp form a CPU
    takes (``attention._mla_decode_xla``), over one layer of a stacked
    latent cache of 2048 positions with DeepSeek-V2-Lite's row (512 + 64,
    padded to 640): slots at position 0, at both sides of block edges and
    at 2047. Each slot's row at its position is ``sign(q_0)`` (head 0's
    query), which head 0 then attends to almost alone, and the row after it
    ``2 sign(q_0)``, which it would attend to instead were the position
    past the slot's read: a block read one short or one long moves head 0
    by whole units. Inputs lie on the bf16 grid, so only the kernel's bf16
    probabilities round: values |v| <= 2 bound the difference by 2**-7.
    A free slot returns zeros, and the live slots' outputs stay the same
    bits when every row of the free slots, in every layer, is NaN."""
    from repro.kernels.flash_attention.mla_decode import mla_decode_attention
    from repro.models.attention import _mla_decode_xla

    block, pos, live = MLA_DECODE_CASES[case]
    rng = np.random.default_rng(5)
    L, B, S, r, dr, w, H = 2, 6, 2048, 512, 64, 640, 16
    grid = lambda *shape: jnp.asarray(rng.uniform(-1, 1, shape), jnp.bfloat16).astype(jnp.float32)
    q = grid(B, H, w).at[..., r + dr:].set(0.0)
    pos = np.asarray(pos, np.int32)
    latent = np.array(grid(L, B, S, w))
    loud = np.sign(np.asarray(q[:, 0]))
    for b, p in enumerate(pos):
        latent[1, b, p] = loud[b]
        if p + 1 < S:
            latent[1, b, p + 1] = 2 * loud[b]
    latent = jnp.asarray(latent).at[..., r + dr:].set(0.0)
    scale = (r + dr) ** -0.5
    pos = jnp.asarray(pos)
    kw = {} if block is None else {"block": block}
    attend = lambda lat, live: mla_decode_attention(q, lat, 1, pos, live, r=r, scale=scale,
                                                    interpret=True, **kw)
    got = attend(latent, None if live is None else jnp.asarray(live, bool))
    want = _mla_decode_xla(q, latent[1], pos, r, scale)
    np.testing.assert_allclose(np.asarray(want)[:, 0], loud[:, :r], atol=0.05)
    on = np.ones(B, bool) if live is None else np.asarray(live, bool)
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(want)[on], atol=2 ** -7, rtol=0)
    assert not np.asarray(got)[~on].any()
    if live is not None:
        garbage = latent.at[:, ~on].set(jnp.nan)
        np.testing.assert_array_equal(np.asarray(attend(garbage, jnp.asarray(on))),
                                      np.asarray(got))


@pytest.mark.parametrize("block, bs", [(None, 128), (512, 512)])
def test_mla_decode_rows_read(block, bs):
    """The rows the MLA kernel copies in: none for a free slot, whatever its
    stale position; ``(p // bs + 1) * bs`` for a live slot at ``p``; every
    slot live without a mask."""
    from repro.kernels.flash_attention.mla_decode import rows_read

    kw = {} if block is None else {"block": block}
    pos = np.asarray([0, 127, 128, 511, 512, 2047, 2047, 300], np.int32)
    live = np.asarray([1, 1, 1, 1, 1, 1, 0, 0], bool)
    want = (pos // bs + 1) * bs
    np.testing.assert_array_equal(np.asarray(rows_read(pos, live, 2048, **kw)),
                                  np.where(live, want, 0))
    np.testing.assert_array_equal(np.asarray(rows_read(pos, None, 2048, **kw)), want)


# ------------------------------------------------------------- expert FFN
# (L, E, C, d, f, tile, layer, experts run in order): every expert,
# some, one; scaled-down Mixtral (f = 3.5 d, several d_ff tiles) and
# DeepSeek-V2 (f = 0.69 d, one tile: the whole expert); layer 0 and not
EXPERT_FFN_CASES = {
    "all": (3, 4, 16, 128, 256, 128, 1, [0, 1, 2, 3]),
    "some_empty": (3, 4, 16, 128, 256, 128, 2, [3, 1]),
    "one": (2, 4, 16, 128, 256, 128, 0, [2]),
    "mixtral_like": (2, 8, 16, 256, 896, 128, 1, [6, 0, 3]),
    "deepseek_like": (3, 8, 16, 384, 256, None, 2, [1, 2, 4, 5, 7]),
}


@pytest.mark.parametrize("case", sorted(EXPERT_FFN_CASES))
def test_expert_ffn_vs_einsums(case):
    """The expert-FFN kernel (interpret mode) gives the three einsums'
    result for each expert it is told to run, reading layer ``layer`` of
    the stacked weights. Inputs lie on the bf16 grid and are bf16, as a
    served step's; both sides round the two projections to bf16, so they
    differ by a few bf16 ulps of the largest output. The weights of the
    experts not run, and of the other layers, are NaN: a block read from
    the wrong expert, tile or layer poisons what it touches."""
    from repro.kernels.moe.expert_ffn import expert_ffn

    L, E, C, d, f, tile, layer, run = EXPERT_FFN_CASES[case]
    rng = np.random.default_rng(11)
    grid = lambda *shape, s=1.0: jnp.asarray(rng.uniform(-s, s, shape), jnp.bfloat16)
    x = grid(E, C, d)
    w_gate, w_in = grid(L, E, d, f, s=d ** -0.5 * 2), grid(L, E, d, f, s=d ** -0.5 * 2)
    w_out = grid(L, E, f, d, s=f ** -0.5 * 2)
    unread = np.ones((L, E), bool)
    unread[layer, run] = False
    poison = lambda w: jnp.where(jnp.asarray(unread)[:, :, None, None], jnp.nan, w)
    order = jnp.asarray(run + [e for e in range(E) if e not in run], jnp.int32)
    got = expert_ffn(x, poison(w_gate), poison(w_in), poison(w_out), layer, order, len(run),
                     tile=tile, interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (E, C, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w_gate[layer])) * jnp.einsum(
        "ecd,edf->ecf", x, w_in[layer])
    want = np.asarray(jnp.einsum("ecf,efd->ecd", h, w_out[layer]), np.float32)[run]
    got = np.asarray(got, np.float32)[run]
    np.testing.assert_allclose(got, want, rtol=0, atol=4 * 2 ** -8 * np.abs(want).max())


def test_expert_ffn_tile_fits_the_budget():
    """Mixtral's experts stream in d_ff tiles of 512 (24 MiB of weight
    blocks, double-buffered), DeepSeek-V2's whole (1408, 33 MiB); a d_ff
    that is no multiple of 128 is one tile."""
    from repro.kernels.moe.expert_ffn import VMEM_BUDGET, ffn_tile

    assert ffn_tile(4096, 14336, 2) == 512
    assert ffn_tile(2048, 1408, 2) == 1408
    assert ffn_tile(64, 96, 4) == 96
    for d, f in [(4096, 14336), (2048, 1408)]:
        t = ffn_tile(d, f, 2)
        assert 6 * d * t * 2 <= VMEM_BUDGET and f % t == 0
