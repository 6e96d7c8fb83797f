"""MoE: sparse capacity-bounded dispatch vs dense-dispatch oracle, router
properties, load-balance loss."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:  # hypothesis is optional — deterministic fallback sampler otherwise
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import moe as MOE


def _setup(seed=0, B=2, S=16):
    cfg = get_smoke_config("mixtral-8x7b")
    params = MOE.moe_init(jax.random.key(seed), cfg, jnp.float32)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)) * 0.1, jnp.float32)
    return cfg, params, x


def test_sparse_matches_dense_with_ample_capacity():
    """With capacity >= T·k no tokens drop: sparse == dense exactly."""
    cfg, params, x = _setup()
    y_dense, aux_d = MOE.moe_apply(params, x, cfg)
    y_sparse, aux_s = MOE.moe_apply_sparse(params, x, cfg, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(y_sparse), np.asarray(y_dense), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux_s), float(aux_d), rtol=1e-6)


def test_sparse_capacity_drops_bounded():
    """With tight capacity outputs differ only by dropped tokens (bounded
    deviation, never NaN)."""
    cfg, params, x = _setup(seed=1)
    y, _ = MOE.moe_apply_sparse(params, x, cfg, capacity_factor=0.5)
    assert np.isfinite(np.asarray(y)).all()


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_router_topk_properties(seed):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)
    w, idx = MOE.router_topk(logits, k=2, norm_probs=True)
    w = np.asarray(w)
    idx = np.asarray(idx)
    assert np.allclose(w.sum(-1), 1.0, atol=1e-5)       # renormalized
    assert (w >= 0).all()
    assert (idx[:, 0] != idx[:, 1]).all()               # distinct experts
    # top-1 really is the argmax
    probs = np.asarray(jax.nn.softmax(logits, -1))
    assert (idx[:, 0] == probs.argmax(-1)).all()


def test_load_balance_loss_uniform_is_one():
    """Perfectly uniform router -> aux loss == E · Σ (1/E)(1/E) · E = 1."""
    T, E, k = 1024, 8, 2
    logits = jnp.zeros((T, E))
    rng = np.random.default_rng(0)
    idx = jnp.asarray(
        np.stack([rng.permutation(E)[:k] for _ in range(T)]), jnp.int32
    )
    loss = MOE.load_balance_loss(logits, idx, E, k)
    # f_e ~ uniform 1/E, p_e = 1/E exactly -> E * E * (1/E * 1/E) = 1
    assert 0.9 < float(loss) < 1.1


def test_shared_expert_always_active():
    cfg = get_smoke_config("deepseek-v3-671b")
    params = MOE.moe_init(jax.random.key(0), cfg, jnp.float32)
    assert "shared" in params
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)) * 0.1, jnp.float32)
    y, _ = MOE.moe_apply_sparse(params, x, cfg)
    # zeroing the shared expert changes every token's output
    p2 = dict(params, shared=jax.tree.map(jnp.zeros_like, params["shared"]))
    y2, _ = MOE.moe_apply_sparse(p2, x, cfg)
    assert not np.allclose(np.asarray(y), np.asarray(y2))


# ------------------------------------------------------------- live mask
LIVE = np.array([True, False, True, True, False, False])


def test_live_mask_keeps_live_rows_and_zeroes_dead_ones():
    """Masked out, a batch row gets nothing from the experts; the live
    rows get what they get unmasked (capacity binds neither call)."""
    cfg, params, x = _setup(seed=3, B=6, S=1)
    y, _ = MOE.moe_apply_sparse(params, x, cfg)
    y_live, _ = MOE.moe_apply_sparse(params, x, cfg, live=jnp.asarray(LIVE))
    y, y_live = np.asarray(y), np.asarray(y_live)
    np.testing.assert_allclose(y_live[LIVE], y[LIVE], rtol=1e-6, atol=1e-7)
    assert (y_live[~LIVE] == 0).all() and (y[~LIVE] != 0).any()


def test_dead_rows_take_no_capacity():
    """40 tokens over 4 experts at capacity 16: unmasked, the 32 dead rows
    ahead of the 8 live ones fill the buffers and live assignments drop;
    masked, the live rows take every row they need and equal the
    drop-free dense dispatch."""
    cfg, params, x = _setup(seed=4, B=40, S=1)
    live = np.arange(40) >= 32
    oracle = np.asarray(MOE.moe_apply(params, x[live], cfg)[0])
    masked = np.asarray(MOE.moe_apply_sparse(params, x, cfg, capacity_factor=0.5,
                                             live=jnp.asarray(live))[0])[live]
    unmasked = np.asarray(MOE.moe_apply_sparse(params, x, cfg, capacity_factor=0.5)[0])[live]
    np.testing.assert_allclose(masked, oracle, rtol=2e-4, atol=2e-5)
    assert not np.allclose(unmasked, oracle, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("held", [None, (2, 2)])
def test_moe_decode_counts_the_held_experts_live_rows_reach(held):
    """``moe_decode`` runs the held experts that some live row's top-k
    reaches: its count equals that set's size, recounted on the host from
    the router's choices."""
    import dataclasses

    cfg = get_smoke_config("deepseek-v2-lite")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held_experts=held))
    params = MOE.moe_init(jax.random.key(5), cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((6, 1, cfg.d_model)),
                    jnp.float32)
    _, n_run = MOE.moe_decode(params, x, cfg, jnp.asarray(LIVE))
    _, idx = MOE.router_topk(x[:, 0] @ params["router"], cfg.moe.top_k,
                             cfg.moe.norm_topk_probs)
    first, n = held or (0, cfg.moe.num_experts)
    reached = {int(e) for e in np.asarray(idx)[LIVE].ravel() if first <= e < first + n}
    assert int(n_run) == len(reached) < n
