"""DeepSeek-V2-Lite's block in the program against the plain float32
reference of the published block (``deepseek_reference.py``), at its
structure with small widths: the direct query projection, YaRN rotary and
softmax scale, the dense first layer, then 8 routed experts of which this
chip holds 2 (experts 2 and 3), and 2 shared experts.

The program runs in float32 here (the smoke configuration), so it differs
from the reference only by where it rounds: it absorbs the key and value
up-projections into the query and the output instead of expanding the
latent (another order of the same sums), and its decode reads the cache
position by position. Those move the logits by a few float32 ulps of the
largest per layer; 1e-4 of the largest logit leaves room for that over
three layers and is ten times below what rounding any one matrix product
to bfloat16 moves them by (checked in the test)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_reference as R
from repro.configs import get_config, get_smoke_config
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as MOE
from repro.serve.engine import Engine, Request

SMOKE = get_smoke_config("deepseek-v2-lite")
CFG = dataclasses.replace(SMOKE, moe=dataclasses.replace(SMOKE.moe, held_experts=(2, 2)))
TOL = 1e-4  # of the largest reference logit (module docstring)


def weights_of(params, cfg):
    """The program's parameters under the published model's names: one
    dict per layer, the held experts by their global index."""
    take = lambda tree, i: jax.tree.map(lambda a: np.asarray(a[i], np.float32), tree)
    layers = []
    for i in range(cfg.first_dense_layers):
        p = take(params["prefix"][0], i)
        layers.append({**p["mixer"], "kv_norm": p["mixer"]["kv_norm"]["scale"],
                       "norm1": p["norm1"]["scale"], "norm2": p["norm2"]["scale"],
                       "mlp": p["ffn"]})
    first, n = cfg.moe.held_experts or (0, cfg.moe.num_experts)
    for g in range(cfg.n_groups):
        p = take(params["stack"][0], g)
        f = p["ffn"]
        layers.append({**p["mixer"], "kv_norm": p["mixer"]["kv_norm"]["scale"],
                       "norm1": p["norm1"]["scale"], "norm2": p["norm2"]["scale"],
                       "router": f["router"], "shared": f["shared"],
                       "experts": {first + j: {k: f[k][j] for k in ("w_in", "w_gate", "w_out")}
                                   for j in range(n)}})
    return {"embed": np.asarray(params["embed"]["table"], np.float32), "layers": layers,
            "final_norm": np.asarray(params["final_norm"]["scale"], np.float32),
            "unembed": np.asarray(params["unembed"]["w"], np.float32)}


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(1).integers(0, CFG.vocab, 24).astype(np.int32)


def test_yarn_numbers():
    """The published config's YaRN: pairs 0-10 keep theta**(-2i/64), pairs
    23-31 are divided by 40, a linear ramp between; mscale 1.2608, so the
    softmax scale is 192**-0.5 times 1.5896; cos and sin unscaled."""
    cfg = get_config("deepseek-v2-lite")
    assert L.yarn_range(64, cfg.rope_theta, cfg.yarn) == (10, 23)
    assert L.yarn_mscale(40.0, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert A.mla_scale(cfg) == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    f = np.asarray(L.rope_freqs(64, cfg.rope_theta, cfg.yarn))
    base = np.asarray(L.rope_freqs(64, cfg.rope_theta))
    np.testing.assert_array_equal(f[:11], base[:11])
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((f[11:23] < base[11:23]) & (f[11:23] > base[11:23] / 40))
    np.testing.assert_allclose(f, R.yarn_inv_freq(64, cfg.rope_theta, cfg.yarn), rtol=1e-6)


def test_forward_matches_reference(params, tokens):
    """The full-sequence forward (the training path) gives the reference's
    logits; so does the reference with one matrix product rounded to
    bfloat16 not (the tolerance separates)."""
    batch = {"tokens": jnp.asarray(tokens)[None], "labels": jnp.asarray(tokens)[None]}
    got = np.asarray(M.forward_train(params, batch, CFG, use_kernel=False, remat=False)[0][0])
    w = weights_of(params, CFG)
    want = np.asarray(R.forward(w, tokens, CFG))
    atol = TOL * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)
    w["unembed"] = np.asarray(jnp.asarray(w["unembed"]).astype(jnp.bfloat16), np.float32)
    assert np.abs(np.asarray(R.forward(w, tokens, CFG)) - want).max() > 10 * atol


def test_engine_prefill_and_decode_match_reference(params, tokens):
    """Requests served through the engine (prompt fed through the cached
    decode step, then greedy decode, another request decoding beside it):
    the logits of every step of the first request equal the reference's
    full forward over its prompt and served tokens."""
    eng = Engine(CFG, params, batch_slots=2, max_seq=64)
    seen, real = {}, eng._step
    plain = jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, CFG))

    def recording(p, c, b, pos):
        # the logits of the step the engine takes, on the cache it carries
        # (before the engine's step donates it)
        logits, _ = plain(p, c, b, pos)
        seen.setdefault(int(pos[0]), np.asarray(logits[0]))  # slot 0's first visit
        return real(p, c, b, pos)

    eng._step = recording
    first = Request(rid=0, prompt=tokens[:7], max_new_tokens=10)
    eng.admit(first)
    eng.admit(Request(rid=1, prompt=tokens[7:10], max_new_tokens=12))
    eng.run_to_completion()
    assert first.done and len(first.out) == 10
    seq = np.concatenate([first.prompt, first.out])
    want = np.asarray(R.forward(weights_of(params, CFG), seq[:-1], CFG))
    got = np.stack([seen[p] for p in range(len(seq) - 1)])
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL * np.abs(want).max())


@pytest.mark.parametrize("dispatch", ["sparse", "dense"])
def test_expert_shares_add_up_to_the_uncut_layer(dispatch):
    """Four chips of 2 of the 8 experts each: each share routes over all 8
    and computes its own experts' part; the parts, with the shared experts
    (computed alike on every chip) counted once, add up to the layer with
    every expert held, and that is the reference's layer."""
    apply = MOE.moe_apply_sparse if dispatch == "sparse" else MOE.moe_apply
    params = MOE.moe_init(jax.random.key(3), SMOKE, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 8, SMOKE.d_model)),
                    jnp.float32)
    whole = np.asarray(apply(params, x, SMOKE)[0])
    parts = []
    for s in range(4):
        cfg = dataclasses.replace(SMOKE, moe=dataclasses.replace(SMOKE.moe,
                                                                 held_experts=(2 * s, 2)))
        share = dict(params, **{k: params[k][2 * s:2 * s + 2]
                                for k in ("w_in", "w_gate", "w_out")})
        parts.append(np.asarray(apply(share, x, cfg)[0]))
    shared = np.asarray(L.mlp(params["shared"], x))
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, rtol=0, atol=1e-5)

    w = {"router": np.asarray(params["router"]), "shared": params["shared"],
         "experts": {e: {k: params[k][e] for k in ("w_in", "w_gate", "w_out")}
                     for e in range(SMOKE.moe.num_experts)}}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.moe(x.reshape(-1, SMOKE.d_model), w, SMOKE))
    np.testing.assert_allclose(whole.reshape(want.shape), want, rtol=0, atol=1e-5)


def test_free_slot_is_not_read_on_the_kernel_path(params, monkeypatch):
    """The decode step as a TPU runs it (the MLA and expert kernels, here
    interpreted), with the last slot free at a stale position two blocks
    deep: the live slots get the logits of the step with every slot live,
    and keep them, to the bit, when every cached row of the free slot, in
    the dense layer and in the stack, is NaN; the free slot's own logits
    stay finite, so neither layer's kernel read its rows."""
    import functools

    from repro.kernels.flash_attention import mla_decode
    from repro.kernels.moe import expert_ffn

    monkeypatch.setattr(A, "default_impl", lambda: "pallas")
    monkeypatch.setattr(A, "mla_decode_attention",
                        functools.partial(mla_decode.mla_decode_attention, interpret=True))
    monkeypatch.setattr(MOE, "default_impl", lambda: "pallas")
    monkeypatch.setattr(MOE, "expert_ffn", functools.partial(expert_ffn.expert_ffn,
                                                             interpret=True))
    B, S = 3, 256
    rng = np.random.default_rng(6)
    w = CFG.mla.kv_lora_rank + CFG.mla.qk_rope_head_dim

    def rows(a):  # a cache of earlier tokens' rows, zero past each row's width
        return a if a.ndim < 4 else jnp.asarray(
            rng.standard_normal(a.shape), a.dtype).at[..., w:].set(0.0)

    cache = jax.tree.map(rows, M.init_cache(CFG, B, S, dtype=jnp.float32))
    step = jax.jit(lambda c, live: M.decode_step(
        params, c, {"token": jnp.asarray([3, 7, 11], jnp.int32), "live": live},
        jnp.asarray([5, 130, S - 1], jnp.int32), CFG)[0])
    live = jnp.asarray([True, True, False])
    every = np.asarray(step(cache, jnp.ones(B, bool)))
    got = np.asarray(step(cache, live))
    np.testing.assert_allclose(got[:2], every[:2], rtol=0, atol=1e-6 * np.abs(every).max())
    nan = jax.tree.map(lambda a: a if a.ndim < 4 else a.at[:, 2].set(jnp.nan), cache)
    poisoned = np.asarray(step(nan, live))
    np.testing.assert_array_equal(poisoned[:2], got[:2])
    assert np.isfinite(poisoned[2]).all()
