"""The decode step updates its cache in place: the serving engine donates
the cache to its jitted step, each layer writes the new token's rows into
the stacked cache where it lies (a recurrent mixer replaces its layer's
state), and the step returns that buffer. Checked on the compiled step's
text and against rows written into a copy on the host, for GQA (Mixtral),
MLA's latent rows (DeepSeek-V3 and DeepSeek-V2-Lite, with their dense
prefix), a GQA/Mamba hybrid (Jamba) and mLSTM/sLSTM (xLSTM)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hlo import aliased_parameter_dims, materialized
from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serve.engine import Engine, Request
from test_serve_engine import greedy_reference

ARCHS = ["mixtral-8x7b", "deepseek-v3-671b", "deepseek-v2-lite", "jamba-1.5-large-398b",
         "xlstm-1.3b"]
SEQUENCE_LEAVES = ("k", "v", "latent")  # (L, B, max_seq, ...)
SLOTS, MAX_SEQ = 3, 40


@pytest.fixture(scope="module", params=ARCHS)
def arch(request):
    cfg = get_smoke_config(request.param)
    return cfg, M.init_params(jax.random.key(0), cfg)


def _sequence_leaves(cache):
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
            if path[-1].key in SEQUENCE_LEAVES]


def test_engine_step_aliases_the_cache_and_copies_no_layer(arch):
    """Every cache leaf shares its buffer with the step's output, and no
    instruction outside a fusion makes an array of a layer's or of the
    stack's sequence-indexed cache shape but the in-place row update (a
    scatter); the attention's reads of the layer are fused."""
    cfg, params = arch
    eng = Engine(cfg, params, batch_slots=SLOTS, max_seq=MAX_SEQ)
    text = eng._step.lower(
        params, eng.cache, {"token": jnp.zeros(SLOTS, jnp.int32)},
        jnp.zeros(SLOTS, jnp.int32)).compile().as_text()

    leaves = jax.tree.leaves(eng.cache)
    assert aliased_parameter_dims(text) == sorted(leaf.shape for leaf in leaves)

    shapes = {s for leaf in _sequence_leaves(eng.cache) for s in (leaf.shape, leaf.shape[1:])}
    made = [(name, dims, kind) for name, dims, kind in materialized(text) if dims in shapes]
    allowed = {"parameter", "get-tuple-element", "bitcast", "scatter", "fusion:scatter"}
    assert [m for m in made if m[2] not in allowed] == []
    if shapes:
        assert any(kind.endswith("scatter") for _, _, kind in made)


def _write_rows(ref, new, old, pos):
    """Write into the host copy ``ref`` what one step changed: for a
    sequence leaf the rows at each slot's position (none where ``pos`` is
    None), checking that nothing else moved; for a state leaf the whole
    new state."""
    b = np.arange(0 if pos is None else pos.shape[0])
    pos = b if pos is None else pos
    for path, r in jax.tree_util.tree_leaves_with_path(ref):
        n = np.asarray(_at(new, path))
        if path[-1].key in SEQUENCE_LEAVES:
            rows = n[:, b, pos].copy()
            o = np.asarray(_at(old, path)).copy()
            o[:, b, pos] = rows
            np.testing.assert_array_equal(n, o)  # the step wrote the rows only
            r[:, b, pos] = rows
        else:
            r[...] = n


def _at(tree, path):
    for p in path:
        tree = tree[p.idx if hasattr(p, "idx") else p.key]
    return tree


def test_cache_matches_rows_written_into_a_copy(arch):
    """Serve six requests through two slots (admit, prefill, decode,
    retire, a slot seated again), recording each step's inputs (tokens,
    live slots, positions); replay the steps through an un-donated jit of
    the same step, writing each step's rows into a host copy. The
    engine's donated cache equals the copy leaf for leaf, and every
    request's greedy tokens equal those it gets served alone."""
    cfg, params = arch
    eng = Engine(cfg, params, batch_slots=2, max_seq=64)
    events, step, reset = [], eng._step, eng._reset_states

    def recording_step(p, c, b, pos):
        # copies: the engine reuses its host buffers
        events.append(({k: np.array(v) for k, v in b.items()}, np.array(pos)))
        return step(p, c, b, pos)

    def recording_reset(c, slot):
        events.append(slot)
        return reset(c, slot)

    eng._step = recording_step
    if reset is not None:
        eng._reset_states = recording_reset
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab, size=n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(3, 5), (5, 2), (2, 4), (4, 3), (3, 3), (2, 2)])]
    pending = list(reqs)
    while pending or eng.slot_req:
        while pending and eng.free_slots:
            eng.admit(pending.pop(0))
        eng.step()
    assert all(r.done for r in reqs) and len(events) > 10

    plain = jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, cfg))
    cache = M.init_cache(cfg, 2, 64, dtype=jnp.float32)
    ref = jax.tree.map(lambda a: np.array(a), cache)
    for event in events:
        if isinstance(event, tuple):
            batch, pos = event
            _, new = plain(params, cache, batch, jnp.asarray(pos))
        else:  # a request seated in a used slot: its recurrent state zeroed
            new, pos = M.reset_states(cache, cfg, event), None
        _write_rows(ref, new, cache, pos)
        cache = new
    for got, want in zip(jax.tree.leaves(eng.cache), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(got), want)

    for r in reqs:
        assert r.out == greedy_reference(cfg, params, r.prompt, r.max_new_tokens), r.rid


def test_decode_step_on_the_kernel_matches_the_jnp_path(arch, monkeypatch):
    """The decode step as a TPU runs it (the Pallas decode kernel, here
    interpreted) gives the logits of the jnp path over twenty steps with
    slots at different positions. The kernel's MXU operands are bf16, so
    the logits agree to a few bf16 ulps of their size; a slot or head
    read from the wrong place moves them by whole units."""
    from repro.models import attention

    cfg, params = arch

    def logits(impl):
        monkeypatch.setattr(attention, "default_impl", lambda: impl)
        step = jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, cfg))
        cache = M.init_cache(cfg, SLOTS, MAX_SEQ, dtype=jnp.float32)
        rng, out = np.random.default_rng(0), []
        for t in range(20):
            tok = jnp.asarray(rng.integers(1, cfg.vocab, SLOTS), jnp.int32)
            pos = jnp.asarray([t, max(t - 3, 0), t // 2], jnp.int32)
            lg, cache = step(params, cache, {"token": tok}, pos)
            out.append(np.asarray(lg))
        return np.stack(out)

    want = logits("xla")
    np.testing.assert_allclose(logits("pallas"), want, rtol=0,
                               atol=4 * 2 ** -8 * np.abs(want).max())


# ------------------------------------------------- live slots and experts
# the MoE smoke configurations, DeepSeek-V2-Lite also as one chip's share
# (experts 2 and 3 of 8, as the benchmark's DeepSeek cell holds 8 of 64)
MOE_ARCHS = {"mixtral-8x7b": None, "deepseek-v2-lite": None,
             "deepseek-v2-lite-share": (2, 2)}


@pytest.fixture(scope="module", params=sorted(MOE_ARCHS))
def moe_arch(request):
    import dataclasses

    cfg = get_smoke_config(request.param.removesuffix("-share"))
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held_experts=MOE_ARCHS[request.param]))
    return cfg, M.init_params(jax.random.key(0), cfg)


def _serve_six(eng, cfg):
    """Six requests through two slots: admit, prefill, decode, retire,
    slots seated again, and steps with a free slot."""
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab, size=n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(3, 5), (5, 2), (2, 4), (4, 3), (3, 3), (2, 2)])]
    pending = list(reqs)
    while pending or eng.slot_req:
        while pending and eng.free_slots:
            eng.admit(pending.pop(0))
        eng.step()
    return reqs


def test_greedy_tokens_with_and_without_the_live_mask(moe_arch):
    """Free slots masked out of routing or routed like live ones, every
    request's greedy tokens are those it gets served alone; masked, the
    step runs fewer experts."""
    cfg, params = moe_arch
    runs = {}
    for masked in (True, False):
        eng = Engine(cfg, params, batch_slots=2, max_seq=64)
        if not masked:
            step = eng._step
            eng._step = lambda p, c, b, pos: step(p, c, {"token": b["token"]}, pos)
        runs[masked] = eng.stats, _serve_six(eng, cfg)
    for stats, reqs in runs.values():
        for r in reqs:
            assert r.out == greedy_reference(cfg, params, r.prompt, r.max_new_tokens), r.rid
    assert runs[True][0].steps == runs[False][0].steps
    assert 0 < runs[True][0].experts_run < runs[False][0].experts_run


def test_experts_run_counts_the_held_experts_live_slots_reach(moe_arch, monkeypatch):
    """``EngineStats.experts_run`` equals a host recount over the served
    run: per step and MoE layer, the held experts that the router's top-k
    of the live slots reaches (the router's choices taken out of the
    engine's own step program)."""
    from repro.models import moe as MOE

    cfg, params = moe_arch
    choices, lives = [], []
    router_topk = MOE.router_topk

    def recording_topk(logits, k, norm_probs):
        w, idx = router_topk(logits, k, norm_probs)
        jax.debug.callback(lambda i: choices.append(np.array(i)), idx, ordered=True)
        return w, idx

    monkeypatch.setattr(MOE, "router_topk", recording_topk)
    eng = Engine(cfg, params, batch_slots=2, max_seq=64)
    step = eng._step

    def recording_step(p, c, b, pos):
        lives.append(np.array(b["live"]))
        return step(p, c, b, pos)

    eng._step = recording_step
    _serve_six(eng, cfg)
    jax.effects_barrier()
    moe_layers = sum(ffn == "moe" for _, ffn in cfg.layer_kinds()) * cfg.n_groups
    assert len(choices) == moe_layers * len(lives) == moe_layers * eng.stats.steps
    first, n = cfg.moe.held_experts or (0, cfg.moe.num_experts)
    want = sum(len({int(e) for e in idx[live].ravel() if first <= e < first + n})
               for idx, live in zip(choices, np.repeat(lives, moe_layers, axis=0)))
    assert eng.stats.experts_run == want > 0


def test_decode_step_on_the_expert_kernel_matches_the_jnp_path(moe_arch, monkeypatch):
    """The decode step as a TPU runs its MoE layers (the expert-FFN
    kernel over the stacked weights, here interpreted) gives the jnp
    path's logits and expert count over ten steps with slots going live
    and free. The configurations are float32, which both paths multiply
    in, so the logits agree to float32 rounding."""
    import functools

    from repro.kernels.moe import expert_ffn
    from repro.models import moe as MOE

    cfg, params = moe_arch

    def run(impl):
        monkeypatch.setattr(MOE, "default_impl", lambda: impl)
        monkeypatch.setattr(MOE, "expert_ffn",
                            functools.partial(expert_ffn.expert_ffn, interpret=True))
        step = jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, cfg))
        cache = M.init_cache(cfg, SLOTS, MAX_SEQ, dtype=jnp.float32)
        rng, out = np.random.default_rng(1), []
        for t in range(10):
            batch = {"token": jnp.asarray(rng.integers(1, cfg.vocab, SLOTS), jnp.int32),
                     "live": jnp.asarray([True, t % 3 > 0, t >= 4])}
            lg, cache = step(params, cache, batch, jnp.asarray([t, t // 2, 3], jnp.int32))
            out.append(np.asarray(lg))
        return np.stack(out), int(cache["experts_run"])

    want, n_want = run("xla")
    got, n_got = run("pallas")
    assert n_got == n_want > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())
