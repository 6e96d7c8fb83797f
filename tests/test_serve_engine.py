"""Serving engine: continuous batching, per-slot positions, greedy decode
consistency with the pure decode_step."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serve.engine import Engine, Request


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("tinyllama-1.1b")
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def greedy_reference(cfg, params, prompt, n_new):
    """Single-request greedy decode via the pure API."""
    cache = M.init_cache(cfg, 1, 64, dtype=jnp.float32)
    step = jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, cfg))
    logits = None
    pos = 0
    for t in prompt:
        logits, cache = step(params, cache, {"token": jnp.asarray([t], jnp.int32)}, pos)
        pos += 1
    out = []
    for _ in range(n_new):
        nxt = int(np.argmax(np.asarray(logits[0])))
        out.append(nxt)
        logits, cache = step(params, cache, {"token": jnp.asarray([nxt], jnp.int32)}, pos)
        pos += 1
    return out


def test_engine_matches_reference_single(setup):
    cfg, params = setup
    prompt = np.asarray([5, 9, 42], np.int32)
    want = greedy_reference(cfg, params, prompt, 6)
    eng = Engine(cfg, params, batch_slots=1, max_seq=64)
    req = Request(rid=0, prompt=prompt, max_new_tokens=6)
    eng.admit(req)
    eng.run_to_completion()
    assert req.done
    assert req.out == want


def test_engine_batched_isolation(setup):
    """Two concurrent requests produce the same outputs as when served
    alone (slots don't leak into each other)."""
    cfg, params = setup
    p1 = np.asarray([3, 7], np.int32)
    p2 = np.asarray([11, 2, 19, 4], np.int32)
    solo1 = greedy_reference(cfg, params, p1, 5)
    solo2 = greedy_reference(cfg, params, p2, 5)
    eng = Engine(cfg, params, batch_slots=2, max_seq=64)
    r1 = Request(rid=1, prompt=p1, max_new_tokens=5)
    r2 = Request(rid=2, prompt=p2, max_new_tokens=5)
    eng.admit(r1)
    eng.admit(r2)
    eng.run_to_completion()
    assert r1.out == solo1
    assert r2.out == solo2


def test_engine_continuous_admission(setup):
    """A late request joins after earlier ones started decoding."""
    cfg, params = setup
    eng = Engine(cfg, params, batch_slots=2, max_seq=64)
    a = Request(rid=0, prompt=np.asarray([1, 2], np.int32), max_new_tokens=4)
    eng.admit(a)
    eng.step()
    eng.step()
    b = Request(rid=1, prompt=np.asarray([9, 9, 9], np.int32), max_new_tokens=3)
    eng.admit(b)
    eng.run_to_completion()
    assert a.done and b.done
    assert b.out == greedy_reference(cfg, params, b.prompt, 3)


def test_slot_reuse(setup):
    cfg, params = setup
    eng = Engine(cfg, params, batch_slots=1, max_seq=64)
    r1 = Request(rid=0, prompt=np.asarray([4], np.int32), max_new_tokens=2)
    eng.admit(r1)
    eng.run_to_completion()
    assert r1.done and eng.free_slots == [0]
    # NOTE: reusing a slot inherits stale cache beyond the new request's
    # positions; positions reset on admit, and attention masks by position,
    # so stale entries past the new prompt are masked out.
    r2 = Request(rid=1, prompt=np.asarray([4], np.int32), max_new_tokens=2)
    eng.admit(r2)
    eng.run_to_completion()
    assert r2.done
    assert r2.out == r1.out  # same prompt, same params -> same greedy output


def test_admit_coadvance_semantics(setup):
    """The documented co-advance contract of ``Engine.admit``: while a new
    prompt prefills, every other active slot keeps DECODING — those tokens
    are real output, identical to solo greedy, they count against the
    decoding request's budget (it can finish mid-prefill), and the
    admitted request itself is charged nothing until its first decode."""
    cfg, params = setup
    a_prompt = np.asarray([3, 7], np.int32)
    solo = greedy_reference(cfg, params, a_prompt, 3)
    eng = Engine(cfg, params, batch_slots=2, max_seq=64)
    a = Request(rid=0, prompt=a_prompt, max_new_tokens=3)
    eng.admit(a)
    eng.step()
    assert len(a.out) == 1
    # 6-token prompt = 5 co-advance steps: a's remaining budget (2) is
    # consumed mid-prefill and its slot frees before admit returns
    b = Request(rid=1, prompt=np.asarray([9, 8, 7, 6, 5, 4], np.int32),
                max_new_tokens=2)
    eng.admit(b)
    assert a.done and a.out == solo      # finished DURING b's prefill
    assert b.out == []                   # prefill charged nothing to b
    eng.run_to_completion()
    assert b.done and len(b.out) == 2
    assert b.out == greedy_reference(cfg, params, b.prompt, 2)


def test_admit_into_slot_freed_same_step(setup):
    """A slot retired inside ``step`` is admittable immediately — no dead
    step between retirement and the next request — and the re-admitted
    request's output matches solo greedy despite the stale cache beyond
    its positions."""
    cfg, params = setup
    eng = Engine(cfg, params, batch_slots=1, max_seq=64)
    r1 = Request(rid=0, prompt=np.asarray([4, 13], np.int32), max_new_tokens=1)
    eng.admit(r1)
    eng.step()  # r1 finishes and leaves its slot during THIS step
    assert r1.done and eng.free_slots == [0]
    r2 = Request(rid=1, prompt=np.asarray([7, 7, 7], np.int32), max_new_tokens=3)
    assert eng.admit(r2)
    eng.run_to_completion()
    assert r2.done
    assert r2.out == greedy_reference(cfg, params, r2.prompt, 3)


def test_max_seq_truncation(setup):
    """A request whose budget exceeds the cache truncates at max_seq-1
    instead of writing past the cache (and still reports done)."""
    cfg, params = setup
    eng = Engine(cfg, params, batch_slots=1, max_seq=12)
    req = Request(rid=0, prompt=np.asarray([5, 9, 42], np.int32),
                  max_new_tokens=100)
    eng.admit(req)
    eng.run_to_completion()
    assert req.done
    assert 0 < len(req.out) < 100
    # truncated exactly at the cache bound, bit-exact up to the cut
    want = greedy_reference(cfg, params, req.prompt, len(req.out))
    assert req.out == want
    assert eng.tokens_out == len(req.out)


# ------------------------------------------------------------ observability
def _tiny_moe():
    """The benchmark's tiny MoE decoder (``bench/tests/tiny.py``)."""
    import pathlib
    import sys

    root = str(pathlib.Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.families import decoder
    from bench.tests import tiny

    cfg = tiny.CONFIGS["tiny-moe"]
    return decoder.program_config(cfg), decoder.make_params(cfg, 0)


def _drive(eng):
    """Admissions that overlap decoding, then decode to the end."""
    reqs = [Request(rid=i, prompt=np.arange(2 + i, dtype=np.int32) + 1,
                    max_new_tokens=3 + i) for i in range(3)]
    eng.admit(reqs[0])
    eng.step()
    eng.admit(reqs[1])
    eng.step()
    eng.admit(reqs[2])
    eng.run_to_completion()
    return reqs


def test_engine_stats_equal_the_harness_counts():
    """``Engine.stats`` counts what the benchmark's ``TimedEngine`` counts
    around the engine's seams, over the same run."""
    from bench.loops.serve_open import _engine_class

    cfg, params = _tiny_moe()
    eng = _engine_class()(cfg, params, 2, 32)
    reqs = _drive(eng)
    assert all(r.done for r in reqs)
    st = eng.stats
    for k in ("steps", "prefill_steps", "tokens_processed", "decode_tokens", "context"):
        assert getattr(st, k) == eng.n[k], k
    assert st.steps == eng.steps_run and st.decode_tokens == eng.tokens_out
    assert st.decode_tokens == sum(len(r.out) for r in reqs)
    assert st.prefill_steps == sum(len(r.prompt) - 1 for r in reqs)
    st.reset()
    assert eng.steps_run == 0 and eng.tokens_out == 0


def test_request_timestamps_are_set_and_ordered(setup):
    cfg, params = setup
    eng = Engine(cfg, params, batch_slots=2, max_seq=64)
    reqs = _drive(eng)
    for r in reqs:
        assert r.arrival is None  # the caller's to stamp
        assert r.admitted_at is not None and r.first_token_at is not None
        assert r.admitted_at < r.first_token_at
    assert reqs[0].admitted_at < reqs[1].admitted_at < reqs[2].admitted_at


def test_engine_spans_in_a_cpu_trace(setup, tmp_path):
    """One admission and one step leave each ``engine.*`` span in a
    profiler trace, the admission and the prefill steps with their rid."""
    import glob

    from jax.profiler import ProfileData

    cfg, params = setup
    eng = Engine(cfg, params, batch_slots=2, max_seq=64)
    eng.admit(Request(rid=0, prompt=np.asarray([1, 2], np.int32), max_new_tokens=4))
    eng.step()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    eng.admit(Request(rid=7, prompt=np.asarray([3, 4, 5], np.int32), max_new_tokens=2))
    eng.step()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1])
    spans = [(e.name, dict(e.stats)) for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name.startswith("engine.")]
    names = [n for n, _ in spans]
    for n in ("engine.inputs", "engine.dispatch", "engine.fetch", "engine.commit"):
        assert names.count(n) == 3, n  # two prefill steps and one decode step
    assert [s for n, s in spans if n == "engine.admit"] == [{"rid": 7, "prompt_tokens": 3}]
    steps = [s for n, s in spans if n == "engine.step"]
    assert sorted(steps, key=lambda s: s["kind"]) == [
        {"kind": "decode", "slots": 2},
        {"kind": "prefill", "rid": 7, "slots": 2},
        {"kind": "prefill", "rid": 7, "slots": 2}]
