"""The reduction by the program's own marks: engine spans kept apart from
the harness's, gaps labelled by the innermost engine span, scopes read
from compiled ``op_name`` metadata, device seconds per scope from a
recorded CPU trace, and the whole attribution on the tiny cells."""

import jax
import jax.numpy as jnp
import pytest

from bench import attribute as A
from bench import program_trace as PT
from bench import run as R
from bench import trace_reduce as TR
from bench.tests import tiny

E = TR.Event
SEED = 2 ** 31 + 77


def _trace():
    # window 0-100 ns; chip 0 busy 10-40 and 60-70
    ops = {0: [E("fusion.1", 10, 40), E("fusion.2", 60, 70)]}
    spans = [E(TR.WINDOW_SPAN, 0, 100), E("bench.step", 0, 100)]
    program = [E("engine.step", 0, 70), E("engine.dispatch", 2, 8),
               E("engine.fetch", 40, 58), E("engine.commit", 70, 75)]
    return TR.Trace(ops, {}, spans), program


def test_gaps_are_labelled_by_the_innermost_engine_span():
    t, program = _trace()
    assert PT.idle_by_program_span(t, program) == [
        (PT.OUTSIDE, pytest.approx(30e-9)),           # 70-100, mid 85
        ("engine.fetch", pytest.approx(20e-9)),       # 40-60, mid 50
        ("engine.dispatch", pytest.approx(10e-9))]    # 0-10, mid 5
    assert PT.step_host_gap_ms(t, program, steps=2) == pytest.approx(1e3 * 30e-9 / 2)
    assert PT.span_ms(t, program) == pytest.approx(
        {"engine.commit": 5e-6, "engine.dispatch": 6e-6, "engine.fetch": 18e-6,
         "engine.step": 70e-6})
    # the harness's labels are unchanged by the program's spans
    assert t.idle_by_span() == [("bench.step", pytest.approx(60e-9))]


def test_scope_of_strips_transform_wrappers():
    assert PT.scope_of("jit(f)/transpose(jvp(decode.layers))/while/body/"
                       "closed_call/moe.expert_ffn/mul") == "moe.expert_ffn"
    assert PT.scope_of("jit(f)/jvp(decode.layers)/while") == "decode.layers"
    assert PT.scope_of("jit(f)/checkpoint/attn.flash_bwd/cos") == "attn.flash_bwd"
    assert PT.scope_of("jit(f)/body/attn.kv_update/squeeze;attn.qkv/reshape") == "attn.kv_update"
    assert PT.scope_of("jit(<lambda>)/reduce_sum") is None


def _scoped(a):
    with jax.named_scope("attn.qkv"):
        b = jnp.tanh(a @ a)
    with jax.named_scope("moe.expert_ffn"):
        return jnp.sin(b @ a).sum()


def test_scope_map_reads_compiled_op_names():
    a = jnp.ones((128, 128))
    scopes = PT.scope_map(jax.jit(_scoped).lower(a).compile().as_text())
    assert set(scopes.values()) == {"attn.qkv", "moe.expert_ffn"}


def test_scope_seconds_of_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(_scoped)
    a = jnp.ones((512, 512))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("engine.step", kind="decode", slots=1):
                f(a).block_until_ready()
    jax.profiler.stop_trace()
    trace, spans = PT.load(str(tmp_path), "cpu")
    assert [s.name for s in spans] == ["engine.step"] * 5
    assert not [s for s in trace.spans if s.name.startswith(PT.PROGRAM_PREFIX)]
    sec = PT.scope_seconds(trace, PT.scope_map(f.lower(a).compile().as_text()))
    assert sec.get("attn.qkv", 0) > 0 and sec.get("moe.expert_ffn", 0) > 0
    assert sum(sec.values()) == pytest.approx(sum(trace.op_seconds().values()))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell, readings", [
    ("tiny-serve-decode", {"step_host_gap_ms.serve", "admit_wait_p90_ms.serve",
                           "kv_cache_share.serve"}),
    ("tiny-train", {"attn_bwd_share.train"})])
def test_attribute_on_the_tiny_cells(root, cell, readings):
    out = A.attribute(R.load_cell(root, cell), SEED, 0.5, platform="cpu",
                      annotation_calls=1000)
    assert set(out["readings"]) == readings
    assert all(v is not None and v >= 0 for v in out["readings"].values())
    assert out["traced"]["steps"] > 0 and out["scope_seconds"]
    assert out["annotation_us"]["off"] > 0 and out["annotation_us"]["on"] > 0
    if "serve" in cell:
        assert out["program_spans"] > 0
        assert out["traced"]["engine"]["steps"] == out["traced"]["steps"]
        assert {label for label, _ in out["idle_by_program_span"]} <= {
            PT.OUTSIDE, "engine.admit", "engine.step", "engine.inputs",
            "engine.dispatch", "engine.fetch", "engine.commit"}
