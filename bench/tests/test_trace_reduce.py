"""The trace reduction: busy as a union, idle share, time per operation,
exposed collective time and gap attribution, on hand-made events and on a
small trace recorded on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as TR

E = TR.Event


def _trace():
    # window 0-100 ns; chip 0: compute 10-40 and 30-50 (overlap 10),
    # all-to-all 45-70 (5 ns under compute), nothing 70-100
    ops = {0: [E("fusion.1", 10, 40), E("fusion.2", 30, 50),
               E("all-to-all.3", 45, 70)],
           1: [E("fusion.1", 0, 100)]}
    spans = [E(TR.WINDOW_SPAN, 0, 100), E("bench.step", 0, 60),
             E("bench.commit", 60, 100), E("bench.admit", 1, 9)]
    return TR.Trace(ops, {0: [E("jit_step(1)", 10, 70)]}, spans)


def test_busy_is_a_union_not_a_sum():
    t = _trace()
    assert t.busy_s(0) == pytest.approx(60e-9)   # 10..70, not 30+20+25
    assert t.busy_s(1) == pytest.approx(100e-9)
    assert t.mean_busy_s() == pytest.approx(80e-9)
    assert t.idle_share() == pytest.approx(0.2)
    assert t.window_s() == pytest.approx(100e-9)


def test_time_per_operation_is_averaged_over_chips():
    s = _trace().op_seconds()
    assert s["fusion.1"] == pytest.approx((30 + 100) / 2 * 1e-9)
    assert s["all-to-all.3"] == pytest.approx(25 / 2 * 1e-9)


def test_tpu_events_are_matched_by_instruction_name():
    hlo = "%flash_attention.20 = bf16[64,2048,128]{2,1,0} custom-call(bf16[64,2048,128] %b)"
    loop = "%while.3 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%body"
    t = TR.Trace({0: [E(loop, 0, 100), E(hlo, 10, 40), E("%fusion.1 = f32[8] fusion()", 50, 60)]},
                 {}, [E(TR.WINDOW_SPAN, 0, 100)])
    assert TR.op_name(hlo) == "flash_attention.20" and TR.op_name("fusion.2") == "fusion.2"
    assert [e.name for e in t.ops_named(["flash_attention.20"])] == [hlo]
    # the loop's body operations count; the loop itself does not
    assert t.op_seconds() == {hlo: pytest.approx(30e-9),
                              "%fusion.1 = f32[8] fusion()": pytest.approx(10e-9)}
    assert t.busy_s(0) == pytest.approx(100e-9)


def test_exposed_collective_time_leaves_out_overlap():
    t = _trace()
    assert t.exposed_collective_s(0) == pytest.approx(20e-9)  # 50..70
    assert t.exposed_collective_s(1) == 0.0
    assert t.collective_calls(0) == 1


def test_gaps_are_labelled_by_the_innermost_open_span():
    gaps = _trace().idle_gaps(0)
    assert gaps == [("bench.commit", pytest.approx(30e-9)),
                    ("bench.admit", pytest.approx(10e-9))]
    assert _trace().idle_by_span(0)[0][0] == "bench.commit"


def test_events_are_clipped_to_the_window():
    t = TR.Trace({0: [E("x", -50, 20), E("y", 90, 150)]}, {},
                 [E(TR.WINDOW_SPAN, 0, 100)])
    assert t.busy_s(0) == pytest.approx(30e-9)


def test_program_runs():
    assert [r.name for r in _trace().module_runs("jit_step")] == ["jit_step(1)"]


def test_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((512, 512))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(a).block_until_ready()
    jax.profiler.stop_trace()
    t = TR.load(str(tmp_path), platform="cpu")
    assert 0 < t.mean_busy_s() <= t.window_s()
    assert 0.0 <= t.idle_share() < 1.0
    assert t.op_seconds()
    total_ops = sum(e.dur for e in t.ops[0])
    assert t.busy_s(0) <= total_ops * 1e-9 + 1e-12
    labels = {label for label, _ in t.idle_gaps()}
    assert labels <= {"bench.call", "(no span)"}
    bd = t.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
