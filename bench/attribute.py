"""Attribute a cell's idle and device time to the program's own marks.

    python3 bench/attribute.py --workload <name> --seed <n> --seconds <s>

Sets the cell up as ``bench/run.py`` does, measures for ``--seconds`` with
no trace, then records the mix's ``trace_seconds`` under the profiler and
reduces that trace by the serving engine's spans and the model step's
named scopes (``bench/program_trace.py``). It checks no correctness and
reports none of the benchmark's metrics; the last line of standard output
is a JSON object with:

- ``readings``: ``step_host_gap_ms.serve``, ``admit_wait_p90_ms.serve``
  and ``kv_cache_share.serve`` in a serving cell, ``attn_bwd_share.train``
  in a training cell;
- the traced window's idle per harness span (``idle_by_span``, as the
  result line's breakdown has it) and per engine span
  (``idle_by_program_span``), the engine spans' mean durations, and
  device seconds per scope;
- the engine's counters over each window, steps per second in the
  measured and the traced window, and what one ``TraceAnnotation`` costs
  to enter and exit with the profiler off and on (``annotation_us``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

# reading -> scopes whose device time it counts
SHARES = {"serve_open": {"kv_cache_share.serve": ("attn.kv_update", "decode.layers")},
          "train_steps": {"attn_bwd_share.train": ("attn.flash_bwd",)}}


def compiled_text(loop) -> str:
    """The compiled text of the step the loop drives, lowered again on the
    loop's own arguments (the compilation cache serves it)."""
    import jax.numpy as jnp

    if hasattr(loop, "eng"):
        eng = loop.eng
        return eng._step.lower(eng.params, eng.cache,
                               {"token": jnp.asarray(eng.pending_tok)},
                               jnp.asarray(eng.positions)).compile().as_text()
    return loop.step_fn.lower(loop.params, loop.opt_state,
                              loop._batch(0)).compile().as_text()


def annotation_us(trace_dir: pathlib.Path, n: int = 100_000) -> dict:
    """Microseconds to enter and exit one ``engine.step``-shaped
    annotation (two keyword arguments), with the profiler off and on."""
    import jax

    def per_call():
        t0 = time.perf_counter()
        for _ in range(n):
            with jax.profiler.TraceAnnotation("engine.step", kind="decode", slots=16):
                pass
        return 1e6 * (time.perf_counter() - t0) / n

    off = per_call()
    jax.profiler.start_trace(str(trace_dir))
    try:
        on = per_call()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {"off": off, "on": on}


def admit_waits(loop, t0: float, t1: float) -> list[float]:
    """Queue waits (admission minus scheduled arrival) of the requests
    admitted between ``t0`` and ``t1``."""
    reqs = list(loop.finished) + list(loop.eng.slot_req.values())
    return [r.admitted_at - loop.submitted[r.rid] for r in reqs
            if r.admitted_at is not None and t0 <= r.admitted_at < t1]


def attribute(cell, seed: int, seconds: float, platform: str = "tpu",
              annotation_calls: int = 100_000) -> dict:
    import jax

    from bench import program_trace as PT
    from bench import run as R
    from bench import trace_reduce as TR
    from repro.launch.compile_cache import enable_compile_cache

    R.log(f"compilation cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    loop = R.make_loop(R.make_ctx(cell, seed))
    loop.setup()
    eng = getattr(loop, "eng", None)
    out: dict = {"workload": cell.name, "seed": seed}

    def window(seconds, name):
        if eng is not None:
            eng.stats.reset()
        t0 = time.perf_counter()
        w = loop.run(seconds)
        t1 = time.perf_counter()
        out[name] = {"seconds": w["seconds"], "steps": w["steps"],
                     "steps_per_s": w["steps"] / w["seconds"]}
        if eng is not None:
            out[name]["engine"] = dataclasses.asdict(eng.stats)
        R.log(f"{name} {w['seconds']:.3f} s: {loop.describe(w)}")
        return w, t0, t1

    w, t0, t1 = window(seconds, "window")
    readings = {}
    if eng is not None:
        waits = admit_waits(loop, t0, t1)
        out["window"]["admitted"] = len(waits)
        readings["admit_wait_p90_ms.serve"] = PT.admit_wait_p90_ms(waits)

    tdir = cell.bench_dir.parent / ".bench_trace" / f"{cell.name}.attribute"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
            tw, _, _ = window(cell.traffic["trace_seconds"], "traced")
    finally:
        jax.profiler.stop_trace()
    trace, spans = PT.load(str(tdir), platform)
    shutil.rmtree(tdir, ignore_errors=True)
    scopes = PT.scope_map(compiled_text(loop))
    if eng is not None:
        readings["step_host_gap_ms.serve"] = PT.step_host_gap_ms(trace, spans, tw["steps"])
    for name, names in SHARES[cell.traffic["loop"]].items():
        readings[name] = PT.scope_share(trace, scopes, names)
    out.update(
        readings=readings,
        window_s=trace.window_s(), busy_s=trace.mean_busy_s(),
        step_device_s=PT.step_device_s(trace),
        idle_by_span=trace.idle_by_span(),
        idle_by_program_span=PT.idle_by_program_span(trace, spans),
        program_spans=len(spans), span_ms=PT.span_ms(trace, spans),
        scope_seconds=PT.scope_seconds(trace, scopes),
        annotation_us=annotation_us(tdir, annotation_calls))
    loop.release()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import run as R

    cell = R.load_cell(ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        R.log(f"attribute needs a TPU; JAX found {jax.devices()[0].platform!r}")
        return 2
    out = attribute(cell, args.seed, args.seconds)
    for k, v in out["readings"].items():
        R.log(f"{k} {v!r}")
    R.log(f"idle by harness span {out['idle_by_span']}")
    R.log(f"idle by engine span {out['idle_by_program_span']}")
    R.log(f"engine spans' mean ms {out['span_ms']}")
    R.log(f"device seconds by scope {out['scope_seconds']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
