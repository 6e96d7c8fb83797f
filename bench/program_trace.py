"""Reduce a profiler trace by the program's own marks.

``bench/trace_reduce.py`` labels idle time by the harness's ``bench.*``
spans and names device operations by instruction. This module adds what
the program marks itself:

- the serving engine's host spans (``engine.*``, ``repro.serve.engine``):
  idle gaps labelled by the innermost engine span open at the gap's
  midpoint, ``"(outside the engine)"`` for the rest;
- the model step's named scopes (``<layer>.<part>``, e.g.
  ``moe.expert_ffn``), which reach the compiled program as ``op_name``
  metadata: instruction name to scope (``scope_map``), then device seconds
  per scope (``scope_seconds``).

The spans are ``jax.profiler.TraceAnnotation`` events in the same trace as
the device planes, so both sit on one clock.
"""

from __future__ import annotations

import collections
import glob
import re

import numpy as np

from bench import trace_reduce as TR

PROGRAM_PREFIX = "engine."
OUTSIDE = "(outside the engine)"
NO_SCOPE = "(no scope)"
SCOPE = re.compile(r"^[a-z_]+\.[a-z_]+$")
# a transform's wrapper around a scope entered outside it: jvp(decode.layers)
_WRAPPER = re.compile(r"^[\w-]+\((.*)\)$")
_INSTR = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*\bop_name="([^"]*)"')


# ---------------------------------------------------------------- spans
def program_spans(pd) -> list[TR.Event]:
    """The engine's host spans in ``jax.profiler.ProfileData``."""
    return [TR.Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_PREFIX)]


def load(trace_dir: str, platform: str = "tpu") -> tuple[TR.Trace, list[TR.Event]]:
    """The harness's ``Trace`` of the newest ``.xplane.pb`` under
    ``trace_dir``, and the program spans of the same file."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    return TR.from_profile(pd, platform), program_spans(pd)


def idle_by_program_span(trace: TR.Trace, spans, chip: int = 0) -> list[tuple[str, float]]:
    """Idle seconds on ``chip`` in the traced window per innermost engine
    span open at each gap's midpoint, largest first."""
    window = [s for s in trace.spans if s.name == TR.WINDOW_SPAN]
    by_program = TR.Trace(trace.ops, trace.modules, window + list(spans))
    return [(OUTSIDE if label == "(no span)" else label, sec)
            for label, sec in by_program.idle_by_span(chip)]


def span_ms(trace: TR.Trace, spans) -> dict[str, float]:
    """Mean duration of each engine span that starts in the traced window,
    by name, in ms."""
    lo, hi = trace.window()
    durs: dict[str, list] = collections.defaultdict(list)
    for s in spans:
        if lo <= s.start < hi:
            durs[s.name].append(s.dur)
    return {k: 1e-6 * sum(v) / len(v) for k, v in sorted(durs.items())}


def step_host_gap_ms(trace: TR.Trace, spans, steps: int) -> float | None:
    """Idle under an engine span over the window's engine steps, in ms."""
    if not steps:
        return None
    idle = sum(sec for label, sec in idle_by_program_span(trace, spans)
               if label != OUTSIDE)
    return 1e3 * idle / steps


# --------------------------------------------------------------- scopes
def scope_of(op_name: str) -> str | None:
    """The innermost scope in an ``op_name`` path, transform wrappers
    stripped: ``jit(f)/transpose(jvp(decode.layers))/while/body/
    attn.kv_update/scatter`` gives ``attn.kv_update``. Where XLA merged
    instructions (``a/b;c/d``) the first path counts."""
    found = None
    for part in op_name.split(";")[0].split("/"):
        while (m := _WRAPPER.match(part)):
            part = m.group(1)
        if SCOPE.match(part):
            found = part
    return found


def scope_map(compiled_text: str) -> dict[str, str]:
    """Instruction name to scope, over a compiled program's text
    (``jax.stages.Compiled.as_text()``); instructions with no scope are
    left out."""
    out = {}
    for line in compiled_text.splitlines():
        m = _INSTR.match(line)
        if m and (scope := scope_of(m.group(2))):
            out[m.group(1)] = scope
    return out


def scope_seconds(trace: TR.Trace, scopes: dict[str, str]) -> dict[str, float]:
    """Device seconds per scope in the traced window, averaged over chips
    (loops left out, as ``Trace.op_seconds`` does), largest first;
    ``"(no scope)"`` for operations outside every scope."""
    tot: dict[str, float] = collections.defaultdict(float)
    for name, sec in trace.op_seconds().items():
        tot[scopes.get(TR.op_name(name), NO_SCOPE)] += sec
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def step_device_s(trace: TR.Trace) -> float:
    """Device seconds of the programs run in the window (busy time where
    the trace has no program line)."""
    runs = trace.module_runs("")
    return sum(r.dur for r in runs) * 1e-9 if runs else trace.busy_s(0)


def scope_share(trace: TR.Trace, scopes: dict[str, str], names) -> float | None:
    """Share of the programs' device time in operations under ``names``,
    in %."""
    device_s = step_device_s(trace)
    if not device_s:
        return None
    sec = scope_seconds(trace, scopes)
    return 100.0 * sum(sec.get(n, 0.0) for n in names) / device_s


# ------------------------------------------------------------- requests
def admit_wait_p90_ms(waits) -> float | None:
    """90th percentile of the requests' queue waits (seconds), in ms."""
    return 1e3 * float(np.percentile(waits, 90)) if len(waits) else None
