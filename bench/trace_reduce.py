"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

What it gives, all over the traced window that the harness marks with its
host span ``bench.trace_window``:

- busy time per chip: the union of the intervals in which an operation ran
  on that chip (overlapping operations count once);
- device time per operation name;
- collective time that no compute on the same chip overlaps (exposed);
- idle gaps, each labelled by the innermost harness span open on the host
  at the gap's midpoint;
- the device time of each compiled program (XLA module) run.

On a TPU the operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane and the programs those of its ``XLA Modules``
line. A CPU trace has no device plane; ``platform="cpu"`` reads the XLA
client threads' events as one chip, which is what the tests record.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import re

WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-to-all|collective-permute|all-reduce|all-gather|reduce-scatter"
    r"|\bsend\b|\brecv\b", re.IGNORECASE)


# control flow whose body's operations are events of the same line
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(name: str) -> str:
    """The instruction name of an operation event. A TPU trace names its
    events by the whole HLO line (``%fusion.3 = f32[8] fusion(...)``);
    this gives ``fusion.3``, as the compiled program's text names it."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float    # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]      # chip -> device operations
    modules: dict[int, list[Event]]  # chip -> compiled-program runs
    spans: list[Event]               # harness host spans

    # ----------------------------------------------------------- window
    def window(self) -> tuple[float, float]:
        marks = [s for s in self.spans if s.name == WINDOW_SPAN]
        if marks:
            return marks[0].start, marks[0].end
        evs = [e for evs in self.ops.values() for e in evs]
        return min(e.start for e in evs), max(e.end for e in evs)

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9

    def _in_window(self, evs):
        lo, hi = self.window()
        return [Event(e.name, max(e.start, lo), min(e.end, hi))
                for e in evs if e.end > lo and e.start < hi]

    # ------------------------------------------------------------- busy
    def busy_s(self, chip: int) -> float:
        return union_ns(self._in_window(self.ops.get(chip, []))) * 1e-9

    def mean_busy_s(self) -> float:
        chips = sorted(self.ops)
        return sum(self.busy_s(c) for c in chips) / len(chips)

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s()

    # ------------------------------------------------------- operations
    def op_seconds(self) -> dict[str, float]:
        """Device seconds per operation name, averaged over chips. Loops
        and other control flow are left out: the operations of their
        bodies are counted themselves."""
        tot: dict[str, float] = collections.defaultdict(float)
        for chip, evs in self.ops.items():
            for e in self._in_window(evs):
                if not CONTAINER.match(op_name(e.name)):
                    tot[e.name] += e.dur * 1e-9
        return {k: v / len(self.ops) for k, v in tot.items()}

    def ops_named(self, names) -> list[Event]:
        """Every operation (all chips) whose instruction name
        (``op_name``) is in ``names``."""
        names = set(names)
        return [e for evs in self.ops.values() for e in self._in_window(evs)
                if op_name(e.name) in names]

    def exposed_collective_s(self, chip: int) -> float:
        """Collective time on ``chip`` during which no other operation ran
        there."""
        evs = self._in_window(self.ops.get(chip, []))
        coll = [e for e in evs if COLLECTIVE.search(e.name)]
        comp = [e for e in evs if not COLLECTIVE.search(e.name)]
        return (union_ns(coll) - intersect_ns(coll, comp)) * 1e-9

    def mean_exposed_collective_s(self) -> float:
        chips = sorted(self.ops)
        return sum(self.exposed_collective_s(c) for c in chips) / len(chips)

    def collective_calls(self, chip: int) -> int:
        return sum(1 for e in self._in_window(self.ops.get(chip, []))
                   if COLLECTIVE.search(e.name))

    # --------------------------------------------------------- programs
    def module_runs(self, prefix: str, chip: int = 0) -> list[Event]:
        """Runs of the compiled program(s) whose name starts with
        ``prefix`` on ``chip``."""
        return [e for e in self._in_window(self.modules.get(chip, []))
                if e.name.startswith(prefix)]

    # ------------------------------------------------------------- gaps
    def idle_gaps(self, chip: int = 0) -> list[tuple[str, float]]:
        """Each gap between busy intervals on ``chip`` inside the window,
        as (label, seconds), longest first. The label is the innermost
        harness span open on the host at the gap's midpoint, or
        ``"(no span)"``."""
        lo, hi = self.window()
        gaps, t = [], lo
        for s, e in merged(self._in_window(self.ops.get(chip, []))):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        spans = [s for s in self.spans if s.name != WINDOW_SPAN]
        out = []
        for s, e in gaps:
            mid = 0.5 * (s + e)
            open_ = [sp for sp in spans if sp.start <= mid < sp.end]
            label = max(open_, key=lambda sp: sp.start).name if open_ else "(no span)"
            out.append((label, (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def idle_by_span(self, chip: int = 0) -> list[tuple[str, float]]:
        """Idle seconds on ``chip`` summed per host-span label, largest
        first."""
        tot: dict[str, float] = collections.defaultdict(float)
        for label, sec in self.idle_gaps(chip):
            tot[label] += sec
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_by_span()[:n]]}


# ------------------------------------------------------------ intervals
def merged(evs) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((e.start, e.end) for e in evs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(evs) -> float:
    return sum(e - s for s, e in merged(evs))


def intersect_ns(a, b) -> float:
    """Length of (union of a) ∩ (union of b)."""
    ma, mb = merged(a), merged(b)
    i = j = 0
    tot = 0.0
    while i < len(ma) and j < len(mb):
        s = max(ma[i][0], mb[j][0])
        e = min(ma[i][1], mb[j][1])
        if e > s:
            tot += e - s
        if ma[i][1] < mb[j][1]:
            i += 1
        else:
            j += 1
    return tot


# -------------------------------------------------------------- loading
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)\b")


def from_profile(pd, platform: str = "tpu") -> Trace:
    """Build a ``Trace`` from ``jax.profiler.ProfileData``."""
    ops: dict[int, list[Event]] = collections.defaultdict(list)
    modules: dict[int, list[Event]] = collections.defaultdict(list)
    spans: list[Event] = []

    def ev(e):
        return Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))

    for plane in pd.planes:
        m = _TPU_PLANE.match(plane.name)
        if platform == "tpu" and m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip].extend(ev(e) for e in line.events)
                elif line.name == "XLA Modules":
                    modules[chip].extend(ev(e) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                cpu_ops = platform == "cpu" and line.name.startswith("tf_XLA")
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(ev(e))
                    elif cpu_ops and e.duration_ns > 0 and "::" not in e.name:
                        ops[0].append(ev(e))
    if not ops:
        raise ValueError(f"no device operations found in the trace ({platform})")
    return Trace(dict(ops), dict(modules), spans)


def load(trace_dir: str, platform: str = "tpu") -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]), platform)
