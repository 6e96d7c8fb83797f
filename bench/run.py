"""Chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``, whose
``family`` picks ``bench/families/<family>.py`` and the plain reference
``bench/reference/<family>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``, whose ``loop`` picks
``bench/loops/<loop>.py``). Per-layer metrics are read by
``bench/metrics/<metric>.py`` and the limits of the correctness comparison
are in ``bench/limits/<workload>.json``. Adding a configuration, a mix, a
metric or a cell adds files and entries; no file here names one.

A run: refuse a device that is not a TPU or has no published peak
(``bench/peaks.py``), or fewer chips than the cell asks for, before
anything else; set up (build, compile, warm, fill) with JAX's persistent
compilation cache in the checkout; measure for ``--seconds``; with
``--trace 1`` continue for the mix's ``trace_seconds`` under the
profiler and reduce that trace (``bench/trace_reduce.py``); free the
program's state; compare what the timed path produced with the float32
reference. The last lines of standard error give each compared number
beside its limit; the last line of standard output is the JSON result.
"""

from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started, from /proc (10 ms steps)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


T_START = time.perf_counter() - process_age()


# ------------------------------------------------------------------ spec
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # [(name, unit)]
    per_layer: list           # [(name, unit)]
    limits: dict
    bench_dir: pathlib.Path   # the benchmark's directory in this checkout


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def reports(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (reports(m) if "workloads" in m else m["moves"] in moved)]
    bdir = root / bench["paths"][0]
    return Cell(
        name=workload, chips=w["chips"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((bdir / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[(m["name"], m["unit"]) for m in e2e],
        per_layer=[(m["name"], m["unit"]) for m in layer],
        limits=json.loads((bdir / "limits" / f"{workload}.json").read_text()),
        bench_dir=bdir)


@dataclasses.dataclass
class Ctx:
    """What a loop gets: the cell's configuration and mix, the seed, and
    the family and reference modules the configuration names."""
    cell: Cell
    seed: int
    family: object
    reference: object
    log: object = log

    @property
    def config(self):
        return self.cell.config

    @property
    def traffic(self):
        return self.cell.traffic


def make_ctx(cell: Cell, seed: int) -> Ctx:
    fam = cell.config["family"]
    return Ctx(cell, seed % (1 << 63),
               importlib.import_module(f"bench.families.{fam}"),
               importlib.import_module(f"bench.reference.{fam}"))


def make_loop(ctx: Ctx):
    return importlib.import_module(f"bench.loops.{ctx.traffic['loop']}").Loop(ctx)


@dataclasses.dataclass
class MetricCtx:
    """What a per-layer reader gets."""
    cell: Cell
    window: dict      # the measured window's counters
    traced: dict      # the traced window's counters
    trace: object     # trace_reduce.Trace
    peak: object      # peaks.Peak
    kernels: dict     # kernel -> compiled instruction names

    @property
    def config(self):
        return self.cell.config


def read_metric(name: str, mctx: MetricCtx):
    path = mctx.cell.bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(mctx)


# ------------------------------------------------------------------- run
class Compiles:
    """Counts programs built (compiled, or loaded from the persistent
    cache) while it is listening."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.n += 1


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run(cell: Cell, seed: int, seconds: float, trace: bool, peak,
        trace_platform: str = "tpu") -> dict:
    import jax

    from bench import trace_reduce
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compilation cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()
    ctx = make_ctx(cell, seed)
    loop = make_loop(ctx)
    with jax.profiler.TraceAnnotation("bench.setup"):
        loop.setup()
    setup_s = time.perf_counter() - T_START
    built = compiles.n
    w = loop.run(seconds)
    log(f"window {w['seconds']:.3f} s: {loop.describe(w)}")
    log(f"programs built in set-up {built}, in the window {compiles.n - built}")
    tr, tw, kernels = None, {}, {}
    if trace:
        tdir = cell.bench_dir.parent / ".bench_trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                tw = loop.run(cell.traffic["trace_seconds"])
        finally:
            jax.profiler.stop_trace()
        tr = trace_reduce.load(str(tdir), trace_platform)
        shutil.rmtree(tdir, ignore_errors=True)
        progs = sorted({e.name.split("(")[0] for e in tr.module_runs("")})
        log(f"trace: operations per chip {[len(v) for v in tr.ops.values()]}, "
            f"programs {progs[:8]}, host spans {len(tr.spans)}")
        if hasattr(loop, "kernel_names"):
            kernels = loop.kernel_names()
        log(f"traced window {tr.window_s():.3f} s: {loop.describe(tw)}; "
            f"kernels {kernels}")
    device = device_info(cell.chips)
    log(f"memory_peak_bytes {device['memory_peak_bytes']}")
    attempted, failed = loop.attempted(w)
    e2e = loop.end_to_end(w)
    loop.release()
    gc.collect()
    t_check = time.perf_counter()
    compared = loop.check()
    log(f"comparison with the reference {time.perf_counter() - t_check:.1f} s")
    correct = failed == 0 and all(
        compared[k] <= cell.limits[k] for k in cell.limits)
    if trace:
        mctx = MetricCtx(cell, w, tw, tr, peak, kernels)
        metrics = {}
        for name, unit in cell.per_layer:
            v = read_metric(name, mctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
        device.update(busy_s=tr.mean_busy_s(), window_s=tr.window_s())
    else:
        e2e["setup_s"] = setup_s
        metrics = {name: {"value": e2e[name], "unit": unit}
                   for name, unit in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = tr.breakdown()
    result["compared"] = {k: {"value": compared[k], "limit": cell.limits[k]}
                          for k in cell.limits}
    for k, v in result["compared"].items():
        log(f"compared {k} {v['value']!r} limit {v['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)

    import jax

    from bench.peaks import UnknownDevice, peak_for

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench needs a TPU; JAX found {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips; JAX sees {len(devs)}")
        return 2
    try:
        peak = peak_for(devs[0].device_kind)
    except UnknownDevice as e:
        log(str(e))
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
