"""Readings that set a cell's correctness limits, on the chip at the
cell's own size: the program's numbers, the lower-precision control's
(the float32 reference computed in float8 in the program's place), and
each planted fault's, for several seeds in one process.

    python bench/controls.py --workload <name> --seeds 1,2,3 --seconds <s>

Each seed: set up, run the timed path for ``--seconds`` (long enough to
finish the mix's longest requests), free the program's state, then
compare; the control and the faults on the first ``--control-seeds``
seeds (all by default). One JSON line per seed. The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys


def readings(root: pathlib.Path, workload: str, seed: int, seconds: float,
             control: bool = True) -> dict:
    from bench import run as R

    cell = R.load_cell(root, workload)
    ctx = R.make_ctx(cell, seed)
    loop = R.make_loop(ctx)
    loop.setup()
    w = loop.run(seconds)
    R.log(f"seed {seed}: {loop.describe(w)}")
    loop.release()
    gc.collect()
    out = {"seed": seed, "program": loop.check()}
    if control:
        out["control"] = loop.check(quant="fp8")
        if hasattr(loop, "faults"):
            out["faults"] = loop.faults()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control and the faults on the first N seeds only")
    args = ap.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    n_control = len(seeds) if args.control_seeds is None else args.control_seeds
    for i, seed in enumerate(seeds):
        print(json.dumps(readings(root, args.workload, seed, args.seconds, i < n_control)),
              flush=True)
    return 0


if __name__ == "__main__":
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root / "src"), str(_root)]
    sys.exit(main())
