"""Sweep the offered rate of an open-loop serving cell to find its knee,
the highest rate the system sustains. The benchmark's runs never run
this: it is run on the chip when a cell is defined, and the cell's mix
then fixes its ``rate_per_s`` at about four fifths of the knee.

    python bench/knee.py --workload <name> --seed <n> --seconds <s> --rates 1,2,3

One JSON line per rate: requests whose first token fell in the window
per second against the rate offered, the queue left at the end, the
tails, and the engine steps per second.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np


def point(w: dict, rate: float) -> dict:
    return {"rate_per_s": rate, "first_tokens_per_s": len(w["ttft"]) / w["seconds"],
            "queued_at_end": w["queued"],
            "ttft_p50_ms": 1e3 * float(np.percentile(w["ttft"], 50)),
            "ttft_p90_ms": 1e3 * float(np.percentile(w["ttft"], 90)),
            "itl_p95_ms": 1e3 * float(np.percentile(w["itl"], 95)),
            "steps_per_s": w["steps"] / w["seconds"],
            "prefill_step_share": w["prefill_steps"] / max(w["steps"], 1),
            "top_slot": w["top_slot"]}


def sweep(root: pathlib.Path, workload: str, seed: int, seconds: float, rates):
    """One set-up, then each rate in turn on the same engine: the requests
    in flight finish and the schedule starts anew at the next rate."""
    from bench import run as R

    loop = R.make_loop(R.make_ctx(R.load_cell(root, workload), seed))
    loop.setup()
    for rate in rates:
        loop.restart(rate)
        yield point(loop.run(seconds), rate)
    loop.release()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rates = [float(r) for r in args.rates.split(",")]
    for pt in sweep(root, args.workload, args.seed, args.seconds, rates):
        print(json.dumps(pt), flush=True)
    return 0


if __name__ == "__main__":
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root / "src"), str(_root)]
    sys.exit(main())
