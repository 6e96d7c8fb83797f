"""Serving launcher: continuous-batching engine.

CPU-scale (the reduced smoke config, the default):
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
        --requests 6 --max-new 12

Published widths with the depth cut (``--full --layers N``), on a chip:
    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --full \\
        --layers 2 --requests 4 --max-new 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve.engine import Engine, Request


def _random_prompts(rng, cfg, n, max_new):
    return [
        Request(
            rid=i,
            prompt=rng.integers(1, cfg.vocab, size=rng.integers(3, 9)).astype(np.int32),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


def serve_single(cfg, args):
    """Serve ``args.requests`` random prompts through one ``Engine``;
    returns the engine and the completed requests."""
    # jitted init writes the weights straight into their buffers (eager
    # init stacks per-layer copies, twice the weights at the peak)
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.key(args.seed), cfg)
    eng = Engine(cfg, params, batch_slots=args.slots, max_seq=args.max_seq)

    rng = np.random.default_rng(args.seed)
    pending = _random_prompts(rng, cfg, args.requests, args.max_new)
    submitted = list(pending)
    done: list[Request] = []
    t0 = time.perf_counter()
    while pending or eng.slot_req:
        while pending and eng.free_slots:
            req = pending.pop(0)
            eng.admit(req)
            print(f"admitted rid={req.rid} prompt_len={len(req.prompt)}")
        eng.step()
        # the engine retires finished requests out of slots itself; collect
        # them once each, in completion order
        done.extend(r for r in submitted if r.done and r not in done)
    dt = time.perf_counter() - t0
    assert len(done) == len(submitted), (
        f"{len(submitted) - len(done)} requests lost by the serve loop")
    print(f"completed {len(done)}/{len(submitted)} requests: "
          f"{[ (r.rid, len(r.out)) for r in done ]}")
    print(f"engine steps: {eng.steps_run}, wall: {dt:.2f}s, "
          f"tokens: {eng.tokens_out}, tokens/s: {eng.tokens_out / max(dt, 1e-9):.1f}")
    return eng, done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="tinyllama-1.1b")
    ap.add_argument("--full", action="store_true",
                    help="published config (default: the reduced smoke config)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the config's depth to N layers (0 = keep)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if cfg.embeds_input:
        raise SystemExit("stub-frontend archs serve via decode_step directly")
    return serve_single(cfg, args)


if __name__ == "__main__":
    main()
