"""Smoke run on a TPU: the serve, train and collective paths through their
entry points, at published widths with the depth cut.

    python chip_smoke.py            # one chip: serve, train, collectives
    python chip_smoke.py --chips 4  # four chips: the MoE expert-parallel
                                    # exchange and the §3/§4 collectives

One process drives the chip(s); do not start it beside another JAX
process. For each phase it prints the wall time, the first call (compile
and run) against a steady call, the device's peak memory, and whether
the kernel-bearing steps hold a Pallas kernel (``tpu_custom_call``). Any
failed check raises. The last line is the JSON verdict. Without a TPU it
exits non-zero before any phase.

JAX's compilation cache sits where ``JAX_COMPILATION_CACHE_DIR`` says, or
else at ``<repo>/.jax_cache``; the hit and miss counts print at the end.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

# Decode-vs-forward logit bound for the serve phase (bf16 weights and
# activations). The cached decode path and the full-sequence forward are
# the same function: at smoke size in f32 on the CPU their logits differ
# by 9.5e-7 (logits up to about 3.3), a few f32 roundings. In bf16 the two
# paths may round at different points (matmuls of other shapes; the
# engine's KV cache is f32 where the forward keeps K/V in bf16); each
# such rounding moves a value by 2^-8 of its size. The logits at
# published widths are about N(0, 1), at most about 4-5, where one bf16
# step is 2^-5; the bound is 8 such steps. A wrong mask, position or
# cache slot moves logits by O(1), far outside it.
LOGIT_BOUND = 0.25

SERVE_ARGV = ["--arch", "mixtral-8x7b", "--full", "--layers", "2",
              "--slots", "4", "--requests", "4", "--max-new", "16",
              "--max-seq", "128", "--seed", "0"]
TRAIN_ARGV = ["--arch", "tinyllama-1.1b", "--layers", "2", "--batch", "8",
              "--seq", "2048", "--steps", "5", "--seed", "0"]
ROW_BYTES = 1 << 20      # collectives: 1 MiB per router row
MATMUL_BLOCK = 512       # §2 blocks, X×X
EP_TOKENS = (4, 512)     # (batch, seq) routed through the MoE EP exchange

_CACHE = {"hits": 0, "misses": 0}


def _on_event(event: str, **kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE["misses"] += 1


def _require(ok: bool, what) -> None:
    """A failed check raises (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _peak_bytes() -> list[int]:
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
            for d in jax.local_devices()]


def _report(phase: str, wall: float, first: float, steady: float,
            kernels: dict[str, list]) -> None:
    peak = _peak_bytes()
    print(f"[{phase}] wall {wall:.3f} s; first call {first:.3f} s, "
          f"steady {steady * 1e3:.3f} ms; peak_bytes_in_use "
          f"{peak[0] if len(peak) == 1 else peak}; tpu_custom_call "
          f"{kernels if kernels else 'none expected'}", flush=True)


def _timed(fn, *args):
    """(result, first-call seconds, steady seconds of a second call)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return out, first, time.perf_counter() - t0


def _kernel_calls(fn, *args) -> list[str]:
    """The Pallas kernels (``tpu_custom_call`` instructions) in the
    compiled program of ``fn`` on ``args``, by instruction name. Compiles
    again; the compilation cache serves it."""
    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    return [line.split("=", 1)[0].strip() for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


# ---------------------------------------------------------------- serve
def decode_vs_forward(eng, prompt) -> float:
    """Max |logit difference| between the engine's cached decode step fed
    ``prompt`` token by token (every slot the same prompt) and the
    full-sequence forward on the same parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    cfg, params = eng.cfg, eng.params
    cache = M.init_cache(cfg, eng.slots, eng.max_seq, dtype=jnp.float32)
    step, dec = _decode_step(cfg), []
    for pos, tok in enumerate(prompt):
        logits, cache = step(
            params, cache, {"token": jnp.full((eng.slots,), tok, jnp.int32)},
            jnp.full((eng.slots,), pos, jnp.int32))
        dec.append(np.asarray(logits[0], np.float32))
    forward = jax.jit(lambda p, t: M.forward_train(
        p, {"tokens": t}, cfg, use_kernel=False, remat=False)[0])
    full = forward(params, jnp.asarray(prompt, jnp.int32)[None])
    return float(np.abs(np.stack(dec) - np.asarray(full[0], np.float32)).max())


def _decode_step(cfg):
    """A fresh jit of the engine's decode step: its first call compiles."""
    import jax

    from repro.models import model as M

    return jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, cfg))


def serve_phase(argv=SERVE_ARGV) -> dict:
    import jax.numpy as jnp

    from repro.launch import serve

    t0 = time.perf_counter()
    eng, done = serve.main(argv)
    wall = time.perf_counter() - t0
    n_req = int(argv[argv.index("--requests") + 1])
    max_new = int(argv[argv.index("--max-new") + 1])
    _require(len(done) == n_req and all(
        r.done and len(r.out) == max_new for r in done), done)
    print(f"[serve] {len(done)}/{n_req} requests completed, "
          f"{max_new} tokens each, {eng.steps_run} engine steps")
    _, first, steady = _timed(_decode_step(eng.cfg), eng.params, eng.cache,
                              {"token": jnp.asarray(eng.pending_tok)},
                              jnp.asarray(eng.positions))
    _report("serve", wall, first, steady, {})
    diff = decode_vs_forward(eng, done[0].prompt)
    print(f"[serve] decode vs forward: max |logit diff| {diff:.6e} "
          f"(bound {LOGIT_BOUND})")
    return {"logit_diff": diff}


# ---------------------------------------------------------------- train
def train_phase(argv=TRAIN_ARGV) -> dict:
    import numpy as np

    from repro.launch import train

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        args = train.parse_args(argv + ["--ckpt-dir", ckpt_dir])
        t0 = time.perf_counter()
        run = train.train_loop(args)
        wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(ckpt_dir)
    finite = bool(np.all(np.isfinite(run.losses))
                  and np.all(np.isfinite(run.grad_norms)))
    print(f"[train] {len(run.losses)} steps, losses {run.losses}, "
          f"grad norms {run.grad_norms}, all finite: {finite}")
    kernels = {"flash_attention": _kernel_calls(
        run.step_fn, run.params, run.opt_state, run.batch)}
    _report("train", wall, run.durations[0],
            statistics.median(run.durations[1:]), kernels)
    return {"finite": finite, "steps": len(run.losses), "kernels": kernels}


# ---------------------------------------------------------- collectives
def collectives_phase(row_bytes=ROW_BYTES, block=MATMUL_BLOCK) -> dict:
    """The four programs replayed on one device: fused tables on
    ``jax_ppermute`` and the ``pallas_fused`` kernels, each against the
    NumPy ``reference`` backend bit for bit."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.topology import D3
    from repro.dist import collectives as coll
    from repro.dist.mesh import DeviceLayout
    from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend
    from repro.runtime.backends.pallas_fused import PallasFusedBackend
    from repro.runtime.backends.reference import NumpyReferenceBackend

    layout = DeviceLayout(D3(2, 2))
    n, F = layout.n, row_bytes // 4
    rng = np.random.default_rng(0)
    ref = NumpyReferenceBackend()
    backends = {"jax_ppermute": JaxPpermuteBackend(),
                "pallas_fused": PallasFusedBackend()}
    cases = {
        "alltoall": (coll.alltoall_program(layout, optimized=True),
                     rng.standard_normal((n, n, F // n)).astype(np.float32)),
        "allreduce": (coll.allreduce_program(layout, optimized=True),
                      rng.standard_normal((n, F)).astype(np.float32)),
        "broadcast": (coll.broadcast_program(layout, 0, optimized=True),
                      rng.standard_normal((n, F)).astype(np.float32)),
    }
    t_phase = time.perf_counter()
    exact, kernels, firsts, steadies = {}, {}, [], []
    for kind, (prog, x) in cases.items():
        want = getattr(ref, f"run_{kind}")(x, prog)
        xj = jnp.asarray(x)
        for name, be in backends.items():
            run = getattr(be, f"run_{kind}")
            got, first, steady = _timed(lambda v: run(v, prog), xj)
            exact[f"{kind}/{name}"] = bool(np.array_equal(np.asarray(got), want))
            firsts.append(first)
            steadies.append(steady)
            print(f"[collectives] {kind:9s} {name:12s} bit-exact "
                  f"{exact[f'{kind}/{name}']}; first {first:.3f} s, "
                  f"steady {steady * 1e3:.3f} ms")
    kernels["pallas_fused allreduce"] = _kernel_calls(
        lambda v: backends["pallas_fused"].run_allreduce(v, cases["allreduce"][0]),
        jnp.asarray(cases["allreduce"][1]))

    # §2: integer-valued blocks keep the MXU's products and sums exact
    prog = coll.matmul_program(2, 2, optimized=True)
    side = prog.program.grid[0] * prog.program.grid[1] * block
    B = rng.integers(-4, 5, (side, side)).astype(np.float32)
    A = rng.integers(-4, 5, (side, side)).astype(np.float32)
    want = ref.run_matmul(B, A, prog)
    pal = backends["pallas_fused"]
    Bj, Aj = jnp.asarray(B), jnp.asarray(A)
    got, first, steady = _timed(lambda b, a: pal.run_matmul(b, a, prog), Bj, Aj)
    exact["matmul/pallas_fused"] = bool(np.array_equal(np.asarray(got), want)
                                        and np.array_equal(want, B @ A))
    firsts.append(first)
    steadies.append(steady)
    print(f"[collectives] matmul    pallas_fused bit-exact "
          f"{exact['matmul/pallas_fused']}; {side}x{side}, blocks "
          f"{block}x{block}; first {first:.3f} s, steady {steady * 1e3:.3f} ms")
    kernels["pallas_fused matmul"] = _kernel_calls(
        lambda b, a: pal.run_matmul(b, a, prog), Bj, Aj)
    _report("collectives", time.perf_counter() - t_phase, sum(firsts),
            sum(steadies), kernels)
    return {"exact": exact, "kernels": kernels}


# ------------------------------------------------------ four chips
def ep_phase(full: bool = True, tokens=EP_TOKENS) -> dict:
    """``moe_apply_ep`` on a (data 1, model 4) mesh under each
    ``moe_collectives`` path: the sequential paths bit for bit, the
    overlapped-fused one within ``overlap_fused_atol``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config, get_smoke_config
    from repro.dist import sharding as SH
    from repro.models import moe as MOE

    cfg = (get_config if full else get_smoke_config)("mixtral-8x7b")
    dtype = jnp.dtype(cfg.param_dtype)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    experts = NamedSharding(mesh, P("model"))
    shardings = {"router": NamedSharding(mesh, P()), "w_in": experts,
                 "w_gate": experts, "w_out": experts}
    params = jax.jit(lambda k: MOE.moe_init(k, cfg, dtype),
                     out_shardings=shardings)(jax.random.key(0))
    B, S = tokens
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), (B, S, cfg.d_model), dtype),
        NamedSharding(mesh, P()))
    base = SH.ShardRules(model_axis_size=4, data_axis_size=1)
    t_phase = time.perf_counter()
    outs, firsts, steadies = {}, [], []
    try:
        for mode in ("xla", "dragonfly", "dragonfly_overlap",
                     "dragonfly_overlap_fused"):
            SH.set_active(dataclasses.replace(base, moe_collectives=mode), mesh)
            fn = jax.jit(lambda p, v: MOE.moe_apply_ep(p, v, cfg)[0])
            y, first, steady = _timed(fn, params, x)
            outs[mode] = np.asarray(y.astype(jnp.float32))
            firsts.append(first)
            steadies.append(steady)
            print(f"[ep] {mode:24s} first {first:.3f} s, steady "
                  f"{steady * 1e3:.3f} ms")
    finally:
        SH.clear_active()
    ref = outs["xla"]
    exact = {m: bool(np.array_equal(outs[m], ref))
             for m in ("dragonfly", "dragonfly_overlap")}
    diff = float(np.abs(outs["dragonfly_overlap_fused"] - ref).max())
    atol = MOE.overlap_fused_atol(np.asarray(ref).astype(dtype))
    print(f"[ep] E={cfg.moe.num_experts} (2 per chip), d={cfg.d_model}, "
          f"f={cfg.moe.d_ff_expert}, {B}x{S} tokens {dtype}; bit-exact vs "
          f"xla {exact}; overlap_fused max |diff| {diff:.6e} <= {atol:.6e}")
    _report("ep", time.perf_counter() - t_phase, sum(firsts), sum(steadies), {})
    return {"exact": exact, "fused_ok": diff <= atol}


def mesh_collectives_phase(row_bytes=ROW_BYTES) -> dict:
    """§3 all-to-all and §4 all-reduce on the 4-device axis through
    ``jax_ppermute`` against ``lax.all_to_all``/``lax.psum``, and the §4
    remote-DMA ring (``pallas_fused.allreduce_shard``). Integer-valued
    data keeps every summation order exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.dist import collectives as coll
    from repro.dist.mesh import dragonfly_layout
    from repro.runtime.backends.pallas_fused import PallasFusedBackend

    n, F = 4, row_bytes // 4
    layout = dragonfly_layout(n)
    mesh = Mesh(np.array(jax.devices()[:n]), ("df",))
    rng = np.random.default_rng(0)
    x_a2a = jnp.asarray(rng.integers(-8, 9, (n * n, F // n)).astype(np.float32))
    x_ar = jnp.asarray(rng.integers(-8, 9, (n, F)).astype(np.float32))
    pal = PallasFusedBackend()

    def shard(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("df"),
                                     out_specs=P("df"), check_vma=False))

    fns = {
        "alltoall/dragonfly": (shard(lambda s: coll.dragonfly_all_to_all(s, "df", layout)), x_a2a),
        "alltoall/xla": (shard(lambda s: coll.xla_all_to_all(s, "df")), x_a2a),
        "allreduce/dragonfly": (shard(lambda s: coll.dragonfly_all_reduce(s, "df", layout)), x_ar),
        "allreduce/rdma_ring": (shard(lambda s: pal.allreduce_shard(
            s, "df", coll.allreduce_program(layout))), x_ar),
        "allreduce/psum": (shard(lambda s: jax.lax.psum(s, "df")), x_ar),
    }
    t_phase = time.perf_counter()
    outs, firsts, steadies = {}, [], []
    for name, (fn, x) in fns.items():
        y, first, steady = _timed(fn, x)
        outs[name] = np.asarray(y)
        firsts.append(first)
        steadies.append(steady)
        print(f"[mesh] {name:20s} first {first:.3f} s, steady "
              f"{steady * 1e3:.3f} ms")
    exact = {
        "alltoall dragonfly == lax.all_to_all": bool(np.array_equal(
            outs["alltoall/dragonfly"], outs["alltoall/xla"])),
        "allreduce dragonfly == lax.psum": bool(np.array_equal(
            outs["allreduce/dragonfly"], outs["allreduce/psum"])),
        "allreduce rdma_ring == lax.psum": bool(np.array_equal(
            outs["allreduce/rdma_ring"], outs["allreduce/psum"])),
    }
    print(f"[mesh] D3({layout.topo.K},{layout.topo.M}) over {n} chips, "
          f"{row_bytes} B per device; {exact}")
    kernels = {"rdma_ring": _kernel_calls(fns["allreduce/rdma_ring"][0], x_ar)}
    _report("mesh", time.perf_counter() - t_phase, sum(firsts),
            sum(steadies), kernels)
    return {"exact": exact, "kernels": kernels}


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve, train, collectives on one chip; "
                         "4: only the cross-chip paths")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {dev.platform!r}")
    if len(jax.devices()) < args.chips:
        raise SystemExit(f"--chips {args.chips}: JAX sees "
                         f"{len(jax.devices())} device(s)")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_listener(_on_event)
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compilation cache {cache_dir}", flush=True)

    if args.chips == 1:
        serve = serve_phase()
        _require(serve["logit_diff"] <= LOGIT_BOUND, serve)
        gc.collect()  # the engine's jitted step closes over the engine
        train = train_phase()
        _require(train["finite"] and train["steps"] == 5, train)
        _require(train["kernels"]["flash_attention"], train)
        gc.collect()
        coll = collectives_phase()
        _require(all(coll["exact"].values()), coll)
        _require(coll["kernels"]["pallas_fused allreduce"], coll)
        # the §2 replay holds the table kernel and the block_matmul kernel
        matmul = coll["kernels"]["pallas_fused matmul"]
        _require(len(matmul) >= 2 and any("block_matmul" in k for k in matmul),
                 coll)
    else:
        ep = ep_phase()
        _require(all(ep["exact"].values()) and ep["fused_ok"], ep)
        mesh = mesh_collectives_phase()
        _require(all(mesh["exact"].values()), mesh)
        _require(mesh["kernels"]["rdma_ring"], mesh)
    print(f"compilation cache: {_CACHE['hits']} hits, "
          f"{_CACHE['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
