"""Benchmark harness — one module per paper table/figure + timed micro-
benchmarks of the runtime layers. Prints ``name,...`` CSV-ish lines;
``--json BENCH_<date>.json`` additionally writes machine-readable records
({name, params, us_per_call?, rounds?}) so the perf trajectory is tracked
across PRs.

    PYTHONPATH=src python -m benchmarks.run [--json BENCH_2026-07-30.json]

``--compare OLD.json NEW.json`` diffs two such trajectories instead of
benchmarking: shared records whose us_per_call grew beyond ``--tolerance``
(default 0.5 = +50%, CPU CI timings are noisy) print as REGRESSION lines.
Warn-only by default; ``--strict`` exits 1 when regressions exist, and
``--strict-families autotuner,optimizer`` promotes just those record-name
prefixes to CI-failing while the rest stay warn-only (what the CI bench
job runs against the committed baseline).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _timed(fn, *args, warmup=1, iters=3, **kw):
    for _ in range(warmup):
        fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / iters
    return out, dt * 1e6


def bench_schedule_lowering(log=print):
    """IR -> mesh lowering throughput: emit the §3 Schedule and lower it to
    device permutations (the control-plane cost the executor pays once per
    layout, then caches)."""
    from repro.core.alltoall import schedule
    from repro.dist.mesh import dragonfly_layout
    from repro.runtime.lowering import lower_alltoall

    for n in (16, 64):
        layout = dragonfly_layout(n)
        p = layout.da_params
        low, us = _timed(lambda: lower_alltoall(schedule(p, layout.topo)))
        log(
            f"schedule_lowering,n={n},K={p.K},M={p.M},s={p.s},"
            f"rounds={p.total_rounds},permutes={low.num_permutes},us_per_call={us:.0f}"
        )


def bench_backends(log=print):
    """Backend comparison on the SAME lowered programs: the §3 all-to-all
    replayed by the dragonfly jax_ppermute backend vs the fused XLA op vs
    the pure-NumPy reference backend, and the §2 ``matmul_program`` vs its
    oracles. Device-backed rows appear when the process has ≥16 host
    devices (CI forces XLA_FLAGS=--xla_force_host_platform_device_count=16);
    otherwise they are recorded as skipped so the JSON trajectory stays
    comparable across environments."""
    import jax
    import jax.numpy as jnp

    from repro.core import alltoall as a2a
    from repro.core import matmul as mm
    from repro.core.matmul import gather_blocks, scatter_blocks
    from repro.dist.mesh import dragonfly_layout
    from repro.runtime import lowering
    from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend
    from repro.runtime.backends.reference import NumpyReferenceBackend

    n = 16
    ref = NumpyReferenceBackend()
    layout = dragonfly_layout(n)
    prog = lowering.lower(a2a.schedule(layout.da_params, layout.topo))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n, 64)).astype(np.float32)
    _, us = _timed(lambda: ref.run_alltoall(x, prog))
    log(f"backend_alltoall,backend=reference,n={n},rounds={prog.num_rounds},us_per_call={us:.0f}")

    g = mm.MatmulGrid(2, 2)
    mprog = lowering.lower(mm.schedule(g))
    X = 16
    side = g.n * X
    B = rng.integers(-4, 5, (side, side)).astype(np.float32)
    A = rng.integers(-4, 5, (side, side)).astype(np.float32)
    _, us = _timed(lambda: ref.run_matmul(B, A, mprog))
    log(f"matmul_program,backend=reference,grid=2x2,X={X},rounds={mprog.num_rounds},us_per_call={us:.0f}")
    _, us = _timed(lambda: B @ A)
    log(f"matmul_program,backend=numpy_oracle,grid=2x2,X={X},us_per_call={us:.0f}")

    # pallas_fused backend: global fused replay + interpret-mode kernels on
    # CPU hosts (compiled kernels + RDMA ring on TPU) — no mesh needed
    from repro.runtime.backends.pallas_fused import PallasFusedBackend

    pal = PallasFusedBackend()
    _, us = _timed(lambda: np.asarray(pal.run_alltoall(x, prog)))
    log(f"backend_alltoall,backend=pallas_fused,n={n},rounds={prog.num_rounds},us_per_call={us:.0f}")
    from repro.core import hypercube as hc

    sbh_prog = lowering.lower(hc.allreduce_schedule(layout.sbh))
    xr = rng.standard_normal((n, 64)).astype(np.float32)
    _, us = _timed(lambda: np.asarray(pal.run_allreduce(xr, sbh_prog)))
    log(f"backend_allreduce,backend=pallas_fused,n={n},rounds={sbh_prog.num_rounds},us_per_call={us:.0f}")
    out, us = _timed(lambda: np.asarray(pal.run_matmul(B, A, mprog)))
    np.testing.assert_array_equal(out, B @ A)
    log(f"matmul_program,backend=pallas_fused,grid=2x2,X={X},rounds={mprog.num_rounds},us_per_call={us:.0f}")

    if jax.device_count() < n:
        log(f"backend_alltoall,backend=dragonfly,n={n},skipped=need_{n}_devices")
        log(f"matmul_program,backend=dragonfly,grid=2x2,skipped=need_{n}_devices")
        return
    from jax.sharding import Mesh, PartitionSpec as P

    jaxbe = JaxPpermuteBackend()
    mesh = Mesh(np.array(jax.devices()[:n]), ("df",))
    xj = jnp.asarray(x)
    run_df = jax.jit(jax.shard_map(
        lambda s: jaxbe.alltoall(s[0], "df", prog)[None],
        mesh=mesh, in_specs=P("df"), out_specs=P("df")))
    run_xla = jax.jit(jax.shard_map(
        lambda s: jax.lax.all_to_all(s[0], "df", split_axis=0, concat_axis=0)[None],
        mesh=mesh, in_specs=P("df"), out_specs=P("df")))
    _, us = _timed(lambda: run_df(xj).block_until_ready())
    log(f"backend_alltoall,backend=dragonfly,n={n},rounds={prog.num_rounds},us_per_call={us:.0f}")
    _, us = _timed(lambda: run_xla(xj).block_until_ready())
    log(f"backend_alltoall,backend=fused_xla,n={n},us_per_call={us:.0f}")

    bb = jnp.asarray(scatter_blocks(g, B))
    aa = jnp.asarray(scatter_blocks(g, A))
    run_mm = jax.jit(jax.shard_map(
        lambda p, q: jaxbe.matmul(p[0], q[0], "df", mprog)[None],
        mesh=mesh, in_specs=(P("df"), P("df")), out_specs=P("df")))
    out, us = _timed(lambda: run_mm(bb, aa).block_until_ready())
    np.testing.assert_array_equal(gather_blocks(g, np.asarray(out)), B @ A)
    log(f"matmul_program,backend=dragonfly,grid=2x2,X={X},rounds={mprog.num_rounds},us_per_call={us:.0f}")


def bench_optimizer(log=print):
    """The optimizer pass vs the per-stage replay loop on the SAME lowered
    programs (§3 all-to-all n=16 and the §2 grid-(2,2) matmul):

      * ``ref_loop`` / ``ref_fused``   — host (reference backend) replay:
        per-stage advanced indexing vs one batched table op per group;
      * ``trace_compile_loop`` / ``trace_compile_fused`` — cold jit
        ``lower().compile()`` wall time of the device replay: the per-stage
        loop unrolls one collective chain per stage into the HLO, the fused
        path is one batched scatter / one lax.scan body regardless of
        program length (this is the cost bench_emulation_rewrite showed
        dominating);
      * ``replay_loop`` / ``replay_fused`` — steady-state device replay.

    Loop rows need a 16-device mesh (CI forces it); fused rows replay the
    global array and run anywhere.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import alltoall as a2a
    from repro.core import matmul as mm
    from repro.dist.mesh import dragonfly_layout
    from repro.runtime import lowering
    from repro.runtime import optimize as ropt
    from repro.runtime.backends.jax_ppermute import (
        JaxPpermuteBackend,
        _compiled_collective,
        _compiled_matmul,
    )
    from repro.runtime.backends.reference import NumpyReferenceBackend

    n = 16
    ref = NumpyReferenceBackend()
    jaxbe = JaxPpermuteBackend()
    layout = dragonfly_layout(n)
    prog = lowering.lower(a2a.schedule(layout.da_params, layout.topo))
    o = ropt.optimize(prog)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n, 64)).astype(np.float32)

    _, us = _timed(lambda: ref.run_alltoall(x, prog))
    log(f"optimizer,path=ref_loop,kind=alltoall,n={n},stages={prog.num_permutes},us_per_call={us:.0f}")
    _, us = _timed(lambda: ref.run_alltoall(x, o))
    log(f"optimizer,path=ref_fused,kind=alltoall,n={n},fused_ops={o.num_fused_ops},us_per_call={us:.0f}")

    g = mm.MatmulGrid(2, 2)
    mprog = lowering.lower(mm.schedule(g))
    mo = ropt.optimize(mprog)
    X = 16
    side = g.n * X
    B = rng.integers(-4, 5, (side, side)).astype(np.float32)
    A = rng.integers(-4, 5, (side, side)).astype(np.float32)
    _, us = _timed(lambda: ref.run_matmul(B, A, mprog))
    log(f"optimizer,path=ref_loop,kind=matmul,grid=2x2,X={X},us_per_call={us:.0f}")
    _, us = _timed(lambda: ref.run_matmul(B, A, mo))
    log(f"optimizer,path=ref_fused,kind=matmul,grid=2x2,X={X},us_per_call={us:.0f}")

    # cold trace+compile: __wrapped__ bypasses the closure caches so every
    # call re-traces and re-compiles from scratch
    xj = jnp.asarray(x)
    _, us = _timed(
        lambda: ropt.jax_alltoall.__wrapped__(o).lower(xj).compile(),
        warmup=0, iters=2)
    log(f"optimizer,path=trace_compile_fused,kind=alltoall,n={n},us_per_call={us:.0f}")
    _, us = _timed(
        lambda: jax.jit(ropt.build_jax_matmul(mo)).lower(
            jnp.zeros((mprog.n, X, X), jnp.float32),
            jnp.zeros((mprog.n, X, X), jnp.float32)).compile(),
        warmup=0, iters=2)
    log(f"optimizer,path=trace_compile_fused,kind=matmul,grid=2x2,us_per_call={us:.0f}")
    _, us = _timed(lambda: ropt.jax_alltoall(o)(xj).block_until_ready())
    log(f"optimizer,path=replay_fused,kind=alltoall,n={n},us_per_call={us:.0f}")

    if jax.device_count() < n:
        log(f"optimizer,path=trace_compile_loop,kind=alltoall,n={n},skipped=need_{n}_devices")
        log(f"optimizer,path=trace_compile_loop,kind=matmul,grid=2x2,skipped=need_{n}_devices")
        return
    _, us = _timed(
        lambda: _compiled_collective.__wrapped__(
            jaxbe, prog, "alltoall", "df", None, False).lower(xj).compile(),
        warmup=0, iters=2)
    log(f"optimizer,path=trace_compile_loop,kind=alltoall,n={n},us_per_call={us:.0f}")
    _, us = _timed(
        lambda: _compiled_matmul.__wrapped__(jaxbe, mprog, "df", None).lower(B, A).compile(),
        warmup=0, iters=2)
    log(f"optimizer,path=trace_compile_loop,kind=matmul,grid=2x2,us_per_call={us:.0f}")
    _, us = _timed(lambda: jaxbe.run_alltoall(xj, prog).block_until_ready())
    log(f"optimizer,path=replay_loop,kind=alltoall,n={n},us_per_call={us:.0f}")


def bench_emulation_rewrite(log=print):
    """Guest-on-host rewrite overhead (the elastic-failover hot path):

      * ``native_lowering``  — derive + lower the guest schedule from
        scratch (what recovery used to do);
      * ``rewrite_cold``     — relabel the already-lowered guest program
        through the embedding (what recovery does now), cache cleared;
      * ``rewrite_cached``   — the same call hitting the lru cache (what
        repeated failovers onto one survivor set pay);
      * ``replay_overhead``  — reference-backend replay of the rewritten
        host-sized program vs the native guest program (idle devices cost).
    """
    from repro.core import alltoall as a2a
    from repro.core.topology import D3
    from repro.dist.mesh import DeviceLayout
    from repro.runtime import lowering, rewrite
    from repro.runtime.backends.reference import NumpyReferenceBackend

    ref = NumpyReferenceBackend()
    for (J, L), (K, M) in (((2, 2), (4, 4)), ((4, 4), (4, 8))):
        guest = DeviceLayout(D3(J, L))
        emb = guest.embed_onto(DeviceLayout(D3(K, M)))
        tag = f"guest={J}x{L},host={K}x{M}"

        _, us = _timed(lambda: lowering.lower(a2a.schedule(guest.da_params, guest.topo)))
        log(f"emulation_rewrite,path=native_lowering,{tag},us_per_call={us:.0f}")

        prog = lowering.lower(a2a.schedule(guest.da_params, guest.topo))

        def cold():
            rewrite.emulate.cache_clear()
            return rewrite.emulate(prog, emb)

        hprog, us = _timed(cold)
        log(f"emulation_rewrite,path=rewrite_cold,{tag},"
            f"stages={hprog.num_permutes},us_per_call={us:.0f}")
        _, us = _timed(lambda: rewrite.emulate(prog, emb))
        log(f"emulation_rewrite,path=rewrite_cached,{tag},us_per_call={us:.0f}")

        rng = np.random.default_rng(0)
        xg = rng.standard_normal((prog.n, prog.n, 8)).astype(np.float32)
        xh = rewrite.scatter_guest(xg, hprog, axes=(0, 1))
        _, us = _timed(lambda: ref.run_alltoall(xg, prog))
        log(f"emulation_rewrite,path=replay_native,{tag},us_per_call={us:.0f}")
        _, us = _timed(lambda: ref.run_alltoall(xh, hprog))
        log(f"emulation_rewrite,path=replay_rewritten,{tag},us_per_call={us:.0f}")


def bench_concurrent_guests(log=print):
    """Multi-tenant makespan: two disjoint D3(2,2) guests on one D3(4,4)
    host (``runtime.combine``) vs time-multiplexing them.

      * ``solo_sum`` — the host without a combinator: replay each guest's
        rewritten program in turn (Σ T_i rounds, two replays);
      * ``combined`` — ONE replay of the combined program (max T_i rounds;
        same-stamp perms packed into single partial permutations);
      * ``combined_fused`` — the combined program through ``optimize()``
        (the stacked-σ table now spans both guests).

    Bit-exactness of combined vs solo per guest is asserted in-line, so a
    regression shows up here as a failure rather than a fast wrong row.
    """
    from repro.core.emulation import disjoint_embeddings
    from repro.core.topology import D3
    from repro.dist import collectives as coll
    from repro.dist.mesh import DeviceLayout
    from repro.runtime import combine as cmb
    from repro.runtime.backends.reference import NumpyReferenceBackend

    ref = NumpyReferenceBackend()
    host = D3(4, 4)
    embs = disjoint_embeddings(host, [(2, 2), (2, 2)])
    guest = DeviceLayout(D3(2, 2))
    solos = [coll.alltoall_program(guest, e) for e in embs]
    comb = coll.concurrent_program("alltoall", tuple(embs))
    tag = "guests=2,guest=2x2,host=4x4"

    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((guest.n, guest.n, 16)).astype(np.float32)
          for _ in embs]
    hosts_solo = [cmb.scatter_guests([x], [e], axes=(0, 1))
                  for x, e in zip(xs, embs)]
    xh = cmb.scatter_guests(xs, embs, axes=(0, 1))

    def solo_sum():
        return [ref.run_alltoall(h, p) for h, p in zip(hosts_solo, solos)]

    outs, us = _timed(solo_sum)
    rounds_sum = sum(p.num_rounds for p in solos)
    log(f"concurrent_guests,path=solo_sum,{tag},rounds={rounds_sum},us_per_call={us:.0f}")

    out, us = _timed(lambda: ref.run_alltoall(xh, comb))
    log(f"concurrent_guests,path=combined,{tag},rounds={comb.num_rounds},us_per_call={us:.0f}")
    assert comb.num_rounds < rounds_sum  # the makespan win, in rounds
    for gi, (e, solo_out) in enumerate(zip(embs, outs)):
        np.testing.assert_array_equal(
            cmb.extract_guest(out, e, axes=(0, 1)),
            cmb.extract_guest(solo_out, e, axes=(0, 1)),
        )

    from repro.runtime.optimize import optimize

    opt = optimize(comb)
    fused, us = _timed(lambda: ref.run_alltoall(xh, opt))
    np.testing.assert_array_equal(fused, out)
    log(f"concurrent_guests,path=combined_fused,{tag},rounds={comb.num_rounds},"
        f"fused_ops={opt.num_fused_ops},us_per_call={us:.0f}")


def bench_core_micro(log=print):
    """Schedule-generation throughput (rounds/s) — the control-plane cost
    of the paper's algorithms at pod scale (D3(4,8) = 256 chips)."""
    from repro.core.alltoall import DAParams, rounds
    from repro.core.broadcast import m_broadcast
    from repro.core.topology import D3

    p = DAParams(4, 8, 4)
    _, us = _timed(lambda: sum(1 for _ in rounds(p)))
    log(f"micro_a2a_schedule,K=4,M=8,s=4,rounds={p.total_rounds},us_per_call={us:.0f}")

    t = D3(4, 8)
    _, us = _timed(lambda: m_broadcast(t, (0, 0, 0)))
    log(f"micro_m_broadcast_schedule,K=4,M=8,us_per_call={us:.0f}")


def bench_kernels(log=print):
    """Pallas kernels (interpret) + the XLA flash path, vs oracles."""
    import jax.numpy as jnp
    from repro.kernels.block_matmul.block_matmul import block_matmul
    from repro.kernels.flash_attention.xla_flash import flash_attention_xla

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    out, us = _timed(
        lambda: block_matmul(a, b, bm=128, bn=128, bk=128, interpret=True).block_until_ready()
    )
    log(f"kernel_block_matmul_interp,shape=256x256x256,us_per_call={us:.0f}")

    q = jnp.asarray(rng.standard_normal((2, 4, 512, 64)), jnp.float32)
    out, us = _timed(
        lambda: flash_attention_xla(q, q, q, causal=True).block_until_ready()
    )
    log(f"kernel_flash_xla,shape=(2,4,512,64),us_per_call={us:.0f}")


def bench_train_smoke(log=print):
    """End-to-end train-step latency on the CPU-scale config (the
    framework's hot loop: loss+grads+AdamW, jitted)."""
    import jax
    from repro.configs import get_smoke_config
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import TrainSettings, make_train_step, init_train_state
    from repro.train.data import DataState, SyntheticLM

    cfg = get_smoke_config("tinyllama-1.1b")
    opt = OptConfig(total_steps=100)
    settings = TrainSettings(use_kernel=False, remat=False)
    params, opt_state = init_train_state(jax.random.key(0), cfg, opt, settings)
    step = jax.jit(make_train_step(cfg, opt, settings))
    data = SyntheticLM(DataState(seed=0, batch=4, seq=32, vocab=cfg.vocab))
    batch = {k: jax.numpy.asarray(v) for k, v in data.next_batch().items()}
    params, opt_state, metrics = step(params, opt_state, batch)  # compile

    def one():
        p, o, m = step(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        return m

    m, us = _timed(one)
    log(f"train_step_smoke,arch=tinyllama-smoke,B=4,S=32,us_per_call={us:.0f},loss={float(m['loss']):.3f}")


def bench_autotuner(log=print):
    """Price-driven autotuner (runtime/autotune.py): the decision table the
    tuner produces for a spread of call-site keys, plus fresh per-strategy
    timings with their measured-vs-analytic error.

    Rows:
      * ``autotuner_decision`` — one per key: chosen strategy, decision
        source (measured / cache / analytic), the schedule's priced rounds
        and hops, predicted µs;
      * ``autotuner_strategy`` — one per runnable candidate: fresh measured
        µs, the analytic seed price, and err_ratio = measured / analytic
        (how well the seed model ranks without calibration).

    The acceptance bound is asserted in-line: the chosen strategy's fresh
    timing is never slower than the worst fixed candidate (with 10% timer
    slack), so a mis-ranking tuner fails the bench instead of logging a
    plausible-looking row. Decisions use the default on-disk cache
    (benchmarks/autotune_cache.json) — the CI artifact next to the BENCH
    trajectory."""
    from repro.runtime import autotune as at

    tuner = at.Autotuner()
    sites = [
        ("alltoall", 16, 256, "host", None),
        ("alltoall", 16, 256, "global", None),
        ("allreduce", 16, 256, "global", None),
        ("broadcast", 16, 256, "global", None),
        ("alltoall", 16, 256, "shard", None),
        ("alltoall", 16, 1 << 16, "global", None),  # large messages rerank
        ("matmul", 16, 16 * 16 * 4, "global", (2, 2)),
    ]
    for kind, n, nbytes, site, grid in sites:
        layout = at.layout_for(n)
        dec = tuner.decide(kind, layout, nbytes, site=site, grid=grid)
        log(
            f"autotuner_decision,kind={kind},site={site},n={n},b={dec.key.nbytes},"
            f"strategy={dec.strategy},source={dec.source},rounds={dec.rounds},"
            f"hops={dec.hops:.0f},us_per_call={dec.predicted_us:.0f}"
        )
        times: dict[str, float] = {}
        for s in at.candidates(kind, site):
            fn = at._measure_closure(kind, site, s, layout, grid,
                                     dec.key.nbytes, dec.key.dtype)
            if fn is None:
                log(f"autotuner_strategy,kind={kind},site={site},n={n},"
                    f"b={dec.key.nbytes},strategy={s},skipped=unrunnable_here")
                continue
            us = at._time_us(fn)
            times[s] = us
            err = us / max(dec.analytic_us.get(s, us), 1e-9)
            log(
                f"autotuner_strategy,kind={kind},site={site},n={n},"
                f"b={dec.key.nbytes},strategy={s},chosen={int(s == dec.strategy)},"
                f"analytic_us={dec.analytic_us.get(s, 0):.0f},err_ratio={err:.2f},"
                f"us_per_call={us:.0f}"
            )
        if dec.strategy in times and len(times) > 1:
            worst = max(times.values())
            assert times[dec.strategy] <= worst * 1.10, (
                f"tuner picked {dec.strategy} ({times[dec.strategy]:.0f}us) but the "
                f"worst fixed strategy costs {worst:.0f}us — ranking inverted: {times}"
            )
    tuner.save()


def bench_export(log=print):
    """Collective compiler export (runtime/export.py): compile the §2–§5
    programs at n=16 into versioned per-device send/recv traces, re-prove
    them (structure, link conflict-freedom, send/recv pairing), JSON
    round-trip them, and replay the traces through the ``sendrecv``
    interpreter — asserted bit-identical to the reference backend in-line,
    so a drifting exporter fails the bench instead of logging a row.

    Rows (family ``export``):
      * ``export_compile``   — cold export (lru cache cleared inside the
        timed closure) with the trace's group/op/send/wave counts;
      * ``export_validate``  — the static validator on the exported form;
      * ``export_roundtrip`` — ``to_json`` + ``from_json`` (lossless),
        with the serialized byte size;
      * ``export_replay``    — the NumPy trace interpreter executing the
        trace (the ``sendrecv`` backend's hot path).
    """
    from repro.core.topology import D3
    from repro.dist import collectives as coll
    from repro.dist.mesh import DeviceLayout
    from repro.runtime import export as rexport
    from repro.runtime.backends.reference import NumpyReferenceBackend
    from repro.runtime.backends.sendrecv import SendRecvBackend

    layout = DeviceLayout(D3(4, 2))  # n=16, power-of-two SBH
    progs = [
        ("alltoall", coll.alltoall_program(layout)),
        ("alltoall_pipe1", coll.alltoall_program(layout, pipelined=1)),
        ("allreduce", coll.allreduce_program(layout)),
        ("broadcast", coll.broadcast_program(layout, 0)),
        ("matmul", coll.matmul_program(2, 2)),
    ]
    rng = np.random.default_rng(0)
    sr, ref = SendRecvBackend(), NumpyReferenceBackend()
    for name, prog in progs:
        def cold_export():
            rexport._export.cache_clear()
            return rexport.export(prog)

        trace, us = _timed(cold_export)
        log(
            f"export_compile,kind={name},n={prog.n},groups={trace.num_groups},"
            f"ops={trace.num_ops},sends={trace.num_sends},"
            f"waves={len(trace.waves())},us_per_call={us:.0f}"
        )
        _, us = _timed(lambda: rexport.validate(trace))
        log(f"export_validate,kind={name},n={prog.n},ops={trace.num_ops},"
            f"us_per_call={us:.0f}")
        text = trace.to_json()
        back, us = _timed(lambda: rexport.DeviceTrace.from_json(trace.to_json()))
        assert back == trace, f"{name}: JSON round-trip not lossless"
        log(f"export_roundtrip,kind={name},n={prog.n},bytes={len(text)},"
            f"us_per_call={us:.0f}")
        if prog.kind == "alltoall":
            x = rng.integers(-4, 5, (prog.n, prog.n, 4)).astype(np.float32)
            out, us = _timed(sr.run_alltoall, x, prog)
            ok = np.array_equal(out, ref.run_alltoall(x, prog))
        elif prog.kind == "allreduce":
            x = rng.integers(-4, 5, (prog.n, 8)).astype(np.float32)
            out, us = _timed(sr.run_allreduce, x, prog)
            ok = np.array_equal(out, ref.run_allreduce(x, prog))
        elif prog.kind == "broadcast":
            x = rng.integers(-4, 5, (prog.n, 8)).astype(np.float32)
            out, us = _timed(sr.run_broadcast, x, prog)
            ok = np.array_equal(out, ref.run_broadcast(x, prog))
        else:  # matmul: N=4 grid of 2x2 blocks -> 8x8 operands
            side = 4 * 2
            B = rng.integers(-4, 5, (side, side)).astype(np.float32)
            A = rng.integers(-4, 5, (side, side)).astype(np.float32)
            out, us = _timed(sr.run_matmul, B, A, prog)
            ok = np.array_equal(out, ref.run_matmul(B, A, prog))
        assert ok, f"{name}: sendrecv replay diverged from reference"
        log(f"export_replay,kind={name},n={prog.n},backend=sendrecv,"
            f"us_per_call={us:.0f}")


def bench_moe_pipeline(log=print):
    """Pipelined shard-path dispatch (§3 Schedules 1–3 overlapped with
    expert compute): the MoE-shaped dispatch+FFN+combine round trip on the
    16-device D3(4,2) mesh, per execution path —

      * ``reference``     — host NumPy ground truth (untimed oracle);
      * ``loop``          — per-stage ppermute dispatch, one batched FFN
        over all arrivals, per-stage combine (the sequential baseline);
      * ``xla``           — ``lax.all_to_all`` dispatch/combine around the
        same batched FFN;
      * ``overlap_fused`` — ``alltoall_compute`` on the pipelined program:
        each wave's ppermutes issue while the previous wave's arrivals
        drain through the FFN and return over the inverse pairs.

    Shapes mirror the EP hot path (E_loc=2, C_loc=32, d=64, f=128 silu-
    gated FFN). Bit-exactness vs the reference is asserted in-line for
    every path, as is the tentpole's acceptance bound: overlap_fused
    strictly beats the sequential loop. ``moe_pipeline_decision`` rows
    record what the autotuner picks for the matching compute-keyed shard
    sites (native 16-device, small 8-device, and an emulated site where
    the fused-XLA candidate is excluded) — at least one must select
    overlap_fused, also asserted in-line."""
    import jax
    import jax.numpy as jnp

    from repro.runtime import autotune as at

    n, E_loc, C_loc, d, f = 16, 2, 32, 64, 128
    tag = f"n={n},E_loc={E_loc},C_loc={C_loc},d={d},f={f}"
    if jax.device_count() < n:
        for path in ("loop", "xla", "overlap_fused"):
            log(f"moe_pipeline,path={path},{tag},skipped=need_{n}_devices")
        return
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.dist.collectives import alltoall_program
    from repro.dist.mesh import dragonfly_layout
    from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend
    from repro.runtime.backends.reference import NumpyReferenceBackend

    layout = dragonfly_layout(n)
    pipe = alltoall_program(layout, pipelined=1)
    barrier = alltoall_program(layout)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n, E_loc, C_loc, d)).astype(np.float32)
    WG = jnp.asarray(rng.standard_normal((d, f)).astype(np.float32) * 0.05)
    WI = jnp.asarray(rng.standard_normal((d, f)).astype(np.float32) * 0.05)
    WO = jnp.asarray(rng.standard_normal((f, d)).astype(np.float32) * 0.05)

    def ffn(chunks):
        g = jax.nn.silu(chunks @ WG) * (chunks @ WI)
        return g @ WO

    ref = NumpyReferenceBackend()
    want = ref.run_alltoall_compute(
        x.copy(), pipe, lambda j, c: np.asarray(ffn(jnp.asarray(c))))
    log(f"moe_pipeline,path=reference,{tag},oracle=1")

    mesh = Mesh(np.array(jax.devices()[:n]), ("df",))
    sm = lambda body: jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("df"), out_specs=P("df")))
    be_loop = JaxPpermuteBackend()
    be_of = JaxPpermuteBackend(overlap_fused=True)
    runners = {
        "loop": sm(lambda s: be_loop.alltoall(
            ffn(be_loop.alltoall(s[0], "df", barrier)), "df", barrier)[None]),
        "xla": sm(lambda s: jax.lax.all_to_all(
            ffn(jax.lax.all_to_all(s[0], "df", split_axis=0, concat_axis=0)),
            "df", split_axis=0, concat_axis=0)[None]),
        "overlap_fused": sm(
            lambda s: be_of.alltoall_compute(s[0], "df", pipe, ffn)[None]),
    }
    times: dict[str, float] = {}
    for path, fn in runners.items():
        out, us = _timed(lambda: jax.block_until_ready(fn(x)), iters=5)
        np.testing.assert_array_equal(np.asarray(out), want)
        times[path] = us
        log(f"moe_pipeline,path={path},{tag},waves={pipe.num_rounds},"
            f"us_per_call={us:.0f}")
    assert times["overlap_fused"] < times["loop"], (
        f"pipelining lost to the sequential loop: {times}")

    # what the tuner records for the matching compute-keyed shard sites
    # (default on-disk cache, the CI artifact next to the BENCH trajectory)
    tuner = at.Autotuner()
    chunk = E_loc * C_loc * d * 4
    sites = [
        (layout, chunk, at.moe_compute_us(E_loc, C_loc, n, d, f), False),
        (at.layout_for(8), chunk, 2000, False),
        (layout, chunk, at.moe_compute_us(E_loc, C_loc, n, d, f), True),
    ]
    chosen = []
    for lay, nbytes, cus, emulated in sites:
        dec = tuner.decide("alltoall", lay, nbytes, site="shard",
                           emulated=emulated, compute_us=cus)
        chosen.append(dec.strategy)
        log(f"moe_pipeline_decision,site=shard,K={lay.topo.K},M={lay.topo.M},"
            f"b={dec.key.nbytes},c={dec.key.compute_us},emulated={int(emulated)},"
            f"strategy={dec.strategy},source={dec.source},"
            f"us_per_call={dec.predicted_us:.0f}")
    assert "overlap_fused" in chosen, (
        f"no compute-keyed shard site selected overlap_fused: {chosen}")
    tuner.save()


def bench_elastic_failover(log=print):
    """Elastic training failover: the detection -> resume wall time and
    the §5 redistribution broadcast's round count for every stage of a
    twice-cascading failure on a D3(2,2) training run (shrinks (1,2) ->
    (2,1), the second stage reachable only through the mixed
    cabinet×position survivor search), plus the one-off prepare cost of
    lowering the full fallback-shape library.

    Asserted in-line: every failover is rewrite-only (zero schedule
    derivations) and the elastic loss curve is continuous — it matches an
    uninterrupted same-seed run at equal data-state."""
    import tempfile

    from repro.configs import get_smoke_config
    from repro.core.topology import D3
    from repro.dist.mesh import DeviceLayout
    from repro.train.elastic import (
        ElasticTrainer, FaultInjector, max_loss_divergence)
    from repro.train.fault_tolerance import ClusterState
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import TrainSettings

    tag = "host=2x2,arch=tinyllama-smoke"
    steps = 10

    t0 = time.perf_counter()
    cs = ClusterState(DeviceLayout(D3(2, 2)))
    cs.prepare_fallbacks()
    prep_us = (time.perf_counter() - t0) * 1e6
    log(f"elastic_failover,phase=prepare,{tag},shapes={len(cs.library)},"
        f"us_per_call={prep_us:.0f}")

    cfg = get_smoke_config("tinyllama-1.1b")
    opt_cfg = OptConfig(lr=3e-3, warmup_steps=2, total_steps=steps)
    settings = TrainSettings(use_kernel=False, remat=False)
    kw = dict(host=D3(2, 2), batch=4, seq=16, seed=0, ckpt_every=2)

    with tempfile.TemporaryDirectory() as d:
        baseline = ElasticTrainer(
            cfg, opt_cfg, settings, ckpt_dir=d, **kw).run(steps)
    with tempfile.TemporaryDirectory() as d:
        el = ElasticTrainer(
            cfg, opt_cfg, settings, ckpt_dir=d,
            injector=FaultInjector({3: [1], 7: [4]}), **kw)
        losses = el.run(steps)

    div = max_loss_divergence(baseline, losses)
    assert div < 1e-4, f"post-failover loss curve diverged: {div}"
    assert [e.absorbed for e in el.events] == [False, False], el.events
    for i, ev in enumerate(el.events):
        assert ev.derivations == 0, ev    # rewrite-only failover
        log(f"elastic_failover,phase=failover,stage={i},"
            f"shape={ev.shape[0]}x{ev.shape[1]},{tag},"
            f"survivors={len(ev.survivors)},rounds={ev.broadcast_rounds},"
            f"bytes={ev.bytes_redistributed},"
            f"us_per_call={ev.wall_s * 1e6:.0f}")
    print(f"# elastic: {len(el.events)} cascaded failovers survived, "
          f"loss divergence {div:.1e}")


# ------------------------------------------------------- trajectory compare
#: param keys excluded from record identity when diffing trajectories —
#: they vary run to run (timing noise, cache state) without the record
#: meaning a different measurement
_VOLATILE_PARAMS = {"err_ratio", "loss", "source", "chosen", "analytic_us",
                    "skipped", "hops"}


def _record_key(rec: dict) -> str:
    items = sorted(
        (k, v) for k, v in rec.get("params", {}).items()
        if k not in _VOLATILE_PARAMS
    )
    return rec["name"] + "|" + ",".join(f"{k}={v}" for k, v in items)


def compare(old_path: str, new_path: str, tolerance: float = 0.5,
            log=print, strict_families: tuple[str, ...] = ()) -> tuple[int, int]:
    """Diff two ``--json`` trajectories; returns (regressions, strict).

    A shared record regresses when its us_per_call grew beyond
    ``1 + tolerance``; symmetric improvements and added/removed records are
    reported informationally. Records without timings (skipped rows,
    structural records) are ignored. ``strict_families`` are record-name
    prefixes (e.g. ``("autotuner", "optimizer")``) whose regressions count
    toward the second, CI-failing total even in warn-only mode — the
    families whose timings have soaked enough to be load-bearing."""
    with open(old_path) as f:
        old = {_record_key(r): r for r in json.load(f)}
    with open(new_path) as f:
        new = {_record_key(r): r for r in json.load(f)}
    shared = sorted(set(old) & set(new))
    regressions = strict = 0
    for key in shared:
        o, nrec = old[key], new[key]
        if "us_per_call" not in o or "us_per_call" not in nrec:
            continue
        ou, nu = float(o["us_per_call"]), float(nrec["us_per_call"])
        if ou <= 0:
            continue
        ratio = nu / ou
        if ratio > 1 + tolerance:
            regressions += 1
            in_family = any(nrec["name"].startswith(f) for f in strict_families)
            strict += in_family
            sev = "REGRESSION(strict)" if in_family else "REGRESSION"
            log(f"{sev} {key}: {ou:.0f}us -> {nu:.0f}us "
                f"({ratio:.2f}x > {1 + tolerance:.2f}x tolerance)")
        elif ratio < 1 / (1 + tolerance):
            log(f"improved   {key}: {ou:.0f}us -> {nu:.0f}us ({ratio:.2f}x)")
    for key in sorted(set(new) - set(old)):
        log(f"added      {key}")
    for key in sorted(set(old) - set(new)):
        log(f"removed    {key}")
    log(f"# compared {len(shared)} shared records; "
        f"{regressions} regression(s) beyond +{tolerance:.0%}"
        + (f", {strict} in strict families" if strict_families else ""))
    return regressions, strict


def _parse_record(line: str) -> dict | None:
    """``name,k=v,...`` -> {name, params, us_per_call?, rounds?}."""
    parts = line.strip().split(",")
    if not parts or not parts[0] or "=" in parts[0]:
        return None
    rec: dict = {"name": parts[0], "params": {}}
    for kv in parts[1:]:
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            val: object = int(v)
        except ValueError:
            try:
                val = float(v)
            except ValueError:
                val = v
        if k in ("us_per_call", "rounds"):
            rec[k] = val
        else:
            rec["params"][k] = val
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write machine-readable records to PATH")
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"), default=None,
                    help="diff two --json trajectories instead of benchmarking")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="relative us_per_call growth before a shared record "
                         "counts as a regression (default 0.5 = +50%%)")
    ap.add_argument("--strict", action="store_true",
                    help="with --compare: exit 1 when regressions exist "
                         "(default is warn-only)")
    ap.add_argument("--strict-families", metavar="PREFIXES", default="",
                    help="with --compare: comma-separated record-name "
                         "prefixes (e.g. autotuner,optimizer) whose "
                         "regressions exit 1 even without --strict")
    args = ap.parse_args(argv)

    if args.compare:
        fams = tuple(f for f in args.strict_families.split(",") if f)
        n_reg, n_strict = compare(*args.compare, tolerance=args.tolerance,
                                  strict_families=fams)
        if (args.strict and n_reg) or n_strict:
            raise SystemExit(1)
        return

    if args.json:  # fail fast before minutes of benchmarking
        with open(args.json, "a"):
            pass

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    records: list[dict] = []

    def log(line):
        print(line)
        rec = _parse_record(str(line))
        if rec is not None:
            records.append(rec)

    from benchmarks import bench_matmul, bench_alltoall, bench_hypercube, bench_broadcast

    print("# ---- paper §2: matrix product on D3(K²,M)")
    bench_matmul.run(log)
    print("# ---- paper §3: doubly-parallel all-to-all")
    bench_alltoall.run(log)
    print("# ---- paper §4: SBH hypercube emulation")
    bench_hypercube.run(log)
    print("# ---- paper §5: broadcast spanning trees")
    bench_broadcast.run(log)
    print("# ---- runtime micro-benchmarks")
    bench_schedule_lowering(log)
    print("# ---- runtime backends (dragonfly vs fused XLA vs reference vs pallas)")
    bench_backends(log)
    print("# ---- optimizer pass (fused table replay vs per-stage loop)")
    bench_optimizer(log)
    print("# ---- emulation rewrite (guest-on-host vs native lowering)")
    bench_emulation_rewrite(log)
    print("# ---- concurrent guests (combined multiplex vs time-multiplex)")
    bench_concurrent_guests(log)
    print("# ---- price-driven autotuner (decision table + strategy timings)")
    bench_autotuner(log)
    print("# ---- collective compiler export (send/recv traces + trace replay)")
    bench_export(log)
    print("# ---- pipelined shard-path dispatch (waves overlapped with expert FFN)")
    bench_moe_pipeline(log)
    print("# ---- elastic failover (rewrite-only recovery + §5 re-shard)")
    bench_elastic_failover(log)
    bench_core_micro(log)
    bench_kernels(log)
    bench_train_smoke(log)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}")


if __name__ == "__main__":
    main()
