"""Price-driven collective autotuner — pick the cheapest strategy per call
site, seeded by the paper's analytic prices and calibrated by measurement.

The paper prices every algorithm in rounds (Theorems 1–4, Schedules 1–3)
and ``core.costmodel`` encodes those tables; PR 4 added three coexisting
execution strategies for every lowered program (per-stage replay, fused
``optimize()`` tables, Pallas kernels) plus the plain XLA collective the
runtime replaces. Nothing *dispatched* on price until now: every call site
hardcoded one strategy. The ``Autotuner`` closes that gap:

  * a call site is keyed on ``(kind, K·M topology, message bytes, dtype,
    site)`` — ``TuneKey``; message bytes are bucketed to the next power of
    two so nearby shapes share one decision;
  * the candidate strategies per site class are

      - ``site="host"``   (NumPy whole-array callers):   loop | fused |
        sendrecv
      - ``site="global"`` (device whole-array ``run_*``): loop | fused |
        pallas_fused | sendrecv | xla
      - ``site="shard"``  (inside a caller's shard_map, e.g. MoE
        dispatch): xla | loop | overlap | overlap_fused (all-to-all
        only — the fused wave pipeline that overlaps dispatch with the
        per-destination compute; priced with the max-of-overlap discount
        when the key carries a ``compute_us`` term)
      - ``site="combined"`` (N disjoint guests on one host):
        combined | time_mux.
        ``combined`` is ONE merged-program replay at makespan
        max(T_1..T_N); ``time_mux`` is N sequential solo replays at
        ΣT_i. Keyed on the guest-set signature (``decide_combined``),
        since the tenant mix — not just the host shape — decides the
        merge's worth.

    where ``loop`` is the per-stage D3 schedule replay, ``overlap`` the
    same program in ``start_step`` order, ``fused`` the ``optimize()``
    table replay, ``pallas_fused`` the Pallas-kernel backend, ``sendrecv``
    the exported per-device trace replayed by the NumPy interpreter
    (``runtime.export`` + ``backends/sendrecv`` — device-free, like the
    host-site strategies), and ``xla`` the fused XLA collective
    (``lax.all_to_all`` / ``psum``). Inside a
    shard_map the fused-table form of an all-to-all IS the single fused
    op, so ``xla`` is how "fused" manifests at shard sites;
  * decisions are SEEDED by analytic prices — ``costmodel.price`` of the
    emitted schedule turned into wall-clock by the bytes-aware
    ``costmodel.seconds`` plus per-strategy software-overhead terms — and
    then CALIBRATED by one-shot measured timings, memoized in an on-disk
    JSON cache (``benchmarks/autotune_cache.json``, schema-versioned,
    corrupt-tolerant: an unreadable cache falls back to analytic seeding
    and is rewritten on the next measurement);
  * escape hatches: ``REPRO_AUTOTUNE=analytic`` forces analytic-only
    ranking (no measurement, no disk), ``REPRO_AUTOTUNE=off`` disables
    tuning (every site gets its pre-autotuner default), and
    ``REPRO_AUTOTUNE=<strategy>`` forces one strategy everywhere it is
    structurally available. ``REPRO_AUTOTUNE_CACHE`` moves the cache file.
    The ``Autotuner`` constructor takes the same knobs (``mode``,
    ``force``, ``cache_path``) for programmatic control.

Wired call sites: ``dist.collectives.dragonfly_*`` accept
``backend="auto"``, ``runtime.backends.get_backend("auto")`` returns the
:class:`AutoBackend` whole-array dispatcher, ``models.moe`` routes EP
dispatch/combine through the tuner when ``moe_collectives="auto"``, and
``serve.engine`` / ``launch.dryrun`` report the chosen strategy + priced
rounds per config via :func:`moe_site_report`.

Determinism: a warm cache always returns the recorded decision (no
re-measurement), analytic ranking is pure arithmetic over the schedule,
and measurement happens at most once per key per cache lifetime.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import tempfile
import time

import numpy as np

from repro.core import costmodel

SCHEMA_VERSION = 1
DEFAULT_CACHE = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "autotune_cache.json"

KINDS = ("alltoall", "allreduce", "broadcast", "matmul")
SITES = ("host", "global", "shard", "combined")
STRATEGIES = ("loop", "overlap", "fused", "pallas_fused", "xla",
              "overlap_fused", "sendrecv", "combined", "time_mux")

#: analytic seed constants (calibration overrides these — they only need to
#: produce a sane ranking before the first measurement lands in the cache)
T_W = 1.0e-6          # per-hop router latency, the paper's t_w
BANDWIDTH = 50e9      # per-link wire bandwidth (TPU v5e ICI)
T_DISPATCH = 5.0e-6   # software overhead per replayed stage (loop paths)
T_GROUP = 2.0e-6      # software overhead per fused table group
T_KERNEL = 10.0e-6    # extra per-group cost of a Pallas kernel launch
T_XLA = 20.0e-6       # fixed overhead of one fused XLA collective
T_TRACE_OP = 2.0e-6   # per-op overhead of the sendrecv trace interpreter
COMPUTE_RATE = 2e9    # proxy flops/s for sizing synthetic pipeline compute


# ---------------------------------------------------------------------------
# Keys and decisions
# ---------------------------------------------------------------------------

def bucket_bytes(nbytes: int) -> int:
    """Round message bytes up to the next power of two (min 64) so nearby
    shapes share one cache entry and the key space stays bounded."""
    n = max(64, int(nbytes))
    return 1 << (n - 1).bit_length()


def bucket_compute_us(compute_us: int) -> int:
    """Bucket the per-device fused-compute term: 0 (pure collective) stays
    0, anything else rounds up to the next power of two µs."""
    n = int(compute_us)
    return 0 if n <= 0 else 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """One call site: what is being moved, over which topology, how big.

    ``compute_us`` is the bucketed per-device cost of the compute fused
    into the collective's round trip (MoE expert FFN at dispatch sites);
    0 means a pure data-movement site. ``emulated`` marks guest-on-host
    ``active_devices`` sites, whose candidate set excludes ``xla`` — it
    must be part of the key or a native decision (possibly ``xla``) would
    be replayed from the memo/cache at an emulated site. Pure native
    sites keep the pre-compute key string (no ``|c``/``|emu`` suffix), so
    caches recorded before these fields existed stay valid."""

    kind: str      # alltoall | allreduce | broadcast | matmul
    K: int         # D3(K, M) of the mesh axis (matmul: the grid's topo)
    M: int
    nbytes: int    # bucketed message bytes (per chunk / vector / block)
    dtype: str
    site: str      # host | global | shard | combined
    compute_us: int = 0  # bucketed fused-compute µs per device (0 = none)
    emulated: bool = False  # guest-on-host program (xla excluded)
    guests: str = ""  # combined sites: the guest-set signature ("2xD3(1,2)")

    def __str__(self) -> str:
        tail = f"|c{self.compute_us}" if self.compute_us else ""
        tail += "|emu" if self.emulated else ""
        tail += f"|g{self.guests}" if self.guests else ""
        return (f"{self.kind}|K{self.K}M{self.M}|b{self.nbytes}"
                f"|{self.dtype}|{self.site}{tail}")


@dataclasses.dataclass(frozen=True)
class Decision:
    """The tuner's answer for one key, with its full evidence trail."""

    key: TuneKey
    strategy: str
    source: str                     # forced | off | cache | measured | analytic
    rounds: int                     # priced schedule rounds (xla: 1)
    hops: float                     # costmodel.price of the schedule, t_w units
    analytic_us: dict[str, float]   # strategy -> analytic seed price
    measured_us: dict[str, float]   # strategy -> measured (empty if analytic)

    @property
    def predicted_us(self) -> float:
        got = self.measured_us.get(self.strategy)
        return got if got is not None else self.analytic_us.get(self.strategy, 0.0)

    def as_row(self) -> dict:
        return {
            "key": str(self.key), "strategy": self.strategy,
            "source": self.source, "rounds": self.rounds, "hops": self.hops,
            "predicted_us": round(self.predicted_us, 1),
            "analytic_us": {k: round(v, 1) for k, v in self.analytic_us.items()},
            "measured_us": {k: round(v, 1) for k, v in self.measured_us.items()},
        }


def _default_strategy(kind: str, site: str) -> str:
    """What each call site did BEFORE the autotuner existed (mode='off')."""
    if site == "combined":
        return "time_mux"  # every guest replayed alone
    return "xla" if site == "shard" else "loop"


def candidates(kind: str, site: str, *, emulated: bool = False) -> tuple[str, ...]:
    """Structurally available strategies for a (kind, site) class.

    ``emulated`` (guest-on-host ``active_devices`` programs) drops ``xla``:
    the fused op would mix idle devices into the result."""
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}")
    if site == "combined":
        return ("combined", "time_mux")
    if site == "host":
        out: tuple[str, ...] = ("loop", "fused", "sendrecv")
    elif site == "global":
        out = ("loop", "fused", "pallas_fused", "sendrecv")
        if kind in ("alltoall", "allreduce"):
            out += ("xla",)
    elif site == "shard":
        out = ("loop", "overlap")
        if kind != "matmul":
            out = ("xla",) + out
        if kind == "alltoall":
            out += ("overlap_fused",)
    else:
        raise ValueError(f"unknown site {site!r}; expected one of {SITES}")
    if emulated:
        out = tuple(s for s in out if s != "xla")
    return out


# ---------------------------------------------------------------------------
# Schedules / programs per kind (lazy dist imports — dist layers on runtime)
# ---------------------------------------------------------------------------

def _schedule(kind: str, layout, grid=None):
    from repro.core import alltoall as a2a
    from repro.core import broadcast as bc
    from repro.core import hypercube as hc
    from repro.core import matmul as mm

    if kind == "alltoall":
        return a2a.schedule(layout.da_params, layout.topo)
    if kind == "allreduce":
        if layout.sbh is None:
            raise ValueError(f"D3({layout.topo.K},{layout.topo.M}) has no SBH")
        return hc.allreduce_schedule(layout.sbh)
    if kind == "broadcast":
        return bc.depth3_schedule(layout.topo, layout.topo.id_router(0))
    if kind == "matmul":
        return mm.schedule(mm.MatmulGrid(*grid))
    raise ValueError(f"unknown kind {kind!r}")


def _program(kind: str, layout, grid=None):
    from repro.dist import collectives as coll

    if kind == "alltoall":
        return coll.alltoall_program(layout)
    if kind == "allreduce":
        return coll.allreduce_program(layout)
    if kind == "broadcast":
        return coll.broadcast_program(layout, 0)
    return coll.matmul_program(*grid)


def layout_for(n: int):
    from repro.dist.mesh import dragonfly_layout

    return dragonfly_layout(n)


def _guest_layout(embedding):
    from repro.dist.mesh import DeviceLayout

    return DeviceLayout(embedding.guest)


# ---------------------------------------------------------------------------
# Analytic seeding
# ---------------------------------------------------------------------------

def analytic_prices(kind: str, layout, nbytes: int, strategies, grid=None,
                    compute_us: int = 0) -> dict[str, float]:
    """Per-strategy analytic seed prices in µs: the schedule's priced hops
    through the bytes-aware ``costmodel.seconds`` plus software-overhead
    terms per replayed stage / fused group / kernel launch.

    ``compute_us`` prices a compute term fused into the site's round trip
    (MoE expert FFN). Sequential strategies pay dispatch + compute + combine
    as a SUM; ``overlap_fused`` issues waves while already-arrived chunks
    are contracted, so it pays max(pipelined wire time, compute) — the
    Schedules 1–3 overlap discount — plus its per-stage table overhead."""
    from repro.runtime import lowering, optimize as ropt

    sched = _schedule(kind, layout, grid)
    hops = costmodel.price(sched, t_w=1.0, t_s=0.0)
    hops_pipe = costmodel.price_pipelined(sched, 1.0, 0.0)
    prog = lowering.lower(sched)
    n_stages = len(prog.stages)
    n_groups = ropt.optimize(prog).num_fused_ops
    n = prog.n
    compute_s = max(0, int(compute_us)) * 1e-6

    out: dict[str, float] = {}
    for s in strategies:
        if s == "loop":
            sec = costmodel.seconds(hops, T_W, n_stages * T_DISPATCH,
                                    bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
        elif s == "overlap":
            sec = costmodel.seconds(hops_pipe, T_W, n_stages * T_DISPATCH,
                                    bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
        elif s == "fused":
            sec = costmodel.seconds(hops, T_W, n_groups * T_GROUP,
                                    bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
        elif s == "pallas_fused":
            sec = costmodel.seconds(hops, T_W, n_groups * (T_GROUP + T_KERNEL),
                                    bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
        elif s == "sendrecv":
            # the exported-trace interpreter walks every per-device op —
            # honest seeding keeps it priced above the fused table replay
            from repro.runtime import export as rexport

            n_ops = rexport.export(prog).num_ops
            sec = costmodel.seconds(hops, T_W, n_ops * T_TRACE_OP,
                                    bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
        elif s == "xla":
            # one fused op: latency-optimal collective, e.g. n-1 exchange
            # steps for all-to-all, 2·log2(n) for a psum ring/tree
            xla_hops = (n - 1) if kind == "alltoall" else 2 * max(1, n).bit_length()
            sec = costmodel.seconds(xla_hops, T_W, T_XLA,
                                    bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
        elif s == "overlap_fused":
            wire = costmodel.seconds(hops_pipe, T_W, 0.0,
                                     bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
            if compute_s and kind == "alltoall":
                # overlap discount: the expert compute hides behind the
                # pipelined dispatch+return rounds (and vice versa) — only
                # the table bookkeeping is serial
                sec = max(2.0 * wire, compute_s) + n_stages * T_GROUP
            else:
                sec = wire + n_stages * T_GROUP
            out[s] = sec * 1e6
            continue
        else:  # pragma: no cover - candidates() guards the universe
            raise ValueError(f"unknown strategy {s!r}")
        if compute_s and kind == "alltoall":
            # sequential round trip: dispatch + compute + combine
            sec = 2.0 * sec + compute_s
        out[s] = sec * 1e6
    return out


def priced_rounds(kind: str, layout, grid=None) -> tuple[int, float]:
    """(rounds, priced hops in t_w units) of the kind's schedule — the
    paper-table numbers the reports attach to each decision."""
    sched = _schedule(kind, layout, grid)
    return len(sched.rounds), costmodel.price(sched, t_w=1.0, t_s=0.0)


def guest_signature(embeddings) -> str:
    """Canonical guest-set signature for combined-site keys: shape counts
    in sorted order, e.g. ``"2xD3(1,2)"`` or ``"1xD3(1,2)+1xD3(2,2)"`` —
    placement-independent, so re-admitting the same mix after churn hits
    the same cache entry."""
    counts: dict[str, int] = {}
    for e in embeddings:
        s = f"D3({e.guest.K},{e.guest.M})"
        counts[s] = counts.get(s, 0) + 1
    return "+".join(f"{n}x{s}" for s, n in sorted(counts.items()))


def analytic_combined_prices(kind: str, embeddings, nbytes: int
                             ) -> dict[str, float]:
    """Seed prices (µs) for one combined site: ``combined`` pays the
    makespan — max of the guests' priced hops — plus the MERGED program's
    per-stage overhead (same-stamp stages packed into one partial stage);
    ``time_mux`` pays the sum of hops plus every solo program's stage
    overhead. The wire term dominates at scale, the software term at toy
    sizes — both favor combining, by Property 2's disjoint-links argument."""
    from repro.dist import collectives as coll
    from repro.dist.mesh import DeviceLayout
    from repro.runtime import lowering

    hops, stages = [], []
    for emb in embeddings:
        sched = _schedule(kind, DeviceLayout(emb.guest))
        hops.append(costmodel.price(sched, t_w=1.0, t_s=0.0))
        stages.append(len(lowering.lower(sched).stages))
    comb = coll.concurrent_program(kind, tuple(embeddings))
    combined = costmodel.seconds(max(hops), T_W, len(comb.stages) * T_DISPATCH,
                                 bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
    mux = costmodel.seconds(sum(hops), T_W, sum(stages) * T_DISPATCH,
                            bytes_per_hop=nbytes, bandwidth=BANDWIDTH)
    return {"combined": combined * 1e6, "time_mux": mux * 1e6}


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def _elems(nbytes: int, dtype: str) -> int:
    return max(1, int(nbytes) // max(1, np.dtype(dtype).itemsize))


def _time_us(fn, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _measure_closure(kind: str, site: str, strategy: str, layout, grid,
                     nbytes: int, dtype: str, compute_us: int = 0):
    """A zero-arg runnable of (kind, strategy) at the keyed message size,
    or None when the strategy cannot run here (e.g. too few devices).

    ``compute_us > 0`` all-to-all keys measure the FULL round-trip
    pipeline — dispatch, a synthetic per-chunk contraction sized to
    ``compute_us`` per device (via ``COMPUTE_RATE``), combine — so the
    overlap discount of ``overlap_fused`` shows up in the timing instead
    of being assumed."""
    from repro.runtime import optimize as ropt

    prog = _program(kind, layout, grid)
    e = _elems(nbytes, dtype)
    rng = np.random.default_rng(0)

    if kind == "matmul":
        from repro.core.matmul import MatmulGrid

        g = MatmulGrid(*grid)
        X = max(1, int(np.sqrt(e)))
        side = g.n * X
        B = rng.integers(-4, 5, (side, side)).astype(dtype)
        A = rng.integers(-4, 5, (side, side)).astype(dtype)
    elif kind == "alltoall":
        x = rng.standard_normal((prog.n, prog.n, e)).astype(dtype)
    else:
        x = rng.standard_normal((prog.n, e)).astype(dtype)

    if site == "host":
        if strategy == "sendrecv":
            from repro.runtime.backends.sendrecv import SendRecvBackend

            ref = SendRecvBackend()
        else:
            from repro.runtime.backends.reference import NumpyReferenceBackend

            ref = NumpyReferenceBackend()
        p = ropt.optimize(prog) if strategy == "fused" else prog
        if kind == "alltoall":
            return lambda: ref.run_alltoall(x, p)
        if kind == "allreduce":
            return lambda: ref.run_allreduce(x, p)
        if kind == "broadcast":
            return lambda: ref.run_broadcast(x, p)
        return lambda: ref.run_matmul(B, A, p)

    if strategy == "sendrecv":
        # device-free at every site class it is a candidate for: the trace
        # interpreter needs no mesh quorum, so measure it before touching jax
        from repro.runtime.backends.sendrecv import SendRecvBackend

        be = SendRecvBackend()
        if kind == "matmul":
            return lambda: be.run_matmul(B, A, prog)
        run = {"alltoall": be.run_alltoall, "allreduce": be.run_allreduce,
               "broadcast": be.run_broadcast}[kind]
        return lambda: run(x, prog)

    # device-backed sites
    import jax
    import jax.numpy as jnp

    if (strategy in ("loop", "overlap", "xla", "overlap_fused")
            and jax.device_count() < prog.n):
        return None

    if kind == "alltoall" and compute_us > 0:
        # full dispatch+compute+combine pipeline: sequential strategies do
        # a2a -> batched contraction -> a2a; overlap_fused runs the fused
        # wave pipeline over the Schedule-1 stamped program
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.dist.collectives import alltoall_program
        from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend

        n = prog.n
        # the proxy is a silu-gated FFN (6·tokens·d_in·f flops per device)
        # with each chunk factored into (tokens, d_in) rows: both the
        # matmul geometry and the gate's elementwise traffic match what a
        # real MoE closure does — a flat (V, e) matmul would be
        # pathologically skinny per wave and elementwise-free, penalizing
        # the wave-sliced strategies for a shape no caller uses
        f_dim = max(1, int(compute_us * 1e-6 * COMPUTE_RATE / (6.0 * n * e)))
        d_in = next((w for w in (64, 32, 16, 8, 4, 2, 1) if e % w == 0))
        # ~1/sqrt(fan-in) weight scale keeps activations O(1) through the
        # gate: unscaled normals push silu into saturated/denormal ranges
        # no trained FFN visits, distorting the timing
        WG = jnp.asarray((rng.standard_normal((d_in, f_dim))
                          / np.sqrt(d_in)).astype(dtype))
        WI = jnp.asarray((rng.standard_normal((d_in, f_dim))
                          / np.sqrt(d_in)).astype(dtype))
        WO = jnp.asarray((rng.standard_normal((f_dim, d_in))
                          / np.sqrt(f_dim)).astype(dtype))
        mesh = Mesh(np.array(jax.devices()[:n]), ("df",))

        def comp(chunks):
            lead = chunks.shape[:-1]
            h = chunks.reshape(-1, d_in)
            g = jax.nn.silu(h @ WG) * (h @ WI)
            return (g @ WO).reshape(*lead, e)

        if strategy == "overlap_fused":
            be = JaxPpermuteBackend(overlap_fused=True)
            pipe = alltoall_program(layout, pipelined=1)
            local = lambda s: be.alltoall_compute(s[0], "df", pipe, comp)[None]
        else:
            if strategy == "xla":
                a2a = lambda v: jax.lax.all_to_all(
                    v, "df", split_axis=0, concat_axis=0)
            else:
                be = JaxPpermuteBackend(overlap=(strategy == "overlap"))
                a2a = lambda v: be.alltoall(v, "df", prog)
            local = lambda s: a2a(comp(a2a(s[0])))[None]
        f = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=P("df"), out_specs=P("df")))
        xj = jnp.asarray(x)
        return lambda: jax.block_until_ready(f(xj))

    if strategy == "xla":
        from jax.sharding import Mesh, PartitionSpec as P


        mesh = Mesh(np.array(jax.devices()[: prog.n]), ("df",))
        if kind == "alltoall":
            f = jax.jit(jax.shard_map(
                lambda s: jax.lax.all_to_all(
                    s[0], "df", split_axis=0, concat_axis=0)[None],
                mesh=mesh, in_specs=P("df"), out_specs=P("df")))
        elif kind == "allreduce":
            f = jax.jit(jax.shard_map(
                lambda s: jax.lax.psum(s, "df"),
                mesh=mesh, in_specs=P("df"), out_specs=P("df")))
        else:  # broadcast root 0: one masked psum
            f = jax.jit(jax.shard_map(
                lambda s: jax.lax.psum(jnp.where(
                    jax.lax.axis_index("df") == 0, s, jnp.zeros_like(s)), "df"),
                mesh=mesh, in_specs=P("df"), out_specs=P("df")))
        xj = jnp.asarray(x)
        return lambda: jax.block_until_ready(f(xj))

    from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend

    if strategy == "pallas_fused":
        from repro.runtime.backends.pallas_fused import PallasFusedBackend

        be = PallasFusedBackend()
        p = prog
    elif strategy == "overlap_fused":
        from repro.dist.collectives import alltoall_program

        be = JaxPpermuteBackend(overlap_fused=True)
        p = alltoall_program(layout, pipelined=1)
    else:
        be = JaxPpermuteBackend(overlap=(strategy == "overlap"))
        p = ropt.optimize(prog) if strategy == "fused" else prog
    if kind == "matmul":
        Bj, Aj = jnp.asarray(B), jnp.asarray(A)
        return lambda: jax.block_until_ready(be.run_matmul(Bj, Aj, p))
    xj = jnp.asarray(x)
    run = {"alltoall": be.run_alltoall, "allreduce": be.run_allreduce,
           "broadcast": be.run_broadcast}[kind]
    return lambda: jax.block_until_ready(run(xj, p))


def _measure_combined_closure(kind: str, strategy: str, embeddings,
                              nbytes: int, dtype: str):
    """A zero-arg runnable of one combined-site strategy, or None when the
    kind has no device-free replay to time. Both arms replay on the NumPy
    reference backend (host-site style: deterministic, no device quorum):
    ``combined`` is ONE merged-program replay, ``time_mux`` is every
    guest's solo emulated replay back to back."""
    if kind not in ("alltoall", "allreduce"):
        return None
    from repro.dist import collectives as coll
    from repro.dist.mesh import DeviceLayout
    from repro.runtime.backends.reference import NumpyReferenceBackend
    from repro.runtime.combine import scatter_guests

    ref = NumpyReferenceBackend()
    e = _elems(nbytes, dtype)
    rng = np.random.default_rng(0)
    axes = (0, 1) if kind == "alltoall" else (0,)
    solos, xs = [], []
    for emb in embeddings:
        layout = DeviceLayout(emb.guest)
        if kind == "alltoall":
            solos.append(coll.alltoall_program(layout, emb))
            xs.append(rng.standard_normal(
                (layout.topo.num_routers, layout.topo.num_routers, e)
            ).astype(dtype))
        else:
            solos.append(coll.allreduce_program(layout, emb))
            xs.append(rng.standard_normal(
                (layout.topo.num_routers, e)).astype(dtype))
    run = ref.run_alltoall if kind == "alltoall" else ref.run_allreduce
    if strategy == "combined":
        comb = coll.concurrent_program(kind, tuple(embeddings))
        xh = scatter_guests(xs, embeddings, axes=axes)
        return lambda: run(xh, comb)
    hs = [scatter_guests([x], [emb], axes=axes)
          for x, emb in zip(xs, embeddings)]

    def mux():
        for prog, xh in zip(solos, hs):
            run(xh, prog)

    return mux


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------

class Autotuner:
    """Per-call-site strategy dispatcher with an on-disk measurement cache.

    ``mode``: ``"measure"`` (default — measure once, cache to disk),
    ``"analytic"`` (rank by seed prices only, touch nothing on disk), or
    ``"off"`` (return each site's pre-autotuner default). ``force`` pins
    one strategy wherever it is structurally available. Both default to
    the ``REPRO_AUTOTUNE`` env var; ``cache_path`` to
    ``REPRO_AUTOTUNE_CACHE`` / ``benchmarks/autotune_cache.json``.
    """

    def __init__(self, cache_path: str | os.PathLike | None = None,
                 mode: str | None = None, force: str | None = None):
        env = os.environ.get("REPRO_AUTOTUNE", "").strip()
        if mode is None and force is None and env:
            if env in ("analytic", "off", "measure"):
                mode = env
            elif env in STRATEGIES:
                force = env
            else:
                raise ValueError(
                    f"REPRO_AUTOTUNE={env!r}: expected 'analytic', 'off', "
                    f"'measure' or a strategy in {STRATEGIES}")
        if force is not None and force not in STRATEGIES:
            raise ValueError(f"unknown forced strategy {force!r}; known: {STRATEGIES}")
        self.mode = mode or "measure"
        if self.mode not in ("measure", "analytic", "off"):
            raise ValueError(f"unknown mode {self.mode!r}")
        self.force = force
        self.cache_path = pathlib.Path(
            cache_path or os.environ.get("REPRO_AUTOTUNE_CACHE", DEFAULT_CACHE))
        self.decisions: list[Decision] = []   # the decision log, for reports
        self._memo: dict[TuneKey, Decision] = {}
        self._cache: dict[str, dict] = self._load_cache()
        self._dirty = False

    # ------------------------------------------------------------- cache
    def _load_cache(self) -> dict[str, dict]:
        """Schema-checked, corrupt-tolerant load: anything unreadable or
        version-mismatched degrades to an empty cache (analytic seeding
        still works; the next measurement rewrites the file)."""
        try:
            raw = json.loads(self.cache_path.read_text())
            if raw.get("schema") != SCHEMA_VERSION:
                return {}
            entries = raw.get("entries")
            return dict(entries) if isinstance(entries, dict) else {}
        except (OSError, ValueError):
            return {}

    def save(self) -> None:
        if not self._dirty:
            return
        payload = {"schema": SCHEMA_VERSION, "entries": self._cache}
        self.cache_path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.cache_path.parent, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.cache_path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        self._dirty = False

    # ------------------------------------------------------------ decide
    def decide(self, kind: str, layout=None, nbytes: int = 0,
               dtype: str = "float32", site: str = "global", grid=None,
               emulated: bool = False, compute_us: int = 0) -> Decision:
        """The cheapest strategy for one call site key. Deterministic for a
        warm cache: same key -> same decision, no re-measurement.

        ``compute_us`` (per-device µs of compute fused into the site's
        round trip, e.g. the MoE expert FFN) keys and prices the decision
        as a full dispatch+compute+combine pipeline: sequential strategies
        pay the sum, ``overlap_fused`` the overlapped max."""
        if kind == "matmul":
            if grid is None:
                raise ValueError("matmul decisions need grid=(K, M)")
            from repro.core.matmul import MatmulGrid

            topo = MatmulGrid(*grid).topo
            if layout is None:
                layout = layout_for(topo.num_routers)
        else:
            if layout is None:
                raise ValueError(f"{kind} decisions need a DeviceLayout")
            topo = layout.topo
        key = TuneKey(kind, topo.K, topo.M, bucket_bytes(nbytes),
                      str(np.dtype(dtype)), site,
                      bucket_compute_us(compute_us), emulated)
        if key in self._memo:
            return self._memo[key]

        cands = candidates(kind, site, emulated=emulated)
        analytic = analytic_prices(kind, layout, key.nbytes, cands, grid,
                                   key.compute_us)
        rounds, hops = priced_rounds(kind, layout, grid)

        if self.force is not None:
            strategy = self.force if self.force in cands else cands[0]
            dec = Decision(key, strategy, "forced", rounds, hops, analytic, {})
        elif self.mode == "off":
            dec = Decision(key, _default_strategy(kind, site), "off",
                           rounds, hops, analytic, {})
        else:
            # analytic mode ignores the cache too: its contract is pure
            # deterministic arithmetic over the schedule, independent of
            # whatever a previous measuring run left on disk
            dec = (self._cached_decision(key, cands, rounds, hops, analytic)
                   if self.mode == "measure" else None)
            if dec is None:
                dec = self._fresh_decision(key, cands, layout, grid,
                                           rounds, hops, analytic)
        self._memo[key] = dec
        self.decisions.append(dec)
        return dec

    def _cached_decision(self, key, cands, rounds, hops, analytic):
        ent = self._cache.get(str(key))
        if not isinstance(ent, dict):
            return None
        strategy = ent.get("strategy")
        if strategy not in cands:   # stale/foreign entry: ignore, re-derive
            return None
        measured = ent.get("measured_us")
        measured = dict(measured) if isinstance(measured, dict) else {}
        return Decision(key, strategy, "cache", rounds, hops, analytic, measured)

    def _fresh_decision(self, key, cands, layout, grid, rounds, hops, analytic):
        measured: dict[str, float] = {}
        if self.mode == "measure":
            for s in cands:
                # None means "cannot run here"; a strategy that fails to
                # compile or run raises
                fn = _measure_closure(key.kind, key.site, s, layout, grid,
                                      key.nbytes, key.dtype, key.compute_us)
                if fn is not None:
                    measured[s] = _time_us(fn)
        return self._conclude(key, rounds, hops, analytic, measured)

    def _conclude(self, key, rounds, hops, analytic, measured):
        """Rank + record: cheapest measured strategy (persisted to the disk
        cache) or, with nothing measurable, cheapest analytic seed."""
        if measured:
            strategy = min(measured, key=measured.__getitem__)
            dec = Decision(key, strategy, "measured", rounds, hops, analytic, measured)
            self._cache[str(key)] = {
                "strategy": strategy, "source": "measured", "rounds": rounds,
                "measured_us": {k: round(v, 2) for k, v in measured.items()},
                "analytic_us": {k: round(v, 2) for k, v in analytic.items()},
            }
            self._dirty = True
            self.save()
        else:
            strategy = min(analytic, key=analytic.__getitem__)
            dec = Decision(key, strategy, "analytic", rounds, hops, analytic, {})
        return dec

    # -------------------------------------------------- combined guest sites
    def decide_combined(self, kind: str, embeddings, nbytes: int = 0,
                        dtype: str = "float32") -> Decision:
        """Combined-vs-time-muxed for one tenant SET: should N disjoint
        guests' ``kind`` collectives replay as one merged host program
        (makespan max(T_i)) or one by one (ΣT_i)?

        The key is the ``combined`` site class keyed on the guest-set
        signature — same host, same bytes, but a different tenant mix is a
        different decision. Measurement replays both arms on the reference
        backend (device-free, like ``site="host"``); kinds without a
        reference replay rank analytically. Memoized and disk-cached like
        ``decide``."""
        embeddings = tuple(embeddings)
        if not embeddings:
            raise ValueError("decide_combined needs at least one embedding")
        host = embeddings[0].host
        key = TuneKey(kind, host.K, host.M, bucket_bytes(nbytes),
                      str(np.dtype(dtype)), "combined", 0, True,
                      guest_signature(embeddings))
        if key in self._memo:
            return self._memo[key]

        cands = candidates(kind, "combined")
        analytic = analytic_combined_prices(kind, embeddings, key.nbytes)
        from repro.dist import collectives as coll

        comb = coll.concurrent_program(kind, embeddings)
        rounds = comb.num_rounds
        hops = max(
            costmodel.price(_schedule(kind, _guest_layout(e)), t_w=1.0, t_s=0.0)
            for e in embeddings
        )

        if self.force is not None:
            strategy = self.force if self.force in cands else cands[0]
            dec = Decision(key, strategy, "forced", rounds, hops, analytic, {})
        elif self.mode == "off":
            dec = Decision(key, _default_strategy(kind, "combined"), "off",
                           rounds, hops, analytic, {})
        else:
            dec = (self._cached_decision(key, cands, rounds, hops, analytic)
                   if self.mode == "measure" else None)
            if dec is None:
                measured: dict[str, float] = {}
                if self.mode == "measure":
                    for s in cands:
                        fn = _measure_combined_closure(
                            kind, s, embeddings, key.nbytes, key.dtype)
                        if fn is not None:
                            measured[s] = _time_us(fn)
                dec = self._conclude(key, rounds, hops, analytic, measured)
        self._memo[key] = dec
        self.decisions.append(dec)
        return dec

    # ------------------------------------------------------------ report
    def report(self) -> list[dict]:
        """The decision table accumulated this process, one row per call."""
        return [d.as_row() for d in self.decisions]


# ---------------------------------------------------------------------------
# Process-wide default tuner (the `backend="auto"` entry points use this)
# ---------------------------------------------------------------------------

_DEFAULT: Autotuner | None = None


def get_autotuner() -> Autotuner:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Autotuner()
    return _DEFAULT


def set_autotuner(tuner: Autotuner | None) -> None:
    """Install (or with None, reset) the process-wide tuner — tests and
    launchers use this to control cache location and mode."""
    global _DEFAULT
    _DEFAULT = tuner


# ---------------------------------------------------------------------------
# Config-level reports (serve.engine / launch.dryrun)
# ---------------------------------------------------------------------------

def moe_compute_us(E_loc: int, c_loc: int, n_model: int, d_model: int,
                   d_ff: int) -> int:
    """Estimated per-device µs of the MoE expert FFN fused into a dispatch
    round trip: each device contracts n_model arriving (E_loc, c_loc,
    d_model) capacity chunks through the silu-gated FFN — three einsums,
    ~6·tokens·d·f flops — at the proxy ``COMPUTE_RATE``. Shared by
    ``models.moe.moe_apply_ep`` and ``moe_site_report`` so both key the
    same tuner decision."""
    flops = 6.0 * E_loc * c_loc * n_model * d_model * d_ff
    return int(flops / COMPUTE_RATE * 1e6)


def moe_site_report(cfg, rules, n_tokens: int, dtype: str = "float32",
                    tuner: Autotuner | None = None) -> dict:
    """Chosen strategy + priced rounds for a config's MoE EP dispatch site.

    Mirrors the key ``models.moe.moe_apply_ep`` uses for its dispatch and
    combine all-to-alls: D3 view of the model axis, per-destination buffer
    bytes from the capacity bound at ``n_tokens`` routed tokens. Returns a
    JSON-ready dict; configs without an EP-capable MoE report why."""
    if getattr(cfg, "moe", None) is None:
        return {"status": "n/a", "reason": "config has no MoE"}
    m = cfg.moe
    E = m.num_experts
    n_model = rules.model_axis_size
    if E % n_model:
        return {"status": "n/a",
                "reason": f"E={E} not divisible by model axis {n_model} (TP path)"}
    tuner = tuner or get_autotuner()
    layout = layout_for(n_model)
    shards = max(1, rules.data_axis_size * n_model)
    t_loc = max(1, n_tokens // shards)
    c_loc = max(8, int(m.capacity_factor * t_loc * m.top_k / E))
    c_loc = -(-c_loc // 8) * 8
    chunk = (E // n_model) * c_loc * cfg.d_model * np.dtype(dtype).itemsize
    dec = tuner.decide(
        "alltoall", layout, chunk, dtype=dtype, site="shard",
        compute_us=moe_compute_us(E // n_model, c_loc, n_model, cfg.d_model,
                                  m.d_ff_expert))
    return {
        "status": "ok",
        "kind": "alltoall",
        "topology": f"D3({layout.topo.K},{layout.topo.M})",
        "key": str(dec.key),
        "strategy": dec.strategy,
        "source": dec.source,
        "rounds": dec.rounds,
        "priced_hops": dec.hops,
        "predicted_us": round(dec.predicted_us, 1),
        "analytic_us": {k: round(v, 1) for k, v in dec.analytic_us.items()},
        "measured_us": {k: round(v, 1) for k, v in dec.measured_us.items()},
        "moe_collectives": {
            "xla": "xla", "loop": "dragonfly",
            "overlap": "dragonfly_overlap",
            "overlap_fused": "dragonfly_overlap_fused"}[dec.strategy],
    }
