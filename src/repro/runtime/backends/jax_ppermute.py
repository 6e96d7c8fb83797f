"""JAX backend: replay a CollectiveProgram as ppermute collectives.

The per-shard methods (``alltoall``/``allreduce``/``broadcast``/``matmul``)
run INSIDE ``shard_map`` over a 1-D mesh axis of ``program.n`` devices
(device i = router ``topo.id_router(i)``). Each communication stage becomes
one ``jax.lax.ppermute``; the conflict-freedom ``core.simulator.verify``
proved for the schedule is the statement that a step's stages occupy
disjoint directed links on the physical D3 network, so issuing them
per-step preserves the paper's round structure (visible in the HLO as one
collective-permute per stage).

``overlap=True`` launches stages in ``start_step`` order instead of round
order: rounds of a pipelined schedule (``meta["start_step"]``) interleave,
letting XLA overlap independent ppermutes across rounds. For barrier
schedules the two orders coincide, so overlap is always safe to enable.

Emulated (guest-on-host) programs — ``runtime.rewrite.emulate`` output,
``program.active_devices`` set — replay on the full K·M·M host mesh with no
special casing: their stages are partial permutations/matchings over the
embedded device subset, ``ppermute`` hands idle (non-destination) devices
zeros, and the replay logic only folds an arrival into a device's state
when that device is a listed destination, so idle devices pass through.
A guest J·L·L-device program therefore runs on the host mesh unchanged,
stamps and pipelining included.

The ``run_*`` wrappers build the shard_map plumbing for whole-array callers
(the backend contract shared with the NumPy reference backend) and are the
executable form of the paper: MoE token dispatch calls the per-shard
``alltoall`` instead of the generic fused ``lax.all_to_all`` when
``--collectives dragonfly`` is on.

Hot-path behavior of the wrappers:

  * meshes and jitted shard_map closures are CACHED per (backend, program,
    axis, mesh, flags) — repeated collective calls (MoE dispatch per layer)
    reuse one compiled executable instead of rebuilding the mesh and
    retracing every call;
  * ``run_matmul`` scatters/gathers operand blocks (and emulated guest
    slots) entirely in jnp inside one jitted closure — no ``np.asarray``
    host sync until the caller materializes the result;
  * every ``run_*`` accepts an ``optimize.OptimizedProgram`` and routes it
    to the fused table replay (``lax.scan`` over stacked index tensors on
    the global array) instead of the per-stage ppermute loop — same bits,
    constant-size HLO;
  * ``donate=True`` on the backend donates the wrapper inputs to XLA
    (buffer reuse for callers that hand over ownership — do NOT enable it
    when the same arrays are passed again, e.g. benchmark loops).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.runtime import optimize as _opt
from repro.runtime.program import (
    CollectiveProgram,
    LocalContract,
    Match,
    Perm,
    ReduceCombine,
    check_kind as _check_kind,
)


@dataclasses.dataclass(frozen=True)
class JaxPpermuteBackend:
    """One ppermute per communication stage on a 1-D router-order axis.

    ``overlap_fused=True`` replays all-to-alls through the wave-ordered
    fused-table dispatch: ONE gather of every outgoing chunk up front
    (stacked-σ table in ``start_step`` order), the per-stage ppermutes
    issued wave by wave, and ONE scatter of every arrival at the end — no
    per-stage dynamic-update-slice chain, which is what the sequential
    ``loop`` replay pays 16× over on a host mesh. The same wave order
    drives ``alltoall_compute``, the §3 Schedules 1–3 pipeline where the
    expert compute for wave w-1's arrivals trails one wave behind wave w's
    dispatch.

    ``donate=True`` donates the whole-array wrapper inputs to XLA (callers
    must not reuse the passed buffers afterwards)."""

    overlap: bool = False
    donate: bool = False
    overlap_fused: bool = False
    name: str = "jax_ppermute"

    # ---------------------------------------------------------- per-shard
    def alltoall(self, x: jax.Array, axis_name: str, program: CollectiveProgram) -> jax.Array:
        """All-to-all of per-destination chunks.

        ``x``: (n, ...) local buffer where x[j] is this device's chunk for
        device j. Returns (n, ...) where out[j] is the chunk received FROM
        device j — the ``lax.all_to_all(split_axis=0, concat_axis=0)``
        layout.

        One ppermute per source vector: for vector permutation σ, device i
        contributes x[σ(i)] and the receiver σ(i) stores the arrival at
        index σ⁻¹(σ(i)) = i, its sender. The σ/σ⁻¹ gather indices are
        precomputed on the program (cached per stage), so retraces reuse
        them instead of rebuilding host arrays.
        """
        program = _opt.as_program(program)  # per-shard path replays stages
        _check_kind(program, "alltoall")
        if x.shape[0] != program.n:
            raise ValueError(f"leading dim {x.shape[0]} != mesh axis {program.n}")
        idx = jax.lax.axis_index(axis_name)
        if self.overlap_fused:
            order = [st for w in _wave_stages(program) for st in w]
            sig = jnp.asarray(np.stack([st.sigma_np for st in order]))
            inv = jnp.asarray(np.stack([st.inverse_np for st in order]))
            all_sel = x[sig[:, idx]]  # ONE gather of every outgoing chunk
            recvs = [
                jax.lax.ppermute(all_sel[k], axis_name, st.pairs)
                for k, st in enumerate(order)
            ]
            # ONE scatter: arrivals of idle emulated devices are the zeros
            # ppermute hands non-destinations, written at their own row.
            return jnp.zeros_like(x).at[inv[:, idx]].set(jnp.stack(recvs))
        out = jnp.zeros_like(x)
        for op in self._ordered(program):
            assert isinstance(op, Perm)
            sigma = jnp.asarray(op.sigma_np)
            inv = jnp.asarray(op.inverse_np)
            sel = x[sigma[idx]]
            recv = jax.lax.ppermute(sel, axis_name, op.pairs)
            out = out.at[inv[idx]].set(recv)
        return out

    def alltoall_compute(
        self,
        x: jax.Array,
        axis_name: str,
        program: CollectiveProgram,
        compute=None,
    ) -> jax.Array:
        """Fused round trip: ship chunk x[j] to device j, apply device j's
        ``compute`` there, return the processed chunk to its sender.

        out[j] = compute_j(x[j]) — NOT the all-to-all transpose; with
        ``compute=None`` this is the identity round trip. ``compute`` is
        THIS device's batched chunk transform: called as compute(chunks)
        with chunks (V, ...), the stacked arrivals of one launch wave.

        Waves follow the program's ``start_step`` stamps (§3 Schedules 1-3
        pipelining): wave w's ppermutes are issued BEFORE wave w-1's
        arrivals go through ``compute`` and return over the inverse pairs,
        so the contraction for already-arrived chunks overlaps the next
        wave's network time. ONE gather feeds every dispatch and ONE
        scatter commits every return; the ``pending`` double buffer holds
        exactly one wave of arrivals between issue and drain. Barrier
        (unstamped) programs degenerate to a single wave — all compute
        after all dispatch — so pass a pipelined lowering to overlap."""
        program = _opt.as_program(program)
        _check_kind(program, "alltoall")
        if x.shape[0] != program.n:
            raise ValueError(f"leading dim {x.shape[0]} != mesh axis {program.n}")
        waves = _wave_stages(program)
        order = [st for w in waves for st in w]
        idx = jax.lax.axis_index(axis_name)
        sig = jnp.asarray(np.stack([st.sigma_np for st in order]))
        dests = sig[:, idx]  # stage k ships this device's chunk for σ_k(idx)
        all_sel = x[dests]
        backs: list = [None] * len(order)

        def drain(pending):
            if not pending:
                return
            stacked = jnp.stack([r for _, r in pending])
            ys = stacked if compute is None else compute(stacked)
            for j, (k, _) in enumerate(pending):
                inv_pairs = tuple((d, s) for s, d in order[k].pairs)
                backs[k] = jax.lax.ppermute(ys[j], axis_name, inv_pairs)

        pending: list = []
        k = 0
        for wave in waves:
            newly = []
            for st in wave:
                newly.append((k, jax.lax.ppermute(all_sel[k], axis_name, st.pairs)))
                k += 1
            drain(pending)
            pending = newly
        drain(pending)
        # Idle emulated devices: dests == idx, backs are ppermute zeros —
        # their row is written with zeros and every other row stays zero.
        return jnp.zeros_like(x).at[dests].set(jnp.stack(backs))

    def allreduce(self, x: jax.Array, axis_name: str, program: CollectiveProgram) -> jax.Array:
        """Recursive-doubling all-reduce (sum): one pairwise exchange per
        cube dimension — the §4 ascend algorithm on the emulated
        hypercube."""
        program = _opt.as_program(program)
        _check_kind(program, "allreduce")
        idx = jax.lax.axis_index(axis_name)
        for st in self._ordered(program):
            assert isinstance(st, ReduceCombine)
            recv = jax.lax.ppermute(x, axis_name, st.link_pairs)
            if st.self_mask_np.any():  # local contributions (identity pairs)
                recv = recv + jnp.where(jnp.asarray(st.self_mask_np)[idx], x, 0)
            x = x + recv
        return x

    def broadcast(
        self,
        x: jax.Array,
        axis_name: str,
        program: CollectiveProgram,
        *,
        pipelined: bool = False,
    ) -> jax.Array:
        """Spanning-tree broadcast from ``program.root``: each stage is a
        masked partial ppermute; non-receivers keep their value, so after
        the last stage every device holds the root's value.

        Multi-round (pipelined wave) programs take ``x`` with a leading
        wave dim (num_rounds, ...); wave w's tree moves slice x[w].
        ``pipelined=True`` (or ``overlap`` on the backend) replays in
        start_step order — cross-round overlap where start_step permits."""
        program = _opt.as_program(program)
        _check_kind(program, "broadcast")
        idx = jax.lax.axis_index(axis_name)
        waves = program.num_rounds > 1
        val = x
        for group in program.step_groups(pipelined=pipelined or self.overlap):
            pre = val
            for st in group:
                assert isinstance(st, Match)
                sent = pre[st.round_index] if waves else pre
                recv = jax.lax.ppermute(sent, axis_name, st.pairs)
                mask = jnp.asarray(st.dst_mask_np)[idx]
                if waves:
                    val = val.at[st.round_index].set(
                        jnp.where(mask, recv, val[st.round_index])
                    )
                else:
                    val = jnp.where(mask, recv, val)
        return val

    def matmul(
        self, b: jax.Array, a: jax.Array, axis_name: str, program: CollectiveProgram
    ) -> jax.Array:
        """§2 block product: ``b``/``a`` are this device's (X, X) blocks of
        B and A in the paper's storage map; returns the device's (X, X)
        block of B @ A. Per-device state is (val, acc) driven by the
        program's LocalContract stages; every hop is a ppermute — no
        ``all_gather``, the HLO shows Theorem 1's round structure."""
        program = _opt.as_program(program)
        _check_kind(program, "matmul")
        idx = jax.lax.axis_index(axis_name)
        dtype = jnp.result_type(b, a)
        val = jnp.zeros(b.shape, dtype)
        acc = jnp.zeros(b.shape, dtype)
        c = jnp.zeros(b.shape, dtype)
        for group in program.step_groups(pipelined=self.overlap):
            if isinstance(group[0], LocalContract):
                (st,) = group
                if st.fn == "load_b":
                    val = b.astype(dtype)
                    acc = jnp.zeros_like(acc)
                elif st.fn == "mul_a":
                    val = val @ a.astype(dtype)  # the off-network block product
                    acc = jnp.zeros_like(acc)
                elif st.fn == "promote":
                    val, acc = acc, jnp.zeros_like(acc)
                elif st.fn == "store_c":
                    c = jnp.where(jnp.asarray(st.mask_np)[idx], val, c)
                continue
            pre = val
            for st in group:
                if isinstance(st, Match):
                    recv = jax.lax.ppermute(pre, axis_name, st.pairs)
                    val = jnp.where(jnp.asarray(st.dst_mask_np)[idx], recv, val)
                elif isinstance(st, ReduceCombine):
                    recv = jax.lax.ppermute(pre, axis_name, st.link_pairs)
                    if st.self_mask_np.any():
                        recv = recv + jnp.where(
                            jnp.asarray(st.self_mask_np)[idx], pre, 0
                        )
                    acc = acc + recv
                else:  # pragma: no cover - lowering never emits Perm here
                    raise TypeError(f"unexpected stage {st!r} in matmul program")
        return c

    def _ordered(self, program: CollectiveProgram):
        return program.pipelined_stages() if self.overlap else program.stages

    # ------------------------------------------------- whole-array wrappers
    def run_alltoall(
        self, x_global, program, axis_name: str = "df", mesh: Mesh | None = None
    ):
        """x_global: (n, n, ...) where x_global[i, j] is the chunk device i
        sends to device j; returns (n, n, ...) with out[i, j] =
        x_global[j, i, ...] moved by the paper's round schedule.

        ``OptimizedProgram`` inputs take the fused table replay on the
        GLOBAL array — there is no shard_map, so ``axis_name``/``mesh``
        do not apply on that path (``donate`` still does)."""
        if isinstance(program, _opt.OptimizedProgram):
            _check_kind(program.program, "alltoall")
            if self.overlap_fused:
                return _opt.jax_alltoall_overlapped(
                    program, donate=self.donate)(x_global)
            return _opt.jax_alltoall(program, self.donate)(x_global)
        return _compiled_collective(self, program, "alltoall", axis_name, mesh,
                                    False)(x_global)

    def run_alltoall_compute(
        self,
        x_global,
        program,
        compute=None,
        weights=(),
        axis_name: str = "df",
        mesh: Mesh | None = None,
    ):
        """x_global: (n, n, ...) with x_global[i, j] the chunk device i sends
        to device j; returns out[i, j] = compute_j(x_global[i, j]) — every
        chunk processed AT its destination j and returned to its sender
        (round trip), NOT the all-to-all transpose.

        ``compute(chunks, *wl)`` runs per shard: chunks is one wave's (V,
        ...) stacked arrivals and ``wl`` holds the device's row of every
        array in ``weights`` (each (n, ...), sharded over the axis). The
        jitted shard_map closure is cached per (backend, program, compute,
        arity) — pass a stable ``compute`` callable, not a per-call lambda,
        to reuse the compiled executable."""
        prog = _opt.as_program(program)
        _check_kind(prog, "alltoall")
        return _compiled_alltoall_compute(
            self, prog, compute, len(weights), axis_name, mesh
        )(x_global, *weights)

    def run_allreduce(
        self, x_global, program, axis_name: str = "df", mesh: Mesh | None = None
    ):
        if isinstance(program, _opt.OptimizedProgram):
            _check_kind(program.program, "allreduce")
            return _opt.jax_allreduce(program, self.donate)(x_global)
        return _compiled_collective(self, program, "allreduce", axis_name,
                                    mesh, False)(x_global)

    def run_broadcast(
        self,
        x_global,
        program,
        axis_name: str = "df",
        mesh: Mesh | None = None,
        *,
        pipelined: bool = False,
    ):
        """Single round: x (n, ...). Pipelined waves: x (R, n, ...) with the
        device axis second. Optimized programs replay their fused tables on
        the global array (``axis_name``/``mesh`` do not apply) — barrier
        order, bit-identical to the pipelined result."""
        if isinstance(program, _opt.OptimizedProgram):
            _check_kind(program.program, "broadcast")
            return _opt.jax_broadcast(program, self.donate)(x_global)
        return _compiled_collective(self, program, "broadcast", axis_name,
                                    mesh, pipelined)(x_global)

    def run_matmul(
        self, B, A, program, axis_name: str = "df", mesh: Mesh | None = None
    ):
        """B, A: (N·X, N·X) matrices -> B @ A via the §2 rounds on a mesh of
        ``program.n`` devices in router order. Emulated programs scatter the
        guest's blocks to their ``active_devices`` slots of the host mesh
        (grid metadata is the GUEST grid) and gather them back. The whole
        scatter -> replay -> gather pipeline is one cached jit — blocks
        never round-trip through the host; the caller materializes the
        returned device array when it actually needs the bytes."""
        prog = _opt.as_program(program)
        _check_kind(prog, "matmul")
        if prog.grid is None:
            raise ValueError("matmul program lacks grid metadata")
        return _compiled_matmul(self, program, axis_name, mesh)(B, A)


@functools.lru_cache(maxsize=None)
def _wave_stages(program: CollectiveProgram) -> tuple[tuple[Perm, ...], ...]:
    """Stages grouped by launch wave — one tuple per distinct ``start_step``
    value, waves in launch order, stage order preserved inside a wave.
    Barrier (unstamped) programs collapse to a single wave. Mirrors
    ``core.alltoall.wave_rounds`` at the lowered-program level."""
    waves: dict[int, list[Perm]] = {}
    for st in program.pipelined_stages():
        assert isinstance(st, Perm)
        waves.setdefault(st.start_step, []).append(st)
    return tuple(tuple(waves[s]) for s in sorted(waves))


@functools.lru_cache(maxsize=None)
def _compiled_alltoall_compute(backend: JaxPpermuteBackend,
                               program: CollectiveProgram, compute,
                               n_weights: int, axis_name: str,
                               mesh: Mesh | None):
    """Jitted shard_map closure for the fused dispatch+compute round trip,
    cached per (backend, program, compute, weight arity, axis, mesh)."""
    _check_kind(program, "alltoall")
    mesh = mesh or _axis_mesh(program.n, axis_name)

    def local(s, *ws):
        wl = [w[0] for w in ws]
        fn = None if compute is None else (lambda chunks: compute(chunks, *wl))
        return backend.alltoall_compute(s[0], axis_name, program, fn)[None]

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name),) * (1 + n_weights),
        out_specs=P(axis_name),
    )
    donate = (0,) if backend.donate else ()
    return jax.jit(f, donate_argnums=donate)


@functools.lru_cache(maxsize=None)
def _axis_mesh(n: int, axis_name: str) -> Mesh:
    """1-D device mesh in router order, cached per (n, axis) — the device
    list is fixed for the process lifetime."""
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices for the lowered program, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis_name,))


@functools.lru_cache(maxsize=None)
def _compiled_collective(backend: JaxPpermuteBackend, program: CollectiveProgram,
                         kind: str, axis_name: str, mesh: Mesh | None,
                         pipelined: bool):
    """Jitted shard_map closure for a whole-array replay, cached per
    (backend, program, axis, mesh, flags) so repeated collective calls
    don't rebuild the mesh or retrace (programs and Mesh are hashable)."""
    _check_kind(program, kind)
    mesh = mesh or _axis_mesh(program.n, axis_name)
    donate = (0,) if backend.donate else ()
    if kind == "broadcast":
        waves = program.num_rounds > 1
        spec = P(None, axis_name) if waves else P(axis_name)

        def local(s):
            s = s[:, 0] if waves else s[0]
            out = backend.broadcast(s, axis_name, program, pipelined=pipelined)
            return out[:, None] if waves else out[None]

        f = jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
        return jax.jit(f, donate_argnums=donate)

    method = backend.alltoall if kind == "alltoall" else backend.allreduce
    f = jax.shard_map(
        lambda s: method(s[0], axis_name, program)[None],
        mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
    )
    return jax.jit(f, donate_argnums=donate)


@functools.lru_cache(maxsize=None)
def _compiled_matmul(backend: JaxPpermuteBackend, program, axis_name: str,
                     mesh: Mesh | None):
    """One jitted closure per (backend, program): jnp block scatter (+ guest
    scatter for emulated programs) -> per-shard replay (or the fused table
    scan for ``OptimizedProgram``) -> jnp gather. No host syncs inside."""
    prog = _opt.as_program(program)
    grid = prog.grid
    if isinstance(program, _opt.OptimizedProgram):
        replay = _opt.build_jax_matmul(program)
    else:
        m = mesh or _axis_mesh(prog.n, axis_name)
        replay = jax.shard_map(
            lambda bb, aa: backend.matmul(bb[0], aa[0], axis_name, program)[None],
            mesh=m, in_specs=(P(axis_name), P(axis_name)),
            out_specs=P(axis_name),
        )

    def f(B, A):
        b = _opt.jax_scatter_guest(_opt.jax_scatter_blocks(B, grid), prog)
        a = _opt.jax_scatter_guest(_opt.jax_scatter_blocks(A, grid), prog)
        c = replay(b, a)
        return _opt.jax_gather_blocks(_opt.jax_gather_guest(c, prog), grid)

    donate = (0, 1) if backend.donate else ()
    return jax.jit(f, donate_argnums=donate)
