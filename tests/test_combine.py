"""Concurrent guests: the ``runtime.combine`` program combinator.

Acceptance (ISSUE 5): two disjoint D3(2,2) guests combined onto a D3(4,4)
host replay bit-exact vs their solo runs on both the reference and
jax_ppermute backends, the combined program passes the Schedule-IR
conflict check, and the combined makespan beats the time-multiplexed sum
(rounds asserted here; wall time in ``benchmarks.run
bench_concurrent_guests``). The mesh-backed (32 forced devices) replay of
a combined program lives in ``program_check_script.py``.
"""

import numpy as np
import pytest

from repro.core import alltoall as a2a
from repro.core import broadcast as bc
from repro.core import hypercube as hc
from repro.core import matmul as mm
from repro.core.emulation import disjoint_embeddings, embed
from repro.core.simulator import verify
from repro.core.topology import D3
from repro.dist.mesh import DeviceLayout
from repro.runtime import lowering
from repro.runtime.backends.reference import NumpyReferenceBackend
from repro.runtime.combine import (
    GuestConflictError,
    check_step_conflicts,
    combine,
    combine_schedules,
    extract_guest,
    gather_guests,
    scatter_guests,
)
from repro.runtime.optimize import optimize
from repro.runtime.program import CollectiveProgram, Perm
from repro.runtime.rewrite import emulate, emulate_schedule, scatter_guest

REF = NumpyReferenceBackend()
HOST = D3(4, 4)
GUEST = DeviceLayout(D3(2, 2))
EMBS = disjoint_embeddings(HOST, [(2, 2), (2, 2)])


def _a2a_prog():
    return lowering.lower(a2a.schedule(GUEST.da_params, GUEST.topo))


def _combined_alltoall():
    prog = _a2a_prog()
    return prog, [emulate(prog, e) for e in EMBS]


# ------------------------------------------------------------- acceptance
def test_two_guests_bit_exact_vs_solo_on_reference_and_jax():
    """The headline: one combined replay == two solo replays, per guest,
    on the reference backend (per-stage AND fused) and on the jax_ppermute
    backend (fused global replay — the meshless OptimizedProgram path)."""
    from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend

    prog, solos = _combined_alltoall()
    comb = combine(solos)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((prog.n, prog.n, 3)).astype(np.float32)
          for _ in EMBS]
    wants = [REF.run_alltoall(x, prog) for x in xs]

    xh = scatter_guests(xs, EMBS, axes=(0, 1))
    out_ref = REF.run_alltoall(xh, comb)
    out_opt = REF.run_alltoall(xh, optimize(comb))
    out_jax = np.asarray(
        JaxPpermuteBackend().run_alltoall(xh, optimize(comb)))
    np.testing.assert_array_equal(out_opt, out_ref)
    np.testing.assert_array_equal(out_jax, out_ref)
    for e, want in zip(EMBS, wants):
        np.testing.assert_array_equal(
            extract_guest(out_ref, e, axes=(0, 1)), want)
    # idle rows/cols of the 64-device host stay zero
    idle = ~comb.active_mask_np
    assert not out_ref[idle].any() and not out_ref[:, idle].any()


def test_combined_program_passes_schedule_ir_conflict_check():
    """The merged host-graph Schedule replays conflict-free through
    ``core.simulator.verify`` — the same checker every algorithm's tests
    use — for all three comm kinds."""
    scheds = {
        "alltoall": a2a.schedule(GUEST.da_params, GUEST.topo),
        "allreduce": hc.allreduce_schedule(GUEST.sbh),
        "broadcast": bc.depth3_schedule(GUEST.topo, (0, 1, 0)),
    }
    for kind, sched in scheds.items():
        merged = combine_schedules([emulate_schedule(sched, e) for e in EMBS])
        assert merged.topo == HOST
        merged.validate()  # every hop is a physical host link
        verify(HOST, merged).raise_on_conflict(f"combined {kind}")
        # payloads are namespaced by guest, so coverage is attributable
        assert all(p[0] in (0, 1) for r in merged.rounds
                   for p in r.payloads())


def test_combine_schedules_preserves_pipelined_stamps_across_shapes():
    """Mixed-SHAPE pipelined guests disagree on per-round start_steps; the
    merged schedule keeps each guest's own launch offsets, so pipelined
    verify stays conflict-free instead of spuriously colliding at 0."""
    embs = disjoint_embeddings(HOST, [(2, 2), (2, 4)])
    scheds = []
    for e in embs:
        lay = DeviceLayout(e.guest)
        s = a2a.pipelined_schedule(lay.da_params, offset=1, topo=lay.topo)
        verify(lay.topo, s, pipelined=True).raise_on_conflict("solo")
        scheds.append(emulate_schedule(s, e))
    merged = combine_schedules(scheds)
    verify(HOST, merged).raise_on_conflict("combined barrier")
    verify(HOST, merged, pipelined=True).raise_on_conflict("combined pipelined")
    want = sorted({r.meta["start_step"] for s in scheds for r in s.rounds
                   if "start_step" in r.meta})
    got = sorted({r.meta["start_step"] for r in merged.rounds
                  if "start_step" in r.meta})
    assert got == want  # every guest's launch offset survived the merge


def test_combined_makespan_is_max_not_sum():
    _, solos = _combined_alltoall()
    comb = combine(solos)
    assert comb.num_rounds == max(p.num_rounds for p in solos)
    assert comb.num_rounds < sum(p.num_rounds for p in solos)
    # and the packing is perfect for same-shape guests: same stage count
    # as ONE guest — every merged Perm carries both guests' pairs
    assert len(comb.stages) == len(solos[0].stages)
    for merged, s0, s1 in zip(comb.stages, solos[0].stages, solos[1].stages):
        assert isinstance(merged, Perm) and merged.is_partial
        assert set(merged.pairs) == set(s0.pairs) | set(s1.pairs)
        assert (merged.round_index, merged.step, merged.start_step) == \
            (s0.round_index, s0.step, s0.start_step)


# ----------------------------------------------------- other kinds
def test_combined_allreduce_and_broadcast_bit_exact():
    rng = np.random.default_rng(1)
    ar = lowering.lower(hc.allreduce_schedule(GUEST.sbh))
    comb = combine([emulate(ar, e) for e in EMBS])
    ys = [rng.standard_normal((ar.n, 4)) for _ in EMBS]
    yh = scatter_guests(ys, EMBS, fill=9.25)  # idle garbage must pass through
    out = REF.run_allreduce(yh, comb)
    np.testing.assert_array_equal(REF.run_allreduce(yh, optimize(comb)), out)
    for e, y in zip(EMBS, ys):
        np.testing.assert_array_equal(extract_guest(out, e),
                                      REF.run_allreduce(y, ar))
    np.testing.assert_array_equal(out[~comb.active_mask_np], 9.25)

    # two broadcasts with DIFFERENT per-guest roots in one replay
    b1 = lowering.lower(bc.depth3_schedule(GUEST.topo, (0, 1, 0)))
    b2 = lowering.lower(bc.depth3_schedule(GUEST.topo, (1, 0, 1)))
    comb = combine([emulate(b1, EMBS[0]), emulate(b2, EMBS[1])])
    assert comb.root is None  # per-guest roots live on the solo programs
    zs = [rng.standard_normal((b1.n, 2)), rng.standard_normal((b2.n, 2))]
    zh = scatter_guests(zs, EMBS, fill=-3.0)
    out = REF.run_broadcast(zh, comb)
    np.testing.assert_array_equal(REF.run_broadcast(zh, optimize(comb)), out)
    np.testing.assert_array_equal(extract_guest(out, EMBS[0]),
                                  REF.run_broadcast(zs[0], b1))
    np.testing.assert_array_equal(extract_guest(out, EMBS[1]),
                                  REF.run_broadcast(zs[1], b2))


def test_combined_matmul_blocks_bit_exact_and_skeleton_guard():
    """Two grid-(1,2) guests multiplex one host at the blocks level; a
    shape-mismatched matmul guest is rejected (local-contract stages act
    on every device, so skeletons must agree)."""
    g = mm.MatmulGrid(1, 2)
    prog = lowering.lower(mm.schedule(g))
    embs = disjoint_embeddings(HOST, [(1, 2), (1, 2)])
    solos = [emulate(prog, e) for e in embs]
    comb = combine(solos)
    assert comb.grid == (1, 2)
    rng = np.random.default_rng(2)
    X = 3
    from repro.core.matmul import scatter_blocks

    Bs = [rng.integers(-4, 5, (g.n * X, g.n * X)).astype(np.float64)
          for _ in embs]
    As = [rng.integers(-4, 5, (g.n * X, g.n * X)).astype(np.float64)
          for _ in embs]
    bh = scatter_guests([scatter_blocks(g, B) for B in Bs], embs)
    ah = scatter_guests([scatter_blocks(g, A) for A in As], embs)
    c = REF.matmul_blocks(bh, ah, comb)
    np.testing.assert_array_equal(REF.matmul_blocks(bh, ah, optimize(comb)), c)
    for e, B, A, solo in zip(embs, Bs, As, solos):
        want = REF.matmul_blocks(
            scatter_guest(scatter_blocks(g, B), solo),
            scatter_guest(scatter_blocks(g, A), solo), solo)
        np.testing.assert_array_equal(extract_guest(c, e),
                                      extract_guest(want, e))

    other = lowering.lower(mm.schedule(mm.MatmulGrid(2, 2)))
    with pytest.raises(GuestConflictError, match="skeleton"):
        combine([solos[0],
                 emulate(other, embed(HOST, 4, 2, p_set=(2, 3)))])


def test_run_matmul_guests_whole_matrix_wrapper():
    """``run_matmul_guests``: N whole (N·X, N·X) products through one
    combined blocks-level replay — each guest's result equals its plain
    ``B @ A``, and the guardrails (count mismatch, wrong kind, backend
    without ``matmul_blocks``) raise informatively."""
    from repro.runtime.combine import run_matmul_guests

    g = mm.MatmulGrid(1, 2)
    embs = disjoint_embeddings(HOST, [(1, 2), (1, 2)])
    comb = combine([emulate(lowering.lower(mm.schedule(g)), e) for e in embs])
    rng = np.random.default_rng(3)
    side = g.n * 3
    Bs = [rng.integers(-4, 5, (side, side)).astype(np.float64) for _ in embs]
    As = [rng.integers(-4, 5, (side, side)).astype(np.float64) for _ in embs]
    Cs = run_matmul_guests(REF, Bs, As, comb, embs)
    for B, A, C in zip(Bs, As, Cs):
        np.testing.assert_array_equal(C, B @ A)

    with pytest.raises(ValueError, match="guests"):
        run_matmul_guests(REF, Bs[:1], As, comb, embs)
    comb_a2a = combine(_combined_alltoall()[1])
    with pytest.raises(ValueError, match="matmul"):
        run_matmul_guests(REF, Bs, As, comb_a2a, embs)

    class NoBlocks:
        name = "noblocks"

    with pytest.raises(ValueError, match="matmul_blocks"):
        run_matmul_guests(NoBlocks(), Bs, As, comb, embs)


# ------------------------------------------------------------ validation
def test_overlapping_images_raise_structured_error():
    prog, solos = _combined_alltoall()
    clash = emulate(prog, embed(HOST, 2, 2, c_set=(1, 2), p_set=(0, 1)))
    with pytest.raises(GuestConflictError) as ei:
        combine([solos[0], clash])
    assert ei.value.guests == (0, 1)
    assert ei.value.device in solos[0].active_devices
    assert ei.value.device in clash.active_devices


def test_step_conflict_check_reports_step_and_link():
    """Defense in depth: disjoint images but hand-built stages that reach
    outside them are caught by the cross-guest step re-check."""
    a = CollectiveProgram(
        "alltoall", 4, 1, (Perm(((0, 2), (2, 0)), n=4),),
        active_devices=(0, 1))
    b = CollectiveProgram(
        "alltoall", 4, 1, (Perm(((0, 2), (2, 0)), n=4),),
        active_devices=(2, 3))
    with pytest.raises(GuestConflictError) as ei:
        check_step_conflicts([a, b])
    assert ei.value.step == (0, 0)
    assert ei.value.link == (0, 2)
    assert ei.value.guests == (0, 1)
    with pytest.raises(GuestConflictError, match="overlap|link|write"):
        combine([a, b])


def test_cross_guest_reduce_combine_write_is_rejected():
    """A guest's ReduceCombine folding into ANOTHER guest's device is a
    conflict (intra-guest repeated RC destinations stay legal) — whatever
    the start_step stamps, the structured error fires before any merge
    could corrupt the victim's bits."""
    from repro.runtime.program import ReduceCombine

    a = CollectiveProgram(
        "allreduce", 4, 1, (ReduceCombine(4, ((0, 2),)),),
        active_devices=(0, 2))
    for start in (0, 1):
        b = CollectiveProgram(
            "allreduce", 4, 1,
            (ReduceCombine(4, ((1, 2),), start_step=start),),
            active_devices=(1, 3))
        with pytest.raises(GuestConflictError) as ei:
            combine([a, b])
        assert ei.value.guests == (0, 1) and ei.value.step == (0, 0)
        assert ei.value.device == 2  # the doubly-written accumulator
    # identity (self) RC pairs use no link but DO write: a foreign Perm
    # landing on that accumulator in the same step is a conflict too
    p = CollectiveProgram(
        "allreduce", 4, 1, (ReduceCombine(4, ((1, 3),)),),
        active_devices=(1, 2))
    q = CollectiveProgram(
        "allreduce", 4, 1, (ReduceCombine(4, ((3, 3),)),),
        active_devices=(0, 3))
    with pytest.raises(GuestConflictError, match="write device 3") as ei:
        combine([p, q])
    assert ei.value.device == 3 and ei.value.link is None  # no link used


def test_combine_rejects_mixed_kinds_sizes_and_native_programs():
    prog, solos = _combined_alltoall()
    with pytest.raises(ValueError, match="kinds"):
        combine([solos[0],
                 emulate(lowering.lower(hc.allreduce_schedule(GUEST.sbh)),
                         EMBS[1])])
    native_host = CollectiveProgram(
        "alltoall", HOST.num_routers, 1,
        (Perm(tuple((i, i) for i in range(HOST.num_routers))),))
    with pytest.raises(ValueError, match="native"):
        combine([solos[0], native_host])
    small = emulate(prog, embed(D3(2, 4), 2, 2, p_set=(0, 2)))
    with pytest.raises(ValueError, match="host-sized"):
        combine([solos[0], small])
    with pytest.raises(ValueError, match="at least one"):
        combine([])
    assert combine([solos[0]]) is solos[0]  # single guest passes through
    with pytest.raises(ValueError, match="native"):
        combine([native_host])  # ... but only after validation


def test_combine_is_cached():
    _, solos = _combined_alltoall()
    assert combine(solos) is combine(tuple(solos))


# -------------------------------------------------- enumerator + movement
def test_disjoint_embeddings_regimes():
    # cabinet regime: ΣJ ≤ K, every guest keeps its full position prefix
    embs = disjoint_embeddings(D3(4, 4), [(2, 2), (2, 2)])
    assert [e.c_set for e in embs] == [(0, 1), (2, 3)]
    # position regime: ΣJ > K but ΣL ≤ M
    embs = disjoint_embeddings(D3(2, 4), [(2, 2), (2, 2)])
    assert [e.p_set for e in embs] == [(0, 1), (2, 3)]
    images = [set(map(int, e.device_map)) for e in embs]
    assert not images[0] & images[1]
    # three tenants of mixed shape on the cabinet axis
    embs = disjoint_embeddings(D3(4, 4), [(1, 2), (2, 4), (1, 3)])
    assert [e.c_set for e in embs] == [(0,), (1, 2), (3,)]
    with pytest.raises(ValueError, match="pack"):
        disjoint_embeddings(D3(2, 2), [(2, 2), (1, 1)])
    with pytest.raises(ValueError, match="fit"):
        disjoint_embeddings(D3(2, 2), [(3, 1)])


def test_scatter_gather_guests_roundtrip_and_host_to_guest_extraction():
    prog, solos = _combined_alltoall()
    comb = combine(solos)
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal((prog.n, prog.n, 2)) for _ in EMBS]
    xh = scatter_guests(xs, EMBS, axes=(0, 1), fill=5.0)
    assert xh.shape == (comb.n, comb.n, 2)
    outs = gather_guests(xh, EMBS, axes=(0, 1))
    for x, o in zip(xs, outs):
        np.testing.assert_array_equal(o, x)
    # extraction via a solo program (active_devices) == via the embedding
    # (host_to_guest) — the two guest views coincide
    np.testing.assert_array_equal(
        extract_guest(xh, solos[0], axes=(0, 1)),
        extract_guest(xh, EMBS[0], axes=(0, 1)))
    idle = ~comb.active_mask_np
    np.testing.assert_array_equal(xh[idle], 5.0)
    # the fill participates in the output dtype: integer guests with a
    # fractional sentinel widen instead of silently truncating the fill
    ints = [np.arange(prog.n, dtype=np.int32) for _ in EMBS]
    ih = scatter_guests(ints, EMBS, fill=9.25)
    assert ih.dtype == np.float64
    np.testing.assert_array_equal(ih[idle], 9.25)
    with pytest.raises(ValueError, match="slots"):
        scatter_guests([xs[0][:3]], [EMBS[0]])
    with pytest.raises(ValueError, match="guests"):
        scatter_guests(xs, [EMBS[0]])


# ------------------------------------------------- dist getters + failover
def test_concurrent_program_getters_cached_and_optimized():
    from repro.dist import collectives as coll

    prog = coll.concurrent_program("alltoall", EMBS)
    assert prog is coll.concurrent_program("alltoall", EMBS)
    assert prog.guest_n == 2 * GUEST.n and prog.n == HOST.num_routers
    opt = coll.concurrent_program("alltoall", EMBS, optimized=True)
    assert opt.program is prog
    suite = coll.concurrent_programs(EMBS, roots=(0, 3))
    assert set(suite) == {"alltoall", "allreduce", "broadcast"}
    # matmul-incapable shapes skip the kind instead of failing the suite
    assert "matmul" not in coll.concurrent_programs(
        EMBS, kinds=("alltoall", "matmul"))
    with pytest.raises(ValueError, match="roots"):
        coll.concurrent_program("broadcast", EMBS, roots=(0,))
    with pytest.raises(ValueError, match="roots"):  # not a silent {} suite
        coll.concurrent_programs(EMBS, roots=(0,))
    # malformed tenant sets raise instead of thinning the suite: these two
    # embeddings target DIFFERENT hosts
    mixed = (EMBS[0], embed(D3(2, 4), 2, 2, p_set=(0, 2)))
    with pytest.raises(ValueError, match="host-sized"):
        coll.concurrent_programs(mixed)
    # degenerate single-router tenants: no hypercube to reduce over — the
    # kind is skipped, not crashed on
    ones = disjoint_embeddings(HOST, [(1, 1), (1, 1)])
    assert set(coll.concurrent_programs(ones)) == {"alltoall", "broadcast"}
    # individually matmul-capable but differently-shaped tenants: matmul
    # is skipped (no shared skeleton) without losing the rest of the suite
    mixed_grids = disjoint_embeddings(HOST, [(1, 2), (4, 2)])
    suite = coll.concurrent_programs(mixed_grids, kinds=("alltoall", "matmul"))
    assert set(suite) == {"alltoall"}


def test_prepare_shape_refuses_mixed_roots():
    """The (J, L) shape library is root-stamped: a cache hit under a
    different broadcast root raises instead of serving wrong-root bits."""
    from repro.train.fault_tolerance import ClusterState

    cs = ClusterState(DeviceLayout(HOST))
    suite = cs.prepare_shape(2, 2, root=3)
    assert suite.root == 3
    assert cs.prepare_shape(2, 2, root=3) is suite  # idempotent per root
    with pytest.raises(ValueError, match="broadcast root"):
        cs.prepare_shape(2, 2)  # default root=0 on a root-3 cache entry


def test_multitenant_eviction_recombines_without_rederiving(monkeypatch):
    """A failure inside one tenant's image evicts ONLY that tenant; the
    survivor keeps its (cached) rewritten programs and the re-combination
    never calls a core derivation or the lowering."""
    from repro.train.fault_tolerance import MultiTenantCluster

    mt = MultiTenantCluster(DeviceLayout(HOST))
    for e in EMBS:
        mt.admit(e)
    with pytest.raises(ValueError, match="overlaps"):
        mt.admit(embed(HOST, 2, 2, c_set=(1, 2), p_set=(0, 1)))

    healthy = mt.plan_eviction()
    assert healthy.surviving == (0, 1) and healthy.evicted == ()
    assert set(healthy.programs) == {"alltoall", "allreduce", "broadcast"}
    assert healthy.programs["alltoall"].guest_n == 2 * GUEST.n
    # explicit kinds intersect with what the survivors support
    assert set(mt.plan_eviction(kinds=["alltoall", "matmul"]).programs) == \
        {"alltoall"}

    def _boom(*a, **k):
        raise AssertionError("eviction path called into a derivation")

    monkeypatch.setattr(a2a, "schedule", _boom)
    monkeypatch.setattr(bc, "depth3_schedule", _boom)
    monkeypatch.setattr(hc, "allreduce_schedule", _boom)
    monkeypatch.setattr(lowering, "lower", _boom)

    mt.fail(int(EMBS[1].device_map[2]))
    plan = mt.plan_eviction()
    assert plan.surviving == (0,) and plan.evicted == (1,)
    # the evictee was UNSEATED: a replacement of a prepared shape avoiding
    # the dead chip can take over the freed cabinets (same derive-once
    # library entry, so even with derivations boomed admit succeeds)
    assert mt.tenants == [EMBS[0]]
    replacement = embed(HOST, 2, 2, c_set=(2, 3), p_set=(2, 3))
    assert mt.admit(replacement) == 1
    assert set(mt.plan_eviction().surviving) == {0, 1}
    mt.tenants = [EMBS[0]]  # back to one survivor for the drain check below
    # a newcomer cannot be seated on chips already marked failed
    fresh = MultiTenantCluster(DeviceLayout(HOST))
    fresh.fail(int(EMBS[1].device_map[2]))
    with pytest.raises(ValueError, match="failed host devices"):
        fresh.admit(EMBS[1])
    assert plan.embeddings == (EMBS[0],)
    # the survivor's combined program IS its cached solo rewrite
    solo = emulate(mt.library[(2, 2)].programs["alltoall"], EMBS[0])
    assert plan.programs["alltoall"] is solo
    assert plan.index_maps[0] == {g: int(h)
                                  for g, h in enumerate(EMBS[0].device_map)}
    with pytest.raises(RuntimeError, match="no tenant"):
        for e in EMBS:
            for h in e.device_map:
                mt.dead.add(HOST.id_router(int(h)))
        mt.plan_eviction()


# ----------------------------------------------- a tenant leaves the host
def _solo_program(kind):
    """The guest's program, derived and lowered here, apart from the
    cluster's library."""
    if kind == "alltoall":
        return _a2a_prog()
    if kind == "allreduce":
        return lowering.lower(hc.allreduce_schedule(GUEST.sbh))
    return lowering.lower(bc.depth3_schedule(GUEST.topo, GUEST.topo.id_router(0)))


# kind -> (replay, device axes of its input, fill of the idle devices)
_REPLAY = {
    "alltoall": (REF.run_alltoall, (0, 1), 0),
    "allreduce": (REF.run_allreduce, (0,), 9.25),
    "broadcast": (REF.run_broadcast, (0,), -3.0),
}


@pytest.mark.parametrize("cause", ["fail", "release"])
@pytest.mark.parametrize("kind", ["alltoall", "allreduce", "broadcast"])
def test_survivor_replays_bit_exact_after_eviction(kind, cause):
    """A tenant leaves, by a failed chip in its image or by ``release``:
    the survivor's slice of the re-combined program's replay is, bit for
    bit, its solo emulated program's replay, what the two-tenant program
    gave it, and the guest-sized program's replay."""
    from repro.train.fault_tolerance import MultiTenantCluster

    mt = MultiTenantCluster(DeviceLayout(HOST))
    for e in EMBS:
        mt.admit(e)
    both = mt.plan_eviction().programs[kind]
    if cause == "fail":
        mt.fail(int(EMBS[1].device_map[0]))
        plan = mt.plan_eviction()
    else:
        plan = mt.release(1)
    assert plan.surviving == (0,) and plan.evicted == (1,)
    assert plan.embeddings == (EMBS[0],) and mt.tenants == [EMBS[0]]

    replay, axes, fill = _REPLAY[kind]
    solo = _solo_program(kind)
    rng = np.random.default_rng(3)
    shape = (solo.n, solo.n, 3) if kind == "alltoall" else (solo.n, 4)
    xs = [rng.standard_normal(shape).astype(np.float32) for _ in EMBS]
    alone = scatter_guests(xs[:1], EMBS[:1], axes=axes, fill=fill)

    def survivor(x, program):
        return extract_guest(replay(x, program), EMBS[0], axes=axes)

    want = survivor(alone, emulate(solo, EMBS[0]))
    np.testing.assert_array_equal(survivor(alone, plan.programs[kind]), want)
    np.testing.assert_array_equal(
        survivor(scatter_guests(xs, EMBS, axes=axes, fill=fill), both), want)
    np.testing.assert_array_equal(replay(xs[0], solo), want)


def test_release_last_tenant_leaves_an_empty_plan():
    """Releasing every tenant is legal: the last plan keeps no survivor
    and no program, and the freed routers seat a tenant again."""
    from repro.train.fault_tolerance import MultiTenantCluster

    mt = MultiTenantCluster(DeviceLayout(HOST))
    for e in EMBS:
        mt.admit(e)
    first = mt.release(0)
    assert first.surviving == (1,) and first.embeddings == (EMBS[1],)
    assert set(first.programs) == {"alltoall", "allreduce", "broadcast"}
    last = mt.release(0)  # positions are re-counted after each release
    assert last.surviving == () and last.evicted == (0,)
    assert last.embeddings == () and last.index_maps == ()
    assert last.programs == {}
    assert mt.tenants == []
    assert mt.admit(EMBS[1]) == 0


def test_release_rejects_an_out_of_range_index():
    """``release`` takes a seated tenant's position; any other index
    raises ``IndexError`` and unseats nobody."""
    from repro.train.fault_tolerance import MultiTenantCluster

    mt = MultiTenantCluster(DeviceLayout(HOST))
    for e in EMBS:
        mt.admit(e)
    for bad in (len(EMBS), -1):
        with pytest.raises(IndexError, match="out of range"):
            mt.release(bad)
    assert mt.tenants == list(EMBS)
    with pytest.raises(IndexError, match="0 seated"):
        MultiTenantCluster(DeviceLayout(HOST)).release(0)
