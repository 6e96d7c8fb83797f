"""Backend equivalence checks — run as a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=32 (set before jax import;
see test_runtime_program.py). Exits 0 on success.

The acceptance bar:

  * the NumPy reference backend and the JAX ppermute backend agree
    bit-for-bit on all four algorithms' programs at (K,M) ∈ {(4,2), (2,4)};
  * ``dragonfly_matmul`` executes the §2 rounds via the program executor —
    bit-exact vs ``jnp.einsum`` on a CPU device mesh, and its HLO contains
    collective-permutes but NO all-gather;
  * pipelined (start_step-ordered) execution of the §5 wave schedule on
    devices is bit-identical to barrier replay;
  * guest D3(2,2) programs rewritten onto a D3(2,4) host
    (``runtime.rewrite.emulate``) replay on the 32-device mesh
    bit-identically to the natively-lowered guest, idle devices passing
    through.

(n = K²M² routers means no §2 grid has exactly 8 devices — the smallest
non-degenerate grid (2,2) is the 16-device mesh checked here; grid (2,1)
runs on 4 of 8 devices in runtime_check_script.py.)
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=32")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import alltoall as a2a
from repro.core import broadcast as bc
from repro.core import hypercube as hc
from repro.core import matmul as mm
from repro.core.emulation import embed
from repro.core.topology import D3
from repro.dist.mesh import DeviceLayout
from repro.runtime import lowering
from repro.runtime import optimize as ropt
from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend
from repro.runtime.backends.reference import NumpyReferenceBackend
from repro.runtime.rewrite import emulate, gather_guest, scatter_guest

JAXBE = JaxPpermuteBackend()
OVER = JaxPpermuteBackend(overlap=True)
REF = NumpyReferenceBackend()


def mesh_of(n):
    return Mesh(np.array(jax.devices()[:n]), ("df",))


def check_differential(K, M):
    """Reference and JAX backends agree bit-for-bit on the §3/§4/§5
    programs of D3(K, M) (broadcast from router id 0 — the falsy root)."""
    layout = DeviceLayout(D3(K, M))
    n = layout.n
    mesh = mesh_of(n)
    rng = np.random.default_rng(0)

    prog = lowering.lower(a2a.schedule(layout.da_params, layout.topo))
    x = rng.standard_normal((n, n, 3)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(JAXBE.run_alltoall(x, prog, mesh=mesh)),
        REF.run_alltoall(x, prog),
    )

    prog = lowering.lower(hc.allreduce_schedule(layout.sbh))
    xr = rng.standard_normal((n, 4)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(JAXBE.run_allreduce(xr, prog, mesh=mesh)),
        REF.run_allreduce(xr, prog),
    )

    prog = lowering.lower(bc.depth3_schedule(layout.topo, layout.topo.id_router(0)))
    assert prog.root == 0
    np.testing.assert_array_equal(
        np.asarray(JAXBE.run_broadcast(xr, prog, mesh=mesh)),
        REF.run_broadcast(xr, prog),
    )
    print(f"differential D3({K},{M}) OK (alltoall/allreduce/broadcast, n={n})")


def check_matmul_differential(K, M, X):
    """§2 on the program executor: JAX == reference == jnp.einsum,
    bit-exact (integer-valued float32)."""
    g = mm.MatmulGrid(K, M)
    prog = lowering.lower(mm.schedule(g))
    rng = np.random.default_rng(1)
    N = g.n * X
    B = rng.integers(-4, 5, (N, N)).astype(np.float32)
    A = rng.integers(-4, 5, (N, N)).astype(np.float32)
    got = JAXBE.run_matmul(B, A, prog, mesh=mesh_of(prog.n))
    want = np.asarray(jnp.einsum("ij,jk->ik", jnp.asarray(B), jnp.asarray(A)))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, REF.run_matmul(B, A, prog))
    print(f"matmul grid ({K},{M}) X={X} OK (n={prog.n}, bit-exact vs einsum)")


def check_matmul_hlo_no_gather():
    """The §2 round structure is on the wire: the dragonfly_matmul HLO has
    one collective-permute per program stage and NO all-gather."""
    from repro.dist import collectives as coll

    prog = coll.matmul_program(2, 2)
    mesh = mesh_of(prog.n)
    b = jnp.zeros((prog.n, 2, 2), jnp.float32)
    f = jax.jit(
        jax.shard_map(
            lambda bb, aa: coll.dragonfly_matmul(bb[0], aa[0], "df", (2, 2))[None],
            mesh=mesh, in_specs=(P("df"), P("df")), out_specs=P("df"),
        )
    )
    txt = f.lower(b, b).as_text()
    n_perm = txt.count("collective_permute") + txt.count("collective-permute")
    n_gather = txt.count("all_gather") + txt.count("all-gather")
    assert n_perm >= prog.num_permutes, (n_perm, prog.num_permutes)
    assert n_gather == 0, f"matmul program must not lower to all-gather ({n_gather})"
    print(f"matmul HLO OK ({n_perm} collective-permutes, 0 all-gathers)")


def check_pipelined_broadcast_on_device():
    """start_step replay on the mesh == barrier replay == reference."""
    topo = D3(4, 2)
    prog = lowering.lower(bc.pipelined_m_broadcast_schedule(topo, (0, 0, 1), waves=4))
    mesh = mesh_of(prog.n)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((prog.num_rounds, prog.n, 3)).astype(np.float32)
    bar = np.asarray(JAXBE.run_broadcast(x, prog, mesh=mesh))
    pip = np.asarray(JAXBE.run_broadcast(x, prog, mesh=mesh, pipelined=True))
    np.testing.assert_array_equal(bar, pip)
    np.testing.assert_array_equal(bar, REF.run_broadcast(x, prog, pipelined=True))
    np.testing.assert_array_equal(
        bar, np.broadcast_to(x[:, prog.root][:, None], x.shape)
    )
    print(f"pipelined broadcast OK (waves={prog.num_rounds}, "
          f"makespan {prog.max_start_step + 1} vs barrier "
          f"{sum(6 for _ in range(prog.num_rounds))})")


def check_emulation_rewrite():
    """Guest D3(2,2) programs rewritten onto a D3(2,4) host (32 devices,
    non-contiguous survivor subset) replay on the JAX mesh bit-identically
    to the natively-lowered guest on the reference backend — idle host
    devices pass through. The §2 matmul runs guest grid (1,2) = D3(1,2)
    on the same 32-device host."""
    host = D3(2, 4)
    guest = DeviceLayout(D3(2, 2))
    emb = embed(host, 2, 2, p_set=(1, 3))
    mesh = mesh_of(host.num_routers)
    rng = np.random.default_rng(3)
    ng = guest.n

    prog = lowering.lower(a2a.schedule(guest.da_params, guest.topo))
    hprog = emulate(prog, emb)
    x = rng.standard_normal((ng, ng, 3)).astype(np.float32)
    xh = scatter_guest(x, hprog, axes=(0, 1))
    got = np.asarray(JAXBE.run_alltoall(xh, hprog, mesh=mesh))
    np.testing.assert_array_equal(got, REF.run_alltoall(xh, hprog))
    np.testing.assert_array_equal(
        gather_guest(got, hprog, axes=(0, 1)), REF.run_alltoall(x, prog)
    )
    idle = ~hprog.active_mask_np
    assert not got[idle].any() and not got[:, idle].any()

    prog = lowering.lower(hc.allreduce_schedule(guest.sbh))
    hprog = emulate(prog, emb)
    xr = rng.standard_normal((ng, 4)).astype(np.float32)
    xrh = scatter_guest(xr, hprog, fill=7.0)  # idle slots must pass through
    got = np.asarray(JAXBE.run_allreduce(xrh, hprog, mesh=mesh))
    np.testing.assert_array_equal(got, REF.run_allreduce(xrh, hprog))
    np.testing.assert_array_equal(gather_guest(got, hprog), REF.run_allreduce(xr, prog))
    np.testing.assert_array_equal(got[~hprog.active_mask_np], 7.0)

    prog = lowering.lower(bc.depth3_schedule(guest.topo, (0, 1, 0)))
    hprog = emulate(prog, emb)
    xbh = scatter_guest(xr, hprog, fill=-2.0)
    got = np.asarray(JAXBE.run_broadcast(xbh, hprog, mesh=mesh))
    np.testing.assert_array_equal(got, REF.run_broadcast(xbh, hprog))
    np.testing.assert_array_equal(gather_guest(got, hprog), REF.run_broadcast(xr, prog))

    g = mm.MatmulGrid(1, 2)
    prog = lowering.lower(mm.schedule(g))
    hprog = emulate(prog, embed(host, g.topo.K, g.topo.M, p_set=(0, 2)))
    X = 2
    N = g.n * X
    B = rng.integers(-4, 5, (N, N)).astype(np.float32)
    A = rng.integers(-4, 5, (N, N)).astype(np.float32)
    got = JAXBE.run_matmul(B, A, hprog, mesh=mesh)
    np.testing.assert_array_equal(got, B @ A)
    np.testing.assert_array_equal(got, REF.run_matmul(B, A, hprog))
    print(f"emulation rewrite OK (guest D3(2,2) on D3(2,4) host, "
          f"{host.num_routers}-device mesh, idle pass-through)")


def check_overlap_differential():
    """Satellite: ``overlap=True`` (start_step-ordered) replay of PIPELINED
    schedules differentially vs the reference backend — the §3 Schedule-1
    all-to-all (``pipelined_schedule``, measured delays stamped) and the §5
    wave broadcast, end-to-end on the device mesh. Barrier replay only used
    to be covered; this pins the overlapped order too."""
    layout = DeviceLayout(D3(4, 2))
    n = layout.n
    mesh = mesh_of(n)
    rng = np.random.default_rng(4)

    prog = lowering.lower(a2a.pipelined_schedule(layout.da_params, offset=1,
                                                 topo=layout.topo))
    assert prog.max_start_step + 1 < 3 * prog.num_rounds  # genuinely pipelined
    x = rng.standard_normal((n, n, 3)).astype(np.float32)
    want = REF.run_alltoall(x, prog)
    np.testing.assert_array_equal(
        np.asarray(OVER.run_alltoall(x, prog, mesh=mesh)), want)

    bprog = lowering.lower(
        bc.pipelined_m_broadcast_schedule(layout.topo, (0, 0, 1), waves=4))
    xw = rng.standard_normal((bprog.num_rounds, n, 3)).astype(np.float32)
    bwant = REF.run_broadcast(xw, bprog, pipelined=True)
    np.testing.assert_array_equal(
        np.asarray(OVER.run_broadcast(xw, bprog, mesh=mesh)), bwant)
    print(f"overlap differential OK (pipelined alltoall makespan "
          f"{prog.max_start_step + 1} vs barrier {3 * prog.num_rounds}; "
          f"wave broadcast)")


def check_optimized_on_device():
    """optimize(program) replays bit-identically to the per-stage ppermute
    loop for every kind on real device buffers (the fused table path the
    run_* wrappers take for OptimizedProgram)."""
    layout = DeviceLayout(D3(4, 2))
    n = layout.n
    mesh = mesh_of(n)
    rng = np.random.default_rng(5)

    prog = lowering.lower(a2a.schedule(layout.da_params, layout.topo))
    x = rng.standard_normal((n, n, 3)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(JAXBE.run_alltoall(x, ropt.optimize(prog))),
        np.asarray(JAXBE.run_alltoall(x, prog, mesh=mesh)))

    prog = lowering.lower(hc.allreduce_schedule(layout.sbh))
    xr = rng.standard_normal((n, 4)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(JAXBE.run_allreduce(xr, ropt.optimize(prog))),
        np.asarray(JAXBE.run_allreduce(xr, prog, mesh=mesh)))

    prog = lowering.lower(bc.depth3_schedule(layout.topo, (0, 1, 0)))
    np.testing.assert_array_equal(
        np.asarray(JAXBE.run_broadcast(xr, ropt.optimize(prog))),
        np.asarray(JAXBE.run_broadcast(xr, prog, mesh=mesh)))

    g = mm.MatmulGrid(2, 2)
    prog = lowering.lower(mm.schedule(g))
    N = g.n * 2
    B = rng.integers(-4, 5, (N, N)).astype(np.float32)
    A = rng.integers(-4, 5, (N, N)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(JAXBE.run_matmul(B, A, ropt.optimize(prog))),
        np.asarray(JAXBE.run_matmul(B, A, prog, mesh=mesh_of(prog.n))))
    print("optimized-vs-loop on-device OK (all four kinds)")


def check_concurrent_guests():
    """Two disjoint D3(2,2) guests COMBINED (``runtime.combine``) onto the
    32-device D3(2,4) host: one mesh replay of the combined program agrees
    bit-for-bit, per guest, with the guests' solo rewritten replays — on
    the per-stage ppermute path AND the fused optimized path."""
    from repro.core.emulation import disjoint_embeddings
    from repro.runtime import combine as cmb

    host = D3(2, 4)
    guest = DeviceLayout(D3(2, 2))
    embs = disjoint_embeddings(host, [(2, 2), (2, 2)])  # position regime
    mesh = mesh_of(host.num_routers)
    rng = np.random.default_rng(6)

    prog = lowering.lower(a2a.schedule(guest.da_params, guest.topo))
    solos = [emulate(prog, e) for e in embs]
    comb = cmb.combine(solos)
    xs = [rng.standard_normal((guest.n, guest.n, 3)).astype(np.float32)
          for _ in embs]
    xh = cmb.scatter_guests(xs, embs, axes=(0, 1))
    got = np.asarray(JAXBE.run_alltoall(xh, comb, mesh=mesh))
    np.testing.assert_array_equal(got, REF.run_alltoall(xh, comb))
    np.testing.assert_array_equal(
        got, np.asarray(JAXBE.run_alltoall(xh, ropt.optimize(comb))))
    for e, x, solo in zip(embs, xs, solos):
        want = gather_guest(
            np.asarray(JAXBE.run_alltoall(
                scatter_guest(x, solo, axes=(0, 1)), solo, mesh=mesh)),
            solo, axes=(0, 1))
        np.testing.assert_array_equal(
            cmb.extract_guest(got, e, axes=(0, 1)), want)
    idle = ~comb.active_mask_np
    assert not got[idle].any() and not got[:, idle].any()

    ar = lowering.lower(hc.allreduce_schedule(guest.sbh))
    comb_ar = cmb.combine([emulate(ar, e) for e in embs])
    ys = [rng.standard_normal((guest.n, 4)).astype(np.float32) for _ in embs]
    yh = cmb.scatter_guests(ys, embs, fill=3.5)
    got = np.asarray(JAXBE.run_allreduce(yh, comb_ar, mesh=mesh))
    np.testing.assert_array_equal(got, REF.run_allreduce(yh, comb_ar))
    for e, y in zip(embs, ys):
        np.testing.assert_array_equal(
            cmb.extract_guest(got, e), REF.run_allreduce(y, ar))
    np.testing.assert_array_equal(got[~comb_ar.active_mask_np], 3.5)
    print(f"concurrent guests OK (2×D3(2,2) combined on D3(2,4) mesh, "
          f"{comb.num_rounds} rounds vs {2 * prog.num_rounds} time-muxed)")


if __name__ == "__main__":
    assert jax.device_count() >= 32, jax.device_count()
    check_differential(4, 2)
    check_differential(2, 4)
    check_overlap_differential()
    check_optimized_on_device()
    check_emulation_rewrite()
    check_concurrent_guests()
    # §2 grids: D3(4,2) is grid (2,2); no grid has K²M² = 2·16 (K must be a
    # perfect square), so (1,4) is the second matmul case.
    check_matmul_differential(2, 2, X=2)
    check_matmul_differential(1, 4, X=1)
    check_matmul_hlo_no_gather()
    check_pipelined_broadcast_on_device()
    print("ALL PROGRAM CHECKS PASSED")
