"""Runtime executor equivalence checks — run as a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (set before jax import;
see test_runtime_executor.py). Exits 0 on success.

The acceptance bar: the §3 all-to-all Schedule, lowered mechanically from
the IR into a ``CollectiveProgram`` and replayed on an 8-device CPU mesh
(one ppermute per source vector), is BIT-EXACT against jax.lax.all_to_all;
the §4/§5 programs reproduce their analytic results; and the §2 matmul
program (grid (2,1) — no K²M² grid has exactly 8 routers) is bit-exact
against jnp.einsum. Heavier device checks live in program_check_script.py.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import alltoall as a2a
from repro.core import broadcast as bc
from repro.core import hypercube as hc
from repro.core import matmul as mm
from repro.dist.mesh import dragonfly_layout
from repro.runtime import lowering
from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend
from jax import shard_map

N = 8
BACKEND = JaxPpermuteBackend()


def get_mesh(n=N):
    return Mesh(np.array(jax.devices()[:n]), ("df",))


def check_alltoall_bit_exact():
    layout = dragonfly_layout(N)
    assert (layout.topo.K, layout.topo.M) == (2, 2), layout
    prog = lowering.lower(a2a.schedule(layout.da_params, layout.topo))
    # n/s rounds of s permutes each: K·M² ppermutes total
    assert prog.num_permutes == N
    assert prog.num_rounds == layout.da_params.total_rounds
    mesh = get_mesh()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, N, 5)), jnp.float32)

    got = np.asarray(BACKEND.run_alltoall(x, prog, mesh=mesh))

    @jax.jit
    def run_ref(x):
        f = shard_map(
            lambda s: jax.lax.all_to_all(s[0], "df", split_axis=0, concat_axis=0)[None],
            mesh=mesh, in_specs=P("df"), out_specs=P("df"),
        )
        return f(x)

    want = np.asarray(run_ref(x))
    np.testing.assert_array_equal(want, np.asarray(x).transpose(1, 0, 2))
    np.testing.assert_array_equal(got, want)  # bit-exact, zero tolerance
    print("alltoall bit-exact OK")


def check_alltoall_hlo_round_structure():
    """The lowered program is visible in the HLO: one collective-permute
    per source vector."""
    layout = dragonfly_layout(N)
    prog = lowering.lower(a2a.schedule(layout.da_params, layout.topo))
    mesh = get_mesh()
    x = jnp.zeros((N, N, 5), jnp.float32)
    f = jax.jit(
        shard_map(
            lambda s: BACKEND.alltoall(s[0], "df", prog)[None],
            mesh=mesh, in_specs=P("df"), out_specs=P("df"),
        )
    )
    txt = f.lower(x).as_text()
    n_perm = txt.count("collective_permute") + txt.count("collective-permute")
    assert n_perm >= prog.num_permutes, (n_perm, prog.num_permutes)
    print(f"round structure OK ({n_perm} collective-permutes >= {prog.num_permutes})")


def check_allreduce():
    layout = dragonfly_layout(N)  # D3(2,2) = SBH(1,1)
    sbh = layout.sbh
    assert sbh is not None and (sbh.k, sbh.m) == (1, 1)
    prog = lowering.lower(hc.allreduce_schedule(sbh))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((N, 4)), jnp.float32)
    got = np.asarray(BACKEND.run_allreduce(x, prog, mesh=get_mesh()))
    want = np.broadcast_to(np.asarray(x).sum(0), (N, 4))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    print("allreduce OK")


def check_broadcast():
    layout = dragonfly_layout(N)
    root = 5
    prog = lowering.lower(
        bc.depth3_schedule(layout.topo, layout.topo.id_router(root))
    )
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((N, 4)), jnp.float32)
    got = np.asarray(BACKEND.run_broadcast(x, prog, mesh=get_mesh()))
    np.testing.assert_array_equal(got, np.broadcast_to(np.asarray(x)[root], (N, 4)))
    print("broadcast OK")


def check_matmul_program():
    """§2 matmul through the program executor on the devices this
    environment has: grid (2,1) -> 4-router mesh, bit-exact vs einsum."""
    g = mm.MatmulGrid(2, 1)
    prog = lowering.lower(mm.schedule(g))
    rng = np.random.default_rng(3)
    X = 4
    side = g.n * X
    B = rng.integers(-4, 5, (side, side)).astype(np.float32)
    A = rng.integers(-4, 5, (side, side)).astype(np.float32)
    got = BACKEND.run_matmul(B, A, prog, mesh=get_mesh(prog.n))
    want = np.asarray(jnp.einsum("ij,jk->ik", jnp.asarray(B), jnp.asarray(A)))
    np.testing.assert_array_equal(got, want)
    print(f"matmul program OK (grid (2,1), n={prog.n}, bit-exact vs einsum)")


if __name__ == "__main__":
    assert jax.device_count() >= N, jax.device_count()
    check_alltoall_bit_exact()
    check_alltoall_hlo_round_structure()
    check_allreduce()
    check_broadcast()
    check_matmul_program()
    print("ALL RUNTIME CHECKS PASSED")
