"""Multi-device collective equivalence checks — run as a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=16 (set before jax import,
see test_dist_collectives.py). Exits 0 on success."""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.mesh import dragonfly_layout
from repro.dist import collectives as coll
from jax import shard_map


def get_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("x",))


def check_all_to_all():
    n = 16
    layout = dragonfly_layout(n)
    assert layout.n == n, layout
    mesh = get_mesh(n)
    rng = np.random.default_rng(0)
    # global input: (n, n, 4) — x[i, j] is the chunk device i sends to j
    x = rng.standard_normal((n, n, 4)).astype(np.float32)

    @jax.jit
    def run_df(x):
        f = shard_map(
            lambda s: coll.dragonfly_all_to_all(s[0], "x", layout)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
        return f(x)

    @jax.jit
    def run_ref(x):
        f = shard_map(
            lambda s: coll.xla_all_to_all(s[0], "x")[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
        return f(x)

    got = np.asarray(run_df(x))
    want = np.asarray(run_ref(x))
    # ground truth: out[i, j] = x[j, i]
    np.testing.assert_allclose(want, x.transpose(1, 0, 2), rtol=0, atol=0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    print("all_to_all OK")


def check_all_reduce():
    n = 16
    layout = dragonfly_layout(n)  # D3(4,2): K=4 M=2 -> SBH(2,1)
    assert layout.sbh is not None
    mesh = get_mesh(n)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, 8)).astype(np.float32)

    @jax.jit
    def run_df(x):
        f = shard_map(
            lambda s: coll.dragonfly_all_reduce(s[0], "x", layout)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
        return f(x)

    got = np.asarray(run_df(x))
    want = np.broadcast_to(x.sum(0), (n, 8))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    print("all_reduce OK")


def check_broadcast():
    n = 16
    layout = dragonfly_layout(n)
    mesh = get_mesh(n)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    root = 3

    @jax.jit
    def run_df(x):
        f = shard_map(
            lambda s: coll.dragonfly_broadcast(s[0], "x", layout, root=root)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
        return f(x)

    got = np.asarray(run_df(x))
    want = np.broadcast_to(x[root], (n, 8))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    print("broadcast OK")


def check_matmul():
    # D3(K²,M) with K=2, M=2: 16 routers = 16 devices in router order.
    # The §2 rounds run on the program executor — ppermutes, no gather.
    from repro.core.matmul import MatmulGrid, gather_blocks, scatter_blocks

    K, M = 2, 2
    grid = MatmulGrid(K, M)
    prog = coll.matmul_program(K, M)
    assert prog.n == 16
    mesh = get_mesh(16)
    b = 8  # block size: Theorem 2's X blocks
    rng = np.random.default_rng(3)
    side = grid.n * b
    # integer-valued floats: the round-structured sum is bit-exact vs einsum
    Bmat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    Amat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    bb = jnp.asarray(scatter_blocks(grid, Bmat))
    aa = jnp.asarray(scatter_blocks(grid, Amat))

    f = jax.jit(
        shard_map(
            lambda x, y: coll.dragonfly_matmul(x[0], y[0], "x", (K, M))[None],
            mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"),
        )
    )
    got = gather_blocks(grid, np.asarray(f(bb, aa)))
    want = np.asarray(jnp.einsum("ij,jk->ik", jnp.asarray(Bmat), jnp.asarray(Amat)))
    np.testing.assert_array_equal(got, want)  # bit-exact, zero tolerance
    txt = f.lower(bb, aa).as_text()
    n_gather = txt.count("all_gather") + txt.count("all-gather")
    assert n_gather == 0, f"dragonfly_matmul must not lower to all-gather ({n_gather})"
    print("matmul OK (program executor, bit-exact, no all-gather)")


def check_ppermute_round_count():
    """HLO of the dragonfly all-to-all shows exactly K·M² collective
    permutes minus the identity vector (the schedule is visible)."""
    n = 16
    layout = dragonfly_layout(n)
    mesh = get_mesh(n)
    x = jnp.zeros((n, n, 4), jnp.float32)
    f = jax.jit(
        shard_map(
            lambda s: coll.dragonfly_all_to_all(s[0], "x", layout)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
    )
    txt = f.lower(x).as_text()
    # StableHLO spells it collective_permute; compiled HLO collective-permute
    n_perm = txt.count("collective_permute") + txt.count("collective-permute")
    K, Mm = layout.topo.K, layout.topo.M
    expected = K * Mm * Mm - 1  # identity vector elided
    assert n_perm >= expected, (n_perm, expected)
    print(f"round structure OK ({n_perm} collective-permutes ~ {expected})")


def check_embedded_collectives():
    """Guest-sized collectives on the host mesh via the optional embedding:
    dragonfly_all_to_all and dragonfly_matmul of a D3(2,2)/grid(1,2) guest
    run on the 16-device D3(4,2) host axis, bit-exact vs the guest run
    host-side, idle devices passing through."""
    from repro.core.matmul import MatmulGrid, gather_blocks, scatter_blocks
    from repro.dist.mesh import DeviceLayout
    from repro.core.topology import D3
    from repro.runtime.backends.reference import NumpyReferenceBackend
    from repro.runtime.rewrite import gather_guest, scatter_guest

    ref = NumpyReferenceBackend()
    host = dragonfly_layout(16)          # D3(4,2)
    guest = DeviceLayout(D3(2, 2))
    emb = guest.embed_onto(host, c_set=(1, 3))
    prog = coll.alltoall_program(guest, emb)
    assert prog.n == 16 and prog.guest_n == guest.n
    mesh = get_mesh(16)
    rng = np.random.default_rng(4)
    xg = rng.standard_normal((guest.n, guest.n, 4)).astype(np.float32)
    xh = jnp.asarray(scatter_guest(xg, prog, axes=(0, 1)))

    f = jax.jit(
        shard_map(
            lambda s: coll.dragonfly_all_to_all(s[0], "x", guest, embedding=emb)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
    )
    got = gather_guest(np.asarray(f(xh)), prog, axes=(0, 1))
    np.testing.assert_array_equal(got, xg.transpose(1, 0, 2))

    g = MatmulGrid(1, 2)                 # guest D3(1,2): 4 of 16 devices
    membb = DeviceLayout(g.topo).embed_onto(host)
    mprog = coll.matmul_program(1, 2, membb)
    side = g.n * 4
    Bmat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    Amat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    bb = jnp.asarray(scatter_guest(scatter_blocks(g, Bmat), mprog))
    aa = jnp.asarray(scatter_guest(scatter_blocks(g, Amat), mprog))
    fm = jax.jit(
        shard_map(
            lambda x, y: coll.dragonfly_matmul(x[0], y[0], "x", (1, 2), embedding=membb)[None],
            mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"),
        )
    )
    out = gather_blocks(g, gather_guest(np.asarray(fm(bb, aa)), mprog))
    np.testing.assert_array_equal(out, Bmat @ Amat)
    np.testing.assert_array_equal(out, ref.run_matmul(Bmat, Amat, mprog))
    print("embedded collectives OK (guest D3(2,2) + grid(1,2) on D3(4,2) mesh)")


if __name__ == "__main__":
    assert jax.device_count() >= 16, jax.device_count()
    check_all_to_all()
    check_all_reduce()
    check_broadcast()
    check_matmul()
    check_ppermute_round_count()
    check_embedded_collectives()
    print("ALL DIST CHECKS PASSED")
