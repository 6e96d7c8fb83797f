"""Scale smoke — run as a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=256 (set before jax
import, see test_autotune.py and the CI scale step). D3(4,4) doubly-
parallel all-to-all plus the Theorem-2 matmul on grid (2,4) — K²M² = 64
devices — and, when the process has 256 devices, the grid-(4,4) matmul
(D3(16,4), K²M² = 256 routers). All bit-exact against ground truth.
Also exports the same shapes to send/recv device traces, re-validates
them, and replays them through the ``sendrecv`` interpreter against the
jax backend (``check_export_256``); set ``REPRO_EXPORT_TRACE_DIR`` to
keep the trace JSON (the CI artifact). Exits 0 on success."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=256")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist import collectives as coll
from repro.dist.mesh import dragonfly_layout
from jax import shard_map


def get_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("x",))


def check_all_to_all_64():
    n = 64
    layout = dragonfly_layout(n)
    assert (layout.topo.K, layout.topo.M) == (4, 4), layout.topo
    mesh = get_mesh(n)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n, 4)).astype(np.float32)

    f = jax.jit(
        shard_map(
            lambda s: coll.dragonfly_all_to_all(s[0], "x", layout)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
    )
    got = np.asarray(f(x))
    np.testing.assert_allclose(got, x.transpose(1, 0, 2), rtol=1e-6)
    print("D3(4,4) all_to_all OK (64 devices)")


def check_matmul_64():
    # Theorem 2 grid (K, M) = (2, 4): the K×K array of M×M blocks needs
    # K²M² = 64 devices in router order.
    from repro.core.matmul import MatmulGrid, gather_blocks, scatter_blocks

    K, M = 2, 4
    grid = MatmulGrid(K, M)
    prog = coll.matmul_program(K, M)
    assert prog.n == 64, prog.n
    mesh = get_mesh(64)
    b = 4
    rng = np.random.default_rng(3)
    side = grid.n * b
    # integer-valued floats: the round-structured sum is bit-exact vs @
    Bmat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    Amat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    bb = jnp.asarray(scatter_blocks(grid, Bmat))
    aa = jnp.asarray(scatter_blocks(grid, Amat))

    f = jax.jit(
        shard_map(
            lambda p, q: coll.dragonfly_matmul(p[0], q[0], "x", (K, M))[None],
            mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"),
        )
    )
    got = gather_blocks(grid, np.asarray(f(bb, aa)))
    np.testing.assert_array_equal(got, Bmat @ Amat)
    print("Theorem-2 matmul grid (2,4) OK (64 devices, bit-exact)")


def check_matmul_256():
    # Theorem 2 grid (K, M) = (4, 4): K²M² = 256 devices — the largest
    # forced-host mesh the CI scale job exercises. b=2 keeps the compile
    # a few seconds while still blocking (32×32 matrix, 16 rounds).
    from repro.core.matmul import MatmulGrid, gather_blocks, scatter_blocks

    K, M = 4, 4
    grid = MatmulGrid(K, M)
    prog = coll.matmul_program(K, M)
    assert prog.n == 256, prog.n
    mesh = get_mesh(256)
    b = 2
    rng = np.random.default_rng(5)
    side = grid.n * b
    Bmat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    Amat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    bb = jnp.asarray(scatter_blocks(grid, Bmat))
    aa = jnp.asarray(scatter_blocks(grid, Amat))

    f = jax.jit(
        shard_map(
            lambda p, q: coll.dragonfly_matmul(p[0], q[0], "x", (K, M))[None],
            mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"),
        )
    )
    got = gather_blocks(grid, np.asarray(f(bb, aa)))
    np.testing.assert_array_equal(got, Bmat @ Amat)
    print("Theorem-2 matmul grid (4,4) OK (256 devices, bit-exact)")
    return got


def check_export_256(jax_c256=None):
    """Differential export at scale: compile the D3(4,4) pipelined §3
    all-to-all and the grid-(4,4) Theorem-2 matmul (256 routers) to
    send/recv traces, re-validate the exported form, replay through the
    ``sendrecv`` interpreter against the jax backend's output, and — when
    ``REPRO_EXPORT_TRACE_DIR`` is set — write the trace JSON for the CI
    artifact + ``python -m repro.runtime.export`` check."""
    import pathlib

    from repro.runtime import export as rexport
    from repro.runtime.backends.sendrecv import SendRecvBackend

    sr = SendRecvBackend()
    written = []
    out_dir = os.environ.get("REPRO_EXPORT_TRACE_DIR")

    # D3(4,4) §3 all-to-all, Schedule-1 pipelined: overlap windows survive
    layout = dragonfly_layout(64)
    prog = coll.alltoall_program(layout, pipelined=1)
    trace = rexport.validate(rexport.export(prog))
    assert trace.waves()[-1][0] < rexport.export(
        coll.alltoall_program(layout)).waves()[-1][0], "no pipelined overlap"
    rng = np.random.default_rng(11)
    x = rng.integers(-4, 5, (64, 64, 4)).astype(np.float32)
    mesh = get_mesh(64)
    f = jax.jit(
        shard_map(
            lambda s: coll.dragonfly_all_to_all(s[0], "x", layout)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
    )
    np.testing.assert_array_equal(sr.run_alltoall(x, prog), np.asarray(f(x)))
    print(f"export D3(4,4) all-to-all pipe1 OK (sendrecv == jax, "
          f"ops={trace.num_ops} waves={len(trace.waves())})")
    traces = {"alltoall_d3_4x4_pipe1": trace}

    # grid-(4,4) matmul: the 256-router trace exports/validates with no
    # devices at all; replay checks vs the jax output when we have one.
    from repro.core.matmul import MatmulGrid

    K, M = 4, 4
    prog = coll.matmul_program(K, M)
    trace = rexport.validate(rexport.export(prog))
    grid = MatmulGrid(K, M)
    rng = np.random.default_rng(5)
    side = grid.n * 2
    Bmat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    Amat = rng.integers(-4, 5, (side, side)).astype(np.float32)
    got = sr.run_matmul(Bmat, Amat, prog)
    np.testing.assert_array_equal(got, Bmat @ Amat)
    if jax_c256 is not None:
        np.testing.assert_array_equal(got, jax_c256)
    print(f"export grid (4,4) matmul OK (sendrecv"
          f"{' == jax' if jax_c256 is not None else ''}, 256 routers, "
          f"ops={trace.num_ops})")
    traces["matmul_grid_4x4"] = trace

    if out_dir:
        d = pathlib.Path(out_dir)
        d.mkdir(parents=True, exist_ok=True)
        for name, t in traces.items():
            p = d / f"{name}.json"
            p.write_text(t.to_json())
            written.append(str(p))
        print("wrote traces:", " ".join(written))


if __name__ == "__main__":
    assert jax.device_count() >= 64, jax.device_count()
    check_all_to_all_64()
    check_matmul_64()
    c256 = None
    if jax.device_count() >= 256:
        c256 = check_matmul_256()
    else:
        print("skipping grid (4,4): need 256 devices, have", jax.device_count())
    check_export_256(c256)
    print("ALL SCALE CHECKS PASSED")
