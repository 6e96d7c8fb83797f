"""Pallas-fused backend smoke tests — CPU, ``interpret=True``.

Tier-1 exercises the fused path without a TPU: the ReduceCombine table
kernel (the interpret-mode face of the remote-DMA ring), the vmapped
``block_matmul`` Pallas kernel on the §2 ``mul_a`` contraction, and the
optimizer-table delegation for the data-movement collectives — all
bit-exact against the reference backend. Shapes stay tiny: the Pallas
interpreter executes kernel bodies op-by-op.
"""

import numpy as np
import pytest

from repro.core import alltoall as a2a
from repro.core import broadcast as bc
from repro.core import hypercube as hc
from repro.core import matmul as mm
from repro.core.emulation import embed
from repro.core.topology import D3
from repro.dist.mesh import DeviceLayout
from repro.runtime import lowering
from repro.runtime import optimize as opt
from repro.runtime.backends import get_backend
from repro.runtime.backends.pallas_fused import PallasFusedBackend
from repro.runtime.backends.reference import NumpyReferenceBackend
from repro.runtime.rewrite import emulate, scatter_guest

REF = NumpyReferenceBackend()
PAL = PallasFusedBackend(interpret=True)
LAYOUT = DeviceLayout(D3(2, 2))


def test_registry_and_auto_interpret():
    be = get_backend("pallas_fused")
    assert isinstance(be, PallasFusedBackend)
    assert be.name == "pallas_fused"
    # on a CPU host the auto mode must select the interpreter
    import jax

    if jax.default_backend() != "tpu":
        assert be._interp()
    assert get_backend("pallas", interpret=True)._interp()


def test_ring_kernel_allreduce_smoke():
    """Satellite: the Pallas ReduceCombine kernel (interpret) replays the
    §4 hypercube rounds bit-exactly — on the program AND its optimized
    form."""
    prog = lowering.lower(hc.allreduce_schedule(LAYOUT.sbh))
    x = np.random.default_rng(0).standard_normal((prog.n, 4)).astype(np.float32)
    want = REF.run_allreduce(x, prog)
    np.testing.assert_array_equal(np.asarray(PAL.run_allreduce(x, prog)), want)
    np.testing.assert_array_equal(
        np.asarray(PAL.run_allreduce(x, opt.optimize(prog))), want)
    np.testing.assert_allclose(want, np.broadcast_to(x.sum(0), x.shape),
                               rtol=1e-5, atol=1e-6)


def test_ring_kernel_allreduce_emulated():
    """Emulated guest rounds drive the same kernel through partial tables:
    idle host devices pass through (fill value survives)."""
    emb = embed(D3(2, 4), 2, 2, p_set=(1, 3))
    hp = emulate(lowering.lower(hc.allreduce_schedule(LAYOUT.sbh)), emb)
    xg = np.random.default_rng(1).standard_normal((LAYOUT.n, 3)).astype(np.float32)
    xh = scatter_guest(xg, hp, fill=7.0)
    got = np.asarray(PAL.run_allreduce(xh, hp))
    np.testing.assert_array_equal(got, REF.run_allreduce(xh, hp))
    assert np.all(got[~hp.active_mask_np] == 7.0)


@pytest.mark.parametrize("grid,X", [((2, 2), 2), ((1, 2), 4)], ids=str)
def test_matmul_through_pallas_kernels(grid, X):
    """§2 replay with mul_a on the block_matmul Pallas kernel and the
    combine groups on the table kernel — bit-exact vs B @ A and the
    reference replay (integer-valued float32)."""
    g = mm.MatmulGrid(*grid)
    prog = lowering.lower(mm.schedule(g))
    rng = np.random.default_rng(2)
    N = g.n * X
    B = rng.integers(-4, 5, (N, N)).astype(np.float32)
    A = rng.integers(-4, 5, (N, N)).astype(np.float32)
    got = np.asarray(PAL.run_matmul(B, A, prog))
    np.testing.assert_array_equal(got, B @ A)
    np.testing.assert_array_equal(got, REF.run_matmul(B, A, prog))


def test_data_movement_delegates_to_fused_tables():
    """alltoall/broadcast have no compute to fuse: the backend replays the
    optimizer tables and must match the reference bit-for-bit."""
    rng = np.random.default_rng(3)
    n = LAYOUT.n
    prog = lowering.lower(a2a.schedule(LAYOUT.da_params, LAYOUT.topo))
    x = rng.standard_normal((n, n, 2)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(PAL.run_alltoall(x, prog)), REF.run_alltoall(x, prog))

    prog = lowering.lower(bc.depth3_schedule(LAYOUT.topo, (0, 1, 0)))
    xb = rng.standard_normal((n, 3)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(PAL.run_broadcast(xb, prog)), REF.run_broadcast(xb, prog))
    # pipelined flag is accepted and bit-identical (fused replay is
    # order-free by conflict-freedom)
    np.testing.assert_array_equal(
        np.asarray(PAL.run_broadcast(xb, prog, pipelined=True)),
        REF.run_broadcast(xb, prog, pipelined=True))


def test_batched_block_matmul_kernel():
    """The vmapped Pallas kernel entry used for mul_a (interpret mode)."""
    from repro.kernels.block_matmul.ops import batched_matmul

    rng = np.random.default_rng(4)
    a = rng.integers(-3, 4, (5, 4, 4)).astype(np.float32)
    b = rng.integers(-3, 4, (5, 4, 4)).astype(np.float32)
    got = np.asarray(batched_matmul(a, b, interpret=True))
    np.testing.assert_array_equal(got, np.einsum("nab,nbc->nac", a, b))


def test_shard_ring_path_guarded_off_tpu():
    """The compiled remote-DMA ring refuses to run without TPU
    interconnect (interpret mode simulates it instead)."""
    import jax

    if jax.default_backend() == "tpu":  # pragma: no cover - CPU CI
        pytest.skip("TPU host: ring path is live")
    prog = lowering.lower(hc.allreduce_schedule(LAYOUT.sbh))
    with pytest.raises(RuntimeError, match="remote DMA"):
        PallasFusedBackend(interpret=False).allreduce_shard(
            np.zeros((4,)), "df", prog)


RING_CHECK = """
import jax, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.dist.collectives import allreduce_program
from repro.dist.mesh import dragonfly_layout
from repro.runtime.backends.pallas_fused import PallasFusedBackend

layout = dragonfly_layout(4)
mesh = Mesh(np.array(jax.devices()[:4]), ("df",))
be = PallasFusedBackend(interpret=True)
ring = jax.jit(jax.shard_map(
    lambda s: be.allreduce_shard(s, "df", allreduce_program(layout)),
    mesh=mesh, in_specs=P("df"), out_specs=P("df"), check_vma=False))
x = np.random.default_rng(0).integers(-8, 9, (4, 256)).astype(np.float32)
np.testing.assert_array_equal(np.asarray(ring(x)), np.tile(x.sum(0), (4, 1)))
print("RING OK")
"""


def test_rdma_ring_interpret_4dev():
    """The remote-DMA ring under the TPU interpreter (DMAs, semaphores and
    the barrier simulated across 4 host devices) sums like lax.psum."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, "-c", RING_CHECK], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "RING OK" in proc.stdout
