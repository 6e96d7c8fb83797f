"""Pallas-fused backend: fused table replay with Pallas kernels on the
reduce and contraction hot spots.

Third implementation of the backend contract (``runtime/__init__.py``).
Where ``jax_ppermute`` issues one collective per stage to keep the paper's
round structure visible in the HLO, this backend replays the OPTIMIZED form
of the program (``runtime.optimize``) and pushes the two compute-bound
pieces into Pallas kernels:

  * the per-round permute+accumulate of the allreduce / matmul
    ``ReduceCombine`` stages runs as ONE kernel per program (allreduce) or
    per fused group (matmul): the stacked (gather, mask) tables ride in as
    scalar-prefetch operands and drive row reads (``pl.ds``) inside the
    kernel, gridded over the feature axis so each (n, tile) slab replays
    every round in VMEM — the kernel-side analog of a remote-DMA ring step
    (see ``_rdma_exchange_kernel`` for the actual inter-chip pattern);
  * the §2 ``mul_a`` local contraction routes through the existing MXU-tiled
    ``kernels/block_matmul`` Pallas kernel (vmapped over the router-block
    axis) instead of a bare ``@``.

Interpret-mode caveats
----------------------
CPU CI runs every kernel with ``interpret=True`` (the Pallas interpreter
executes kernel bodies op-by-op): numerically identical to the compiled
kernel, but *slow* — the smoke tests keep shapes tiny, and the benchmark
rows labeled ``pallas_fused`` on a CPU host measure the interpreter, not
the hardware. On a TPU host (``jax.default_backend() == "tpu"``) the same
entry points compile the kernels for real. ``allreduce_shard`` is the
per-shard form: one ``_rdma_exchange_kernel`` per round, a
``make_async_remote_copy`` to the round's partner inside the caller's
``shard_map``; the TPU interpreter simulates its DMAs and semaphores on
host devices.

``run_alltoall`` / ``run_broadcast`` are pure data movement with no
compute to fuse — they delegate to the optimizer's table replay (one
batched scatter / one ``lax.scan`` over masked gathers), which is already
the fastest XLA-expressible form.

All four entry points are bit-exact against the reference backend on the
same programs, native and emulated — differential-tested by
``tests/test_pallas_fused.py`` without any device requirement.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import optimize as _opt
from repro.runtime.program import CollectiveProgram, check_kind as _check_kind


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Kernels. The (gather, mask) tables ride in as scalar-prefetch int32 arrays
# (SMEM); the value buffer is gridded over its feature axis, so VMEM holds
# one (n, tile) slab at a time. Gathers only mix rows (devices), never
# features, so every feature tile replays the rounds independently.
# ---------------------------------------------------------------------------

_TILE = 8192  # feature lanes per grid step: (n, 8192) f32 is 256 KiB at n=8


def _gather_fold(src_ref, g_ref, m_ref, idx, d, acc):
    """Fold the masked rows ``src[gather[idx + (k, d)]]`` into ``acc`` (a
    (1, tile) row) in table order k — the reference's stage-order fold; an
    unmasked row leaves ``acc`` untouched."""
    for k in range(g_ref.shape[len(idx)]):
        row = src_ref[pl.ds(g_ref[(*idx, k, d)], 1), :]
        acc = jnp.where(m_ref[(*idx, k, d)] != 0, acc + row, acc)
    return acc


def _reduce_rounds_kernel(g_ref, m_ref, x_ref, o_ref, recv_ref):
    """Replay R permute+accumulate rounds on one (n, tile) slab: round r
    adds Σ_k where(mask[r, k, d], val[gather[r, k, d]]) (stage order) into
    row d. ``o_ref`` holds the running value; ``recv_ref`` collects one
    round's arrivals so every read sees the pre-round value."""
    rounds, _, n = g_ref.shape
    o_ref[...] = x_ref[...]

    def round_body(r, carry):
        for d in range(n):  # static row count — unrolled
            zero = jnp.zeros((1, o_ref.shape[1]), o_ref.dtype)
            recv_ref[pl.ds(d, 1), :] = _gather_fold(o_ref, g_ref, m_ref, (r,), d, zero)
        o_ref[...] = o_ref[...] + recv_ref[...]
        return carry

    jax.lax.fori_loop(0, rounds, round_body, 0)


def _combine_group_kernel(g_ref, m_ref, v_ref, a_ref, o_ref):
    """One fused ReduceCombine group on one slab: row d of the output is
    acc[d] with the masked rows val[gather[k, d]] folded in, k in stage
    order (the reference's accumulation order, bit for bit)."""
    for d in range(g_ref.shape[1]):
        o_ref[pl.ds(d, 1), :] = _gather_fold(
            v_ref, g_ref, m_ref, (), d, a_ref[pl.ds(d, 1), :])


def _table_call(kernel, tables, operands, scratch: bool, interpret: bool):
    """``pallas_call`` of a table kernel on (n, F) operands: pad F to whole
    lane tiles, grid over the tiles, slice the padding off the result."""
    n, F = operands[0].shape
    tile = min(_TILE, -(-F // 128) * 128)
    Fp = -(-F // tile) * tile
    if Fp != F:
        operands = [jnp.pad(o, ((0, 0), (0, Fp - F))) for o in operands]
    spec = pl.BlockSpec((n, tile), lambda j, *_: (0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables),
        grid=(Fp // tile,),
        in_specs=[spec] * len(operands),
        out_specs=spec,
        scratch_shapes=[pltpu.VMEM((n, tile), operands[0].dtype)] if scratch else [],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, Fp), operands[0].dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
    )(*tables, *operands)
    return out[:, :F]


def reduce_rounds(gather, mask, x, *, interpret: bool = False):
    """All-reduce table replay of (R, k, n) ``gather``/``mask`` tables on an
    (n, F) value through ``_reduce_rounds_kernel``."""
    return _table_call(_reduce_rounds_kernel,
                       (gather.astype(jnp.int32), mask.astype(jnp.int32)),
                       [x], True, interpret)


def combine_group(gather, mask, val, acc, *, interpret: bool = False):
    """One ReduceCombine group of (k, n) tables folded into (n, F) ``acc``
    through ``_combine_group_kernel``."""
    return _table_call(_combine_group_kernel,
                       (gather.astype(jnp.int32), mask.astype(jnp.int32)),
                       [val, acc], False, interpret)


def _rdma_exchange_kernel(partner_ref, x_ref, o_ref, send_sem, recv_sem):
    """Ring step: ship this device's buffer to ``partner`` over the
    interconnect. Partners are involutions, so the partner sends to us at
    the same time. The barrier handshake first makes sure the partner is
    inside this kernel (its output buffer live) before the DMA lands."""
    partner = partner_ref[0]
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, 1, device_id=partner,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 1)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref,
        dst_ref=o_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=partner,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    rdma.wait()


def _ring_exchange(x, partner, interpret):
    """Per-shard remote-DMA permute: send local ``x`` to ``partner`` (its
    index on the 1-D mesh axis, which is its logical device id there)."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()), pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _rdma_exchange_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=0),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(partner.reshape(1), x)


@functools.lru_cache(maxsize=None)
def _allreduce_executor(opt: _opt.OptimizedProgram, interpret: bool):
    gat, msk = _opt.stacked_combine_tables(opt)
    n = opt.n

    @jax.jit
    def run(x):
        out = reduce_rounds(gat, msk, x.reshape(n, -1), interpret=interpret)
        return out.reshape(x.shape)

    return run


@functools.lru_cache(maxsize=None)
def _matmul_executor(opt: _opt.OptimizedProgram, interpret: bool):
    from repro.kernels.block_matmul.ops import batched_matmul

    n = opt.n

    def combine_fn(acc, val, gather, mask):
        out = combine_group(gather, mask, val.reshape(n, -1),
                            acc.reshape(n, -1), interpret=interpret)
        return out.reshape(val.shape)

    def mul_fn(val, a):
        return batched_matmul(val, a, interpret=interpret)

    return jax.jit(_opt.build_jax_matmul(opt, mul_fn=mul_fn,
                                         combine_fn=combine_fn))


# ---------------------------------------------------------------------------
# The backend.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PallasFusedBackend:
    """Fused table replay + Pallas kernels on the reduce/contract hot path.

    ``interpret=None`` auto-selects: compiled kernels on TPU, interpreter
    everywhere else (the CPU CI path).
    """

    interpret: bool | None = None
    name: str = "pallas_fused"

    def _interp(self) -> bool:
        return (not _on_tpu()) if self.interpret is None else self.interpret

    def _optimized(self, program, kind: str) -> _opt.OptimizedProgram:
        prog = _opt.as_program(program)
        _check_kind(prog, kind)
        return program if isinstance(program, _opt.OptimizedProgram) \
            else _opt.optimize(program)

    # ------------------------------------------------------------- contract
    def run_alltoall(self, x, program):
        opt = self._optimized(program, "alltoall")
        return _opt.jax_alltoall(opt)(x)

    def run_allreduce(self, x, program):
        opt = self._optimized(program, "allreduce")
        return _allreduce_executor(opt, self._interp())(x)

    def run_broadcast(self, x, program, *, pipelined: bool = False):
        # fused replay is order-free: barrier == pipelined bit-for-bit
        opt = self._optimized(program, "broadcast")
        return _opt.jax_broadcast(opt)(x)

    def run_matmul(self, B, A, program):
        opt = self._optimized(program, "matmul")
        prog = opt.program
        if prog.grid is None:
            raise ValueError("matmul program lacks grid metadata")
        replay = _matmul_executor(opt, self._interp())
        b = _opt.jax_scatter_guest(_opt.jax_scatter_blocks(B, prog.grid), prog)
        a = _opt.jax_scatter_guest(_opt.jax_scatter_blocks(A, prog.grid), prog)
        return _opt.jax_gather_blocks(_opt.jax_gather_guest(replay(b, a), prog),
                                      prog.grid)

    # ------------------------------------------------------ per-shard ring
    def allreduce_shard(self, x, axis_name: str, program: CollectiveProgram):
        """Per-shard §4 all-reduce with the remote-DMA ring kernel: one
        RDMA exchange + local accumulate per round, inside the caller's
        ``shard_map`` over a 1-D axis of ``program.n`` devices. Interpret
        mode simulates the DMAs and semaphores across the host's devices;
        compiled mode needs TPU chips."""
        prog = _opt.as_program(program)
        _check_kind(prog, "allreduce")
        interpret = self._interp()
        if not interpret and not _on_tpu():
            raise RuntimeError(
                "compiled allreduce_shard needs TPU remote DMA; use "
                "interpret mode or run_allreduce on CPU hosts"
            )
        idx = jax.lax.axis_index(axis_name)
        for st in prog.comm_stages:
            if not st.is_full_permutation:
                raise ValueError(
                    "RDMA ring path handles native (full-involution) "
                    "programs; replay emulated programs via run_allreduce"
                )
            partner = jnp.asarray(st.inverse_np, jnp.int32)[idx]
            x = x + _ring_exchange(x, partner, interpret)
        return x
