"""Auto backend: route every call through the price-driven autotuner.

``AutoBackend`` satisfies the backend contract by DELEGATING: each call
asks ``runtime.autotune`` for the cheapest strategy at this call site's
``TuneKey`` (kind, D3 topology, message bytes, dtype, site) and dispatches
to the strategy's executor —

  * ``loop``          per-stage replay on the ``jax_ppermute`` backend
  * ``overlap``       the same program in ``start_step`` order
  * ``fused``         the ``optimize()`` table replay
  * ``pallas_fused``  the Pallas-kernel backend
  * ``xla``           the fused XLA collective (``lax.all_to_all``/``psum``)
  * ``overlap_fused`` the wave-ordered fused-table pipeline (all-to-all:
    single gather/scatter dispatch, and the fused dispatch+compute+combine
    round trip of ``alltoall_compute``)
  * ``sendrecv``      the exported per-device send/recv trace replayed by
    the NumPy interpreter (``runtime.export`` — device-free, never needs
    a mesh quorum, so it is exempt from the too-few-devices degrade)

Whole-array ``run_*`` calls tune at ``site="global"``; the per-shard
methods (valid inside a caller's shard_map, e.g. MoE dispatch) tune at
``site="shard"`` where the structural candidates are xla/loop/overlap
(+ overlap_fused for all-to-all).
Results are bit-identical across strategies (the backend contract), so
the tuner is free to switch on speed alone. Decisions are made in Python
at trace time — a jitted caller retraces only when the decision (a cache
lookup after the first call) changes.

Emulated (``active_devices``) programs never dispatch to ``xla``: the
fused op would mix idle devices into the result. ``get_backend("auto")``
instantiates this class.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.runtime import autotune as _at
from repro.runtime import optimize as _opt
from repro.runtime.program import CollectiveProgram, check_kind as _check_kind


def _chunk_bytes(x, kind: str, site: str = "shard") -> int:
    """Message bytes at this site: per-destination capacity chunk for
    all-to-all, the full per-device vector otherwise.

    The all-to-all chunk is ``site``-dependent because the buffers differ
    by a device axis: a shard-site ``x`` is (n, chunk...) so one leading
    dim strips to the chunk, while a global ``x`` is (n, n, chunk...) —
    dividing by ``x.shape[0]`` alone would key the tuner on the n-times
    larger full per-device buffer, a different bucket than the
    per-destination bytes ``_measure_closure`` times and ``models.moe``
    keys for the same exchange."""
    itemsize = np.dtype(x.dtype).itemsize
    if kind == "alltoall":
        div = x.shape[0] * (x.shape[1] if site == "global" else 1)
        return max(1, int(x.size) // max(1, div)) * itemsize
    return int(x.size) * itemsize


@functools.lru_cache(maxsize=None)
def _xla_collective(kind: str, n: int, axis_name: str, root: int = 0):
    """Jitted whole-array shard_map closure of the fused XLA op, cached per
    (kind, n, axis) — the ``xla`` strategy's executor at global sites."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.runtime.backends.jax_ppermute import _axis_mesh

    mesh = _axis_mesh(n, axis_name)
    if kind == "alltoall":
        body = lambda s: jax.lax.all_to_all(
            s[0], axis_name, split_axis=0, concat_axis=0)[None]
    elif kind == "allreduce":
        body = lambda s: jax.lax.psum(s, axis_name)
    else:  # broadcast from root: one masked psum
        body = lambda s: jax.lax.psum(jnp.where(
            jax.lax.axis_index(axis_name) == root, s, jnp.zeros_like(s)),
            axis_name)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name)))


@dataclasses.dataclass(frozen=True)
class AutoBackend:
    """Strategy-per-call-site dispatcher (see module docstring).

    ``tuner=None`` uses the process-wide ``autotune.get_autotuner()`` —
    pass an explicit ``Autotuner`` to pin mode/cache (tests, launchers)."""

    tuner: object | None = None
    name: str = "auto"

    def _tuner(self) -> _at.Autotuner:
        return self.tuner if self.tuner is not None else _at.get_autotuner()

    def _decide(self, kind: str, program: CollectiveProgram, nbytes: int,
                dtype, site: str, compute_us: int = 0) -> _at.Decision:
        emulated = program.active_devices is not None
        grid = program.grid if kind == "matmul" else None
        layout = _at.layout_for(program.n)
        return self._tuner().decide(
            kind, layout, nbytes, dtype=str(dtype), site=site, grid=grid,
            emulated=emulated, compute_us=compute_us)

    def _delegate(self, strategy: str, program):
        """(backend instance, program form) for a non-xla strategy."""
        from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend

        prog = _opt.as_program(program)
        if strategy == "pallas_fused":
            from repro.runtime.backends.pallas_fused import PallasFusedBackend

            return PallasFusedBackend(), prog
        if strategy == "sendrecv":
            from repro.runtime.backends.sendrecv import SendRecvBackend

            return SendRecvBackend(), prog
        if strategy == "overlap_fused":
            return JaxPpermuteBackend(overlap_fused=True), prog
        be = JaxPpermuteBackend(overlap=(strategy == "overlap"))
        return be, (_opt.optimize(prog) if strategy == "fused" else prog)

    @staticmethod
    def _global_strategy(dec: _at.Decision, n: int) -> str:
        """Analytic decisions can name a mesh-backed strategy the process
        cannot run (too few devices) — degrade to the fused global replay,
        which runs anywhere."""
        if dec.strategy in ("loop", "overlap", "xla", "overlap_fused"):
            import jax

            if jax.device_count() < n:
                return "fused"
        return dec.strategy

    # ------------------------------------------------- whole-array wrappers
    def _run(self, kind: str, x, program, *run_args, **run_kw):
        prog = _opt.as_program(program)
        _check_kind(prog, kind)
        dec = self._decide(kind, prog, _chunk_bytes(x, kind, "global"),
                           x.dtype, "global")
        strategy = self._global_strategy(dec, prog.n)
        if strategy == "xla":
            return _xla_collective(kind, prog.n, "df", prog.root or 0)(x)
        be, p = self._delegate(strategy, prog)
        return getattr(be, f"run_{kind}")(x, p, *run_args, **run_kw)

    def run_alltoall(self, x, program):
        return self._run("alltoall", x, program)

    def run_allreduce(self, x, program):
        return self._run("allreduce", x, program)

    def run_broadcast(self, x, program, *, pipelined: bool = False):
        prog = _opt.as_program(program)
        _check_kind(prog, "broadcast")
        dec = self._decide("broadcast", prog, _chunk_bytes(x, "broadcast"),
                           x.dtype, "global")
        # no global xla candidate for broadcast
        be, p = self._delegate(self._global_strategy(dec, prog.n), prog)
        return be.run_broadcast(x, p, pipelined=pipelined)

    def run_matmul(self, B, A, program):
        prog = _opt.as_program(program)
        _check_kind(prog, "matmul")
        nbytes = 0
        if prog.grid is not None:
            from repro.core.matmul import MatmulGrid

            X = B.shape[0] // MatmulGrid(*prog.grid).n
            nbytes = X * X * np.dtype(B.dtype).itemsize
        dec = self._decide("matmul", prog, nbytes, B.dtype, "global")
        be, p = self._delegate(self._global_strategy(dec, prog.n), prog)
        return be.run_matmul(B, A, p)

    # ---------------------------------------------------------- per-shard
    def alltoall(self, x, axis_name: str, program: CollectiveProgram):
        import jax

        prog = _opt.as_program(program)
        _check_kind(prog, "alltoall")
        dec = self._decide("alltoall", prog, _chunk_bytes(x, "alltoall"),
                           x.dtype, "shard")
        if dec.strategy == "xla":
            return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)
        be, p = self._delegate(dec.strategy, prog)
        return be.alltoall(x, axis_name, p)

    def alltoall_compute(self, x, axis_name: str, program: CollectiveProgram,
                         compute=None, compute_us: int = 0):
        """Fused round trip out[j] = compute_j(x[j]) (see the ppermute
        backend's ``alltoall_compute``), tuned as a full pipeline when the
        caller passes its ``compute_us`` estimate. Strategies other than
        ``overlap_fused`` fall back to the bit-identical sequential form:
        dispatch all-to-all, one batched ``compute`` over all n arrivals,
        combine all-to-all."""
        import jax

        prog = _opt.as_program(program)
        _check_kind(prog, "alltoall")
        dec = self._decide("alltoall", prog, _chunk_bytes(x, "alltoall"),
                           x.dtype, "shard", compute_us)
        if dec.strategy == "overlap_fused":
            be, p = self._delegate(dec.strategy, prog)
            return be.alltoall_compute(x, axis_name, p, compute)
        if dec.strategy == "xla":
            a2a = lambda v: jax.lax.all_to_all(
                v, axis_name, split_axis=0, concat_axis=0)
        else:
            be, p = self._delegate(dec.strategy, prog)
            a2a = lambda v: be.alltoall(v, axis_name, p)
        recv = a2a(x)
        return a2a(recv if compute is None else compute(recv))

    def allreduce(self, x, axis_name: str, program: CollectiveProgram):
        import jax

        prog = _opt.as_program(program)
        _check_kind(prog, "allreduce")
        dec = self._decide("allreduce", prog, _chunk_bytes(x, "allreduce"),
                           x.dtype, "shard")
        if dec.strategy == "xla":
            return jax.lax.psum(x, axis_name)
        be, p = self._delegate(dec.strategy, prog)
        return be.allreduce(x, axis_name, p)

    def broadcast(self, x, axis_name: str, program: CollectiveProgram,
                  *, pipelined: bool = False):
        import jax
        import jax.numpy as jnp

        prog = _opt.as_program(program)
        _check_kind(prog, "broadcast")
        dec = self._decide("broadcast", prog, _chunk_bytes(x, "broadcast"),
                           x.dtype, "shard")
        if dec.strategy == "xla" and prog.num_rounds == 1:
            return jax.lax.psum(jnp.where(
                jax.lax.axis_index(axis_name) == (prog.root or 0),
                x, jnp.zeros_like(x)), axis_name)
        be, p = self._delegate(dec.strategy if dec.strategy != "xla" else "loop",
                               prog)
        return be.broadcast(x, axis_name, p, pipelined=pipelined)

    def matmul(self, b, a, axis_name: str, program: CollectiveProgram):
        prog = _opt.as_program(program)
        _check_kind(prog, "matmul")
        nbytes = int(b.size) * np.dtype(b.dtype).itemsize
        dec = self._decide("matmul", prog, nbytes, b.dtype, "shard")
        be, p = self._delegate(dec.strategy, prog)
        return be.matmul(b, a, axis_name, p)
