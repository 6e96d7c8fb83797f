"""Runtime: Schedule IR -> one backend-neutral program -> pluggable backends.

``lowering.lower(schedule)`` turns ANY ``core.schedule.Schedule`` — all four
of the paper's algorithms — into a single ``program.CollectiveProgram``:
an ordered tuple of primitive stages (``Perm`` / ``Match`` /
``ReduceCombine`` / ``LocalContract``), each stamped with the IR
``(round_index, step)`` it came from and a ``start_step`` launch offset so
pipelined schedules survive lowering.

Backend interface contract
--------------------------
A backend executes programs; it never sees the IR. It must provide

    run_alltoall(x, program)                 # (n, n, ...) -> (n, n, ...)
    run_allreduce(x, program)                # (n, ...)    -> (n, ...)
    run_broadcast(x, program, pipelined=..)  # (n, ...) or (R, n, ...) waves
    run_matmul(B, A, program)                # (N·X, N·X) pair -> product

with identical results across backends (differential-testable bit-for-bit
on integer-valued floats). Obligations:

  * replay communication stages grouped by synchronous step — every stage
    of one ``(round_index, step)`` group reads the PRE-group values; the
    lowering guarantees distinct write targets within each stage, and
    across the stages of one group only ``ReduceCombine`` destinations may
    repeat (each arrival folds into the accumulator with a commutative
    combine, so replay order within a group cannot change results);
  * ``Perm``: full permutation of the per-device value; ``Match``: listed
    destinations replace their value; ``ReduceCombine``: destinations sum
    the arrival into an accumulator, identity pairs meaning a local (no
    link) contribution; ``LocalContract``: the named local compute steps
    of the matmul state machine (``load_b``/``mul_a``/``promote``/
    ``store_c``) over per-device state (val, acc, c);
  * honor ``pipelined``/``overlap`` by replaying in stable ``start_step``
    order — bit-identical to barrier order for any program whose schedule
    verified conflict-free under ``verify(pipelined=True)``;
  * use each stage's cached host index arrays (``sigma_np`` etc.) rather
    than rebuilding them per trace;
  * honor ``program.active_devices`` (emulated guest-on-host programs,
    below): devices outside it are IDLE — they must not contribute data to
    any active device's result, and their own slots pass through (inputs
    unchanged for allreduce/broadcast; outputs zero for alltoall/matmul).
    Stages of such programs are partial permutations/matchings that never
    name an idle device, so a conforming backend usually gets this for
    free; the reference backend additionally ASSERTS idle slots were
    untouched after every replay.

Emulation rewrite guarantees (``rewrite.emulate(program, embedding)``)
----------------------------------------------------------------------
Paper Property 2 as a program-to-program pass: a lowered guest D3(J,L)
program becomes a host D3(K,M)-sized program with every device id mapped
through ``Embedding.device_map`` and ``active_devices`` recording the
guest-ordered host image. The pass guarantees:

  * ``(round_index, step, start_step)`` stamps are preserved, so pipelined
    replay of the rewrite interleaves exactly like the guest's;
  * dilation-1: every rewritten pair is one physical host link — the guest
    schedule's conflict-freedom transfers without re-verification (and can
    be re-checked via ``rewrite.emulate_schedule`` + ``core.simulator``);
  * bit-exactness: replaying the rewrite on host arrays carrying the guest
    data at ``active_devices`` slots (``rewrite.scatter_guest``) yields, at
    those slots, exactly the guest program's result on any conforming
    backend;
  * rewrites are memoized per (program, embedding) — i.e. per (host,
    guest, c_set, p_set, program) — so repeated failover re-lowers reuse
    the built host index arrays instead of rebuilding them in jit traces.

Concurrent-guest guarantees (``combine.combine(programs)``)
-----------------------------------------------------------
N rewritten guest programs with pairwise-disjoint ``active_devices``
images merge into ONE host program (disjoint D3(J,L) workloads on one
mesh). What ``combine`` adds to the contract:

  * the combined program is an ordinary emulated program —
    ``active_devices`` is the guests' images concatenated in argument
    order — so every conforming backend replays it with NO new code: the
    idle-pass-through rules above already cover it;
  * stages from different guests sharing one ``(round_index, step,
    start_step)`` stamp are PACKED into a single partial stage (disjoint
    ``Perm``s become one partial permutation — one ppermute moves every
    guest's chunk), so the combined makespan is max(T_i) rounds, not Σ T_i;
  * per-guest isolation: a guest's stages only name its own devices, so
    each guest's slots carry bit-for-bit its solo (un-combined) result —
    any replay order preserving each guest's own stage order is exact;
  * conflicts are re-checked, not assumed: ``combine`` re-walks every
    synchronous step across guests (one packet per directed link; only
    ``ReduceCombine`` destinations repeat) and raises a structured
    ``GuestConflictError`` with the offending (step, link) — and
    ``combine.combine_schedules`` merges the guests' host-graph Schedule
    views so ``core.simulator.verify`` re-proves conflict-freedom on the
    literal host links;
  * matmul guests must share one local-contract skeleton (same grid
    shape/rounds) because ``load_b``/``mul_a``/``promote`` act on every
    device; combined matmul programs replay at the blocks level;
  * ``optimize`` fuses combined programs like any other: the stacked-σ
    exchange table spans all guests, so the fused replay is still one
    batched op per step group.

Optimizer pass guarantees (``optimize.optimize(program)``)
----------------------------------------------------------
The performance layer between lowering and execution: ``optimize`` fuses
every conflict-free step group of a program into one batched table op
(stacked-σ scatter for ``Perm`` groups, masked-gather tables for ``Match``
groups, stage-ordered (gather, mask) row stacks for ``ReduceCombine``
groups) and precomputes all per-stage host arrays into device-ready index
tensors, so replay is a single batched op or a ``lax.scan`` over tables
instead of a per-stage Python loop. The pass preserves:

  * **stamps** — fusion follows barrier ``(round_index, step)`` groups;
    because the schedule verified conflict-free under pipelined replay,
    the fused barrier-order result equals the ``start_step``-ordered one,
    so ``pipelined``/``overlap`` callers may substitute an optimized
    program freely;
  * **``active_devices``** — emulated programs fuse to partial tables
    (identity gathers + zero masks outside the embedded subset); idle
    pass-through holds exactly as for the unfused program, and the
    reference backend still asserts it;
  * **conflict-freedom** — only stages the lowering proved concurrent are
    merged; no fusion crosses a synchronous step;
  * **bit-exactness** — ``FusedCombine`` rows fold in stage order, group
    reads see pre-group values: every backend must produce bit-identical
    results for ``optimize(p)`` and ``p`` (differential-tested in
    ``tests/test_optimize.py``).

Every backend ``run_*`` accepts either representation. The optimized form
is the hot path: constant-size HLO regardless of program length (compile
time), one upload of stacked index tensors (trace time), one advanced-
indexing pass per group (host replay).

``backends.get_backend("jax_ppermute" | "reference" | "pallas_fused" |
"sendrecv" | "auto")`` instantiates the built-ins: ppermutes on a JAX
mesh (optionally overlapped), a pure-NumPy host replay used for
differential testing and device-free validation, the Pallas-fused
backend — optimized-table replay with Pallas kernels on the
ReduceCombine rounds and the §2 ``mul_a`` block contraction — and the
send/recv trace interpreter (below). The Pallas kernels run compiled on
TPU (where ``run_allreduce``'s exchange uses the remote-DMA ring
pattern) and under ``interpret=True`` everywhere else, so CPU CI
exercises the fused path bit-for-bit; interpret mode is a correctness
vehicle, not a performance one — see ``backends/pallas_fused.py`` for
the caveats. Conformance is executable: every registered backend is
swept against ``reference`` across all four algorithms and all program
forms by ``tests/test_backend_contract.py``.

Send/recv export guarantees (``export.export(program)``)
--------------------------------------------------------
The portable half of the collective compiler: any program — lowered,
optimized, emulated, combined — compiles to a versioned,
JSON-serializable :class:`~repro.runtime.export.DeviceTrace`, an ordered
op list PER DEVICE over five primitives (``send`` / ``recv`` /
``reduce`` / ``copy`` / ``contract``). What the export preserves:

  * **stamps** — every op keeps its ``(round_index, step)`` group and
    ``start_step`` launch window, so pipelined §3/§5 schedules export
    with their real overlap waves (``DeviceTrace.waves()``);
  * **static safety, re-proved** — ``export.validate`` checks the
    EXPORTED form (not the IR it came from) for link-conflict-freedom
    per synchronous step AND per overlap window, exact send/recv pairing
    per group, and structurally-empty op lists on idle devices; failures
    raise typed ``TraceValidationError`` subclasses;
  * **executability** — the ``sendrecv`` backend replays the trace alone
    (never the program stages) bit-identically to every other backend;
    ``to_json``/``from_json`` round-trip losslessly, so the JSON file is
    the whole program (``python -m repro.runtime.export`` validates
    saved traces from the CLI — the CI artifact check).

Autotuner guarantees (``autotune.Autotuner`` / the ``auto`` backend)
---------------------------------------------------------------------
The dispatcher that turns the three coexisting execution strategies into
one fast default path. Per call site — keyed on ``(kind, D3 topology,
bucketed message bytes, dtype, site)`` — it picks the cheapest of the
strategies structurally available there (per-stage ``loop`` replay,
``start_step``-ordered ``overlap``, fused ``optimize()`` tables, the
``pallas_fused`` backend, the device-free ``sendrecv`` trace replay, or
the plain ``xla`` collective), seeded by
``core.costmodel`` analytic prices and calibrated by one-shot measured
timings memoized in a schema-versioned on-disk cache. What it preserves:

  * **bit-exactness is free** — every candidate strategy satisfies the
    backend contract above, so switching strategies can never change a
    result, only its latency; emulated (``active_devices``) programs
    additionally exclude ``xla`` (the fused op would mix idle devices);
  * **determinism** — a warm cache returns the recorded decision without
    re-measurement; a corrupt or missing cache degrades to analytic
    seeding without error;
  * **escape hatches** — ``REPRO_AUTOTUNE=analytic`` (rank without
    measuring), ``REPRO_AUTOTUNE=off`` (pre-autotuner defaults), or
    ``REPRO_AUTOTUNE=<strategy>`` (pin one strategy) — the same knobs the
    ``Autotuner`` constructor takes programmatically.
"""

from repro.runtime import (  # noqa: F401
    autotune,
    backends,
    combine,
    export,
    lowering,
    optimize,
    program,
    rewrite,
)
