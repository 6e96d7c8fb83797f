"""Multi-tenant serving: N models share one mesh through ONE combined host
program.

Paper Property 2 packs disjoint D3(J,L) guests onto a D3(K,M) host;
``runtime.combine`` proved the program-level consequence (N guests'
collectives at makespan max(T_i) instead of ΣT_i). This module serves
THROUGH it:

* Every tenant model decodes via the staged generator forward
  (``models.model.decode_step_staged``), which suspends at each MoE
  boundary instead of computing the expert FFN inline.
* ``TenantFleet.step`` drives all tenants' generators in lockstep: at each
  boundary round it collects every paused tenant's dispatch array
  (``models.moe.moe_guest_dispatch``), scatters them to their guests' host
  slots (``runtime.combine.scatter_guests``), and issues ONE
  ``run_alltoall_compute`` replay of the combined pipelined program
  (``dist.collectives.concurrent_program(..., pipelined=1)``) — each chunk
  is processed AT its destination device with THAT tenant's expert shard
  and returned to its sender. One ppermute wave set carries all tenants'
  chunks; on the JAX backend the waves overlap the expert compute
  (PR 7's ``overlap_fused`` pipeline).
* Admission prefill services the single admitting tenant through the same
  combined program immediately (other guests' slots carry zeros — still
  bit-exact, by guest isolation), so tenants join mid-traffic without
  stalling the fleet.
* Churn is rewrite-only: ``evict`` / ``plan_eviction`` unseat tenants via
  ``MultiTenantCluster`` (cached re-combine) and the next boundary round
  replays the survivors' combined program. Surviving tenants' in-flight
  requests continue BIT-EXACT across the swap: engines and caches are
  per-tenant, and each survivor's stages inside any combined program are
  its own solo stages (the ``combine`` contract), so the re-combine is
  invisible to its tokens.

``combined=False`` is the time-multiplexed control: the same tenants, the
same staged decode, but each boundary round replays every tenant's SOLO
emulated program sequentially — ΣT_i rounds, the arm
``bench_multitenant_serving`` measures the combined fleet against.

Tenant compatibility: one combined replay moves one host-shaped array, so
all seated tenants must share the dispatch chunk signature
(E_loc, C, d, d_ff_expert) — same experts-per-guest-device, capacity,
model width and expert FFN width. Guest shapes and layer counts may
differ (a tenant with fewer MoE boundaries simply drops out of later
rounds of a step).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.emulation import Embedding, embed
from repro.core.topology import D3
from repro.dist.mesh import DeviceLayout
from repro.models import model as M
from repro.models import moe as MOE
from repro.serve.engine import Engine, Request
from repro.train.fault_tolerance import MultiTenantCluster


class FleetEngine(Engine):
    """An ``Engine`` whose forward is the staged eager decode: it pauses at
    every MoE boundary and hands ``(ffn_params, h2)`` to a service callable
    instead of computing the expert FFN inline. Driven two ways: the
    inherited ``_advance`` path (admission prefill, solo stepping) services
    each boundary immediately via ``service``; ``TenantFleet.step`` drives
    ``begin_forward``/``pump`` directly to interleave N tenants' boundaries
    into shared combined replays."""

    def __init__(self, cfg, params, batch_slots: int, max_seq: int, service):
        super().__init__(cfg, params, batch_slots, max_seq)
        self._service = service     # (ffn_params, h2) -> y
        self._gen = None
        self._last_tokens = None

    def begin_forward(self):
        """Start one staged forward over all slots; returns the first MoE
        boundary's ``(ffn_params, h2)`` or None if the step completed."""
        batch = {"token": jnp.asarray(self.pending_tok)}
        self._gen = M.decode_step_staged(
            self.params, self.cache, batch, jnp.asarray(self.positions), self.cfg
        )
        return self.pump(None)

    def pump(self, y):
        """Resume the staged forward with expert output ``y`` (None to
        start). Returns the next boundary's item, or None when the forward
        finished — its greedy tokens are then in ``_last_tokens`` and the
        cache is committed."""
        try:
            item = next(self._gen) if y is None else self._gen.send(y)
        except StopIteration as stop:
            logits, self.cache = stop.value
            self._last_tokens = np.asarray(logits, np.float32).argmax(-1)
            self._gen = None
            return None
        return item

    def _forward(self):
        item = self.begin_forward()
        while item is not None:
            item = self.pump(jnp.asarray(self._service(*item)))
        return self._last_tokens


@dataclasses.dataclass
class Tenant:
    """One seated model: its engine, its guest embedding, its traffic."""

    tid: int
    cfg: object
    engine: FleetEngine
    embedding: Embedding
    n_guest: int
    sig: tuple                 # (E_loc, C, d, d_ff_expert) dispatch signature
    queue: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)


class TenantFleet:
    """N small models as disjoint guests on one D3(K,M) host mesh, every
    tenant's MoE dispatch+combine routed through the single combined host
    program (module docstring has the full story).

    ``backend``: ``"reference"`` (device-free NumPy replay) or ``"jax"``
    (device-backed ``run_alltoall_compute`` — needs ``host_n`` devices).
    ``combined=False`` switches to the time-multiplexed control (one solo
    emulated replay per tenant per boundary round).
    """

    def __init__(self, host=(2, 2), *, backend="reference", max_seq: int = 64,
                 combined: bool = True):
        K, M_ = host
        self.cluster = MultiTenantCluster(DeviceLayout(D3(K, M_)))
        self.host = self.cluster.layout.topo
        self.max_seq = max_seq
        self.combined = combined
        self.backend = self._make_backend(backend)
        self.tenants: dict[int, Tenant] = {}   # insertion order = seat order
        self._next_tid = 0
        self._next_rid = 0
        self._owner = None          # host device -> (tid, guest device) cache
        self.steps_run = 0
        self.replays = 0            # program replays issued at boundaries
        self.rounds_replayed = 0    # Σ num_rounds over those replays
        self._tokens_evicted = 0

    @staticmethod
    def _make_backend(backend):
        if backend == "reference":
            from repro.runtime.backends.reference import NumpyReferenceBackend

            return NumpyReferenceBackend()
        if backend == "jax":
            from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend

            return JaxPpermuteBackend()
        return backend

    # -------------------------------------------------------------- admission
    def _free_cabinets(self):
        used = set()
        for t in self.tenants.values():
            used |= set(t.embedding.c_set)
        return [c for c in range(self.host.K) if c not in used]

    def _place(self, J: int, L: int) -> Embedding:
        """Cabinet-regime first-fit: each guest takes J whole free cabinets
        (disjoint cabinet sets need no position bookkeeping), so an evicted
        tenant's cabinets immediately free up for re-admission."""
        free = self._free_cabinets()
        if L > self.host.M or len(free) < J:
            raise ValueError(
                f"guest D3({J},{L}) does not fit: {len(free)} free cabinets "
                f"of {self.host.K}, host positions {self.host.M}"
            )
        return embed(self.host, J, L, c_set=tuple(free[:J]))

    def admit_model(self, cfg, params, *, guest=(1, 2), slots: int = 2) -> int:
        """Seat a model as a D3(J,L) guest: first-fit placement, cluster
        validation (image disjointness + derive-once program suite), and
        the uniform dispatch-signature check. Returns the tenant id."""
        m = getattr(cfg, "moe", None)
        if m is None:
            raise ValueError(
                "fleet tenants serve their expert dispatch through the "
                "combined program; a config without MoE has no dispatch "
                "to combine — serve it on a plain Engine"
            )
        J, L = guest
        n_guest = J * L * L
        if m.num_experts % n_guest:
            raise ValueError(
                f"E={m.num_experts} experts do not shard over the "
                f"D3({J},{L}) guest's {n_guest} devices"
            )
        sig = (m.num_experts // n_guest, MOE.guest_capacity(m, slots),
               cfg.d_model, m.d_ff_expert)
        for t in self.tenants.values():
            if t.sig != sig:
                raise ValueError(
                    "one combined replay moves one host-shaped array, so "
                    "every tenant must share the dispatch chunk signature "
                    f"(E_loc, C, d, f); seated tenants have {t.sig}, new "
                    f"tenant has {sig}"
                )
        emb = self._place(J, L)
        self.cluster.admit(emb)
        tid = self._next_tid
        self._next_tid += 1
        service = lambda fp, h2, _tid=tid: self._service_single(_tid, fp, h2)
        eng = FleetEngine(cfg, params, slots, self.max_seq, service)
        self.tenants[tid] = Tenant(tid=tid, cfg=cfg, engine=eng,
                                   embedding=emb, n_guest=n_guest, sig=sig)
        self._owner = None
        return tid

    # ---------------------------------------------------------------- traffic
    def submit(self, tid: int, prompt, max_new_tokens: int) -> Request:
        """Enqueue a request for tenant ``tid``; admitted immediately if a
        slot is free (prefill services its boundaries through the combined
        program right away), queued otherwise."""
        t = self.tenants[tid]
        req = Request(rid=self._next_rid, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new_tokens))
        self._next_rid += 1
        t.requests.append(req)
        if not t.engine.admit(req):
            t.queue.append(req)
        return req

    def step(self):
        """One lockstep decode step for every tenant with active slots: all
        staged forwards advance together, and each MoE boundary round is
        serviced by ONE combined replay carrying every paused tenant's
        chunks (``combined=False``: one solo replay per tenant instead)."""
        for t in self.tenants.values():
            while t.queue and t.engine.free_slots:
                t.engine.admit(t.queue.pop(0))
        active = {tid: t for tid, t in self.tenants.items() if t.engine.slot_req}
        if not active:
            return
        items = {}
        for tid, t in active.items():
            it = t.engine.begin_forward()
            if it is not None:
                items[tid] = it
        while items:
            ys = self._dispatch(items)
            nxt = {}
            for tid in items:
                it = active[tid].engine.pump(jnp.asarray(ys[tid]))
                if it is not None:
                    nxt[tid] = it
            items = nxt
        for t in active.values():
            t.engine._commit(t.engine._last_tokens,
                             decode_slots=list(t.engine.slot_req))
        self.steps_run += 1

    def run_to_completion(self, max_steps: int = 4096):
        for _ in range(max_steps):
            if not any(t.engine.slot_req or t.queue
                       for t in self.tenants.values()):
                break
            self.step()

    @property
    def tokens_out(self) -> int:
        return self._tokens_evicted + sum(
            t.engine.tokens_out for t in self.tenants.values())

    # ------------------------------------------------------------------ churn
    def evict(self, tid: int):
        """Voluntarily unseat tenant ``tid`` mid-traffic (its unfinished
        requests are dropped, ``done`` stays False) and re-combine the
        survivors via ``MultiTenantCluster.release`` — cached emulate +
        cached combine, so churn back to a previously-seen tenant set is
        free. Returns the cluster's ``TenantPlan``."""
        seat = list(self.tenants).index(tid)
        t = self.tenants.pop(tid)
        self._tokens_evicted += t.engine.tokens_out
        self._owner = None
        return self.cluster.release(seat)

    def fail(self, host_device: int) -> None:
        """Mark a host device failed (bookkeeping only; call
        ``plan_eviction`` to act on it)."""
        self.cluster.fail(host_device)

    def plan_eviction(self):
        """Failure-driven churn: evict exactly the tenants whose guest
        images contain a failed device (``MultiTenantCluster.plan_eviction``)
        and drop them from the fleet; survivors keep serving through the
        re-combined program from the next boundary round on."""
        seats = list(self.tenants)
        plan = self.cluster.plan_eviction()
        for pos in plan.evicted:
            t = self.tenants.pop(seats[pos])
            self._tokens_evicted += t.engine.tokens_out
        self._owner = None
        return plan

    # -------------------------------------------------------------- dispatch
    def _embeddings(self) -> tuple[Embedding, ...]:
        return tuple(t.embedding for t in self.tenants.values())

    def program(self):
        """The current tenant set's combined pipelined §3 program (cached
        in ``dist.collectives``, so churn re-combines are lookups)."""
        from repro.dist import collectives as coll

        return coll.concurrent_program("alltoall", self._embeddings(),
                                       pipelined=1)

    def _solo_program(self, emb: Embedding):
        from repro.dist import collectives as coll

        return coll.alltoall_program(DeviceLayout(emb.guest), emb, pipelined=1)

    def _host_owner(self) -> dict:
        if self._owner is None:
            self._owner = {}
            for tid, t in self.tenants.items():
                for gdev, hdev in enumerate(t.embedding.device_map):
                    self._owner[int(hdev)] = (tid, gdev)
        return self._owner

    def _service_single(self, tid: int, ffn_params, h2):
        """Service ONE tenant's boundary (admission prefill / solo
        stepping) — still through the fleet's replay path, other guests'
        slots zero."""
        return self._dispatch({tid: (ffn_params, h2)})[tid]

    def _dispatch(self, items: dict) -> dict:
        """items: {tid: (ffn_params, h2)} — one boundary round. Returns
        {tid: y} with y the (B, S, d) expert output for that tenant."""
        Xs, states = {}, {}
        for tid, (fp, h2) in items.items():
            t = self.tenants[tid]
            X, st = MOE.moe_guest_dispatch(fp, np.asarray(h2, np.float32),
                                           t.cfg, t.n_guest)
            Xs[tid], states[tid] = X, st
        backs = (self._replay_combined(items, Xs) if self.combined
                 else self._replay_muxed(items, Xs))
        out = {}
        for tid, (fp, h2) in items.items():
            out[tid] = MOE.moe_guest_combine(
                backs[tid], states[tid], fp, np.asarray(h2, np.float32))
        return out

    def _replay_combined(self, items: dict, Xs: dict) -> dict:
        from repro.runtime.combine import extract_guest, scatter_guests

        proto = next(iter(Xs.values()))
        chunk_shape = proto.shape[2:]          # (E_loc, C, d), sig-uniform
        arrays, guests, order = [], [], []
        for tid, t in self.tenants.items():
            arrays.append(Xs.get(tid, np.zeros(
                (t.n_guest, t.n_guest, *chunk_shape), np.float32)))
            guests.append(t.embedding)
            order.append(tid)
        Xh = scatter_guests(arrays, guests, axes=(0, 1))
        prog = self.program()
        out = self._replay(prog, items, Xh)
        self.replays += 1
        self.rounds_replayed += prog.num_rounds
        return {tid: extract_guest(out, emb, axes=(0, 1))
                for tid, emb in zip(order, guests) if tid in Xs}

    def _replay_muxed(self, items: dict, Xs: dict) -> dict:
        """Time-multiplexed control: each tenant's chunks through its own
        solo emulated program, sequentially — the ΣT_i arm."""
        from repro.runtime.combine import extract_guest, scatter_guests

        backs = {}
        for tid in items:
            t = self.tenants[tid]
            prog = self._solo_program(t.embedding)
            Xh = scatter_guests([Xs[tid]], [t.embedding], axes=(0, 1))
            out = self._replay(prog, {tid: items[tid]}, Xh)
            self.replays += 1
            self.rounds_replayed += prog.num_rounds
            backs[tid] = extract_guest(out, t.embedding, axes=(0, 1))
        return backs

    def _replay(self, prog, items: dict, Xh: np.ndarray) -> np.ndarray:
        """One ``run_alltoall_compute`` round trip of ``Xh`` through
        ``prog``, computing each arriving chunk's expert FFN with the
        owning tenant's weights for THAT destination device."""
        if getattr(self.backend, "name", "") == "reference":
            owner = self._host_owner()
            shards = {tid: MOE.guest_expert_shards(items[tid][0],
                                                   self.tenants[tid].n_guest)
                      for tid in items}
            # the reference oracle stacks chunks from EVERY active source at
            # each destination; in a combined program the other guests'
            # slots are structural zeros (no cross-guest links exist), so
            # restrict the FFN to the owner guest's source rows
            act = (np.flatnonzero(prog.active_mask_np)
                   if prog.active_devices is not None
                   else np.arange(prog.n))
            pos = {int(d): k for k, d in enumerate(act)}
            rows = {tid: np.asarray(
                [pos[int(d)] for d in self.tenants[tid].embedding.device_map],
                np.intp) for tid in items}

            def compute(j, chunks):
                own = owner.get(int(j))
                if own is None or own[0] not in shards:
                    return np.zeros_like(chunks)
                wi, wg, wo = shards[own[0]]
                g, r = own[1], rows[own[0]]
                out = np.zeros_like(chunks)
                out[r] = MOE.guest_expert_ffn_np(chunks[r], wi[g], wg[g], wo[g])
                return out

            return self.backend.run_alltoall_compute(Xh, prog, compute)

        # device-backed path: per-device weight rows scattered host-sized,
        # the stable module-level compute keeps the compiled closure cached
        from repro.runtime.combine import scatter_guests

        ws, guests = [], []
        for tid in items:
            t = self.tenants[tid]
            ws.append(MOE.guest_expert_shards(items[tid][0], t.n_guest))
            guests.append(t.embedding)
        WI, WG, WO = (scatter_guests([w[i] for w in ws], guests, axes=(0,))
                      for i in range(3))
        out = self.backend.run_alltoall_compute(
            jnp.asarray(Xh), prog, MOE.guest_expert_ffn,
            weights=(jnp.asarray(WI), jnp.asarray(WG), jnp.asarray(WO)))
        return np.asarray(out, np.float32)

    # ------------------------------------------------------------- reporting
    def collective_report(self, tuner=None) -> dict:
        """The combined-site autotuner decision for this tenant set plus
        the fleet's replay evidence: combined vs time-muxed round counts
        and the replays issued so far."""
        from repro.runtime import autotune

        embs = self._embeddings()
        if not embs:
            return {"status": "n/a", "reason": "no tenants seated"}
        t0 = next(iter(self.tenants.values()))
        E_loc, C, d = t0.sig[:3]
        nbytes = E_loc * C * d * 4
        tuner = tuner or autotune.get_autotuner()
        dec = tuner.decide_combined("alltoall", embs, nbytes=nbytes,
                                    dtype="float32")
        comb = self.program()
        mux_rounds = sum(self._solo_program(e).num_rounds for e in embs)
        return {
            "status": "ok",
            "tenants": len(embs),
            "key": str(dec.key),
            "strategy": dec.strategy,
            "source": dec.source,
            "combined_rounds": comb.num_rounds,
            "time_mux_rounds": int(mux_rounds),
            "replays": self.replays,
            "rounds_replayed": self.rounds_replayed,
            "analytic_us": {k: round(v, 1) for k, v in dec.analytic_us.items()},
            "measured_us": {k: round(v, 1) for k, v in dec.measured_us.items()},
        }
