"""Serving engine: batched decode with CONTINUOUS BATCHING — requests
join/leave slots at step boundaries; per-slot positions flow into the
decode step (scalar-or-(B,) position support in the attention caches).

The engine drives the pure ``decode_step``; prefill feeds prompt tokens
through the same cached path (functionally exact). Pod-scale shapes are
exercised via the dry-run; this engine runs for real on CPU-scale configs.

Observability: the engine marks its seams with ``jax.profiler``
annotations, which land in a profiler trace beside the device's
operations when one is being recorded and cost about a microsecond each
when none is: ``engine.admit(rid, prompt_tokens)``, then per batched
forward ``engine.step(kind, slots[, rid])`` holding ``engine.inputs``
(host-to-device copies), ``engine.dispatch`` (the jitted call until it
returns), ``engine.fetch`` (waiting for each slot's greedy token and
copying it to the host) and ``engine.commit``. ``Engine.stats`` counts
the work; ``Request`` carries its own timestamps.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import model as M


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (len,) int32
    max_new_tokens: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # ``time.perf_counter()`` seconds. ``arrival`` is stamped by whoever
    # queues the request; the engine stamps when ``admit`` seats it and
    # when its first token is committed. Queue wait: admitted_at - arrival.
    arrival: float | None = None
    admitted_at: float | None = None
    first_token_at: float | None = None


def greedy_step(params, cache, batch, positions, cfg):
    """``decode_step`` returning each slot's greedy next token (the first
    index of its largest logit) in place of the logits, in the same
    program: the host fetches one id per slot, not (slots, vocab) logits
    to search."""
    logits, cache = M.decode_step(params, cache, batch, positions, cfg)
    return jnp.argmax(logits, axis=-1), cache


@dataclasses.dataclass
class EngineStats:
    """What the engine has done since it started or since ``reset()``."""
    steps: int = 0             # batched forwards committed
    prefill_steps: int = 0     # of them, run inside ``admit`` (a prompt token)
    tokens_processed: int = 0  # occupied slots, summed over steps
    decode_tokens: int = 0     # tokens committed to requests
    context: int = 0           # positions in use (position + 1), summed
    experts_run: int = 0       # held experts run, summed over steps and MoE layers

    def reset(self):
        self.__init__()


class Engine:
    def __init__(self, cfg, params, batch_slots: int, max_seq: int):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_seq = max_seq
        self.cache = M.init_cache(cfg, batch_slots, max_seq, dtype=jnp.float32)
        self.positions = np.zeros(batch_slots, np.int32)  # next write index
        self.pending_tok = np.zeros(batch_slots, np.int32)
        self.slot_req: dict[int, Request] = {}
        # the cache is donated: each step updates it in place and returns
        # it, and the previous ``self.cache`` is then deleted, so nothing
        # may hold it across a step
        self._step = jax.jit(
            lambda p, c, b, pos: greedy_step(p, c, b, pos, self.cfg),
            donate_argnums=(1,),
        )
        self._reset_states = None
        if any(mixer != "attn" for mixer, _ in cfg.layer_kinds()):
            self._reset_states = jax.jit(
                lambda c, slot: M.reset_states(c, self.cfg, slot), donate_argnums=(0,)
            )
        self.stats = EngineStats()
        self._experts_total = 0  # the cache's ``experts_run`` last read
        self._prefill_rid = None  # the request ``admit`` is prefilling

    @property
    def steps_run(self) -> int:
        return self.stats.steps

    @property
    def tokens_out(self) -> int:
        """Decoded (committed) tokens, for tokens/s."""
        return self.stats.decode_tokens

    @property
    def free_slots(self):
        return [s for s in range(self.slots) if s not in self.slot_req]

    # ------------------------------------------------------------- admit
    def admit(self, req: Request) -> bool:
        """Seat ``req`` in a free slot and prefill its prompt.

        CO-ADVANCE SEMANTICS (intended, tested): prefill feeds the prompt
        through the same batched decode path, one engine step per prompt
        token, and every OTHER active slot DECODES during those steps —
        continuous batching has no prefill stall, so the tokens the other
        slots emit while a prompt streams in are real output, identical to
        what they would have produced solo, and they count against those
        requests' ``max_new_tokens`` budgets exactly like any decoded
        token (a request can even finish mid-prefill; its slot frees for
        the next ``admit``). Prefill steps are NOT charged to the admitted
        request's budget — its ``out`` stays empty until the first decode
        step after admission.
        """
        free = self.free_slots
        if not free:
            return False
        slot = free[0]
        with TraceAnnotation("engine.admit", rid=req.rid,
                             prompt_tokens=len(req.prompt)):
            req.admitted_at = time.perf_counter()
            self.slot_req[slot] = req
            self.positions[slot] = 0
            if self._reset_states is not None:  # the slot's last request's state
                self.cache = self._reset_states(self.cache, slot)
            # prefill: feed prompt tokens through the cached decode path;
            # the other slots advance with their own pending tokens (no
            # stalls).
            self._prefill_rid = req.rid
            try:
                for tok in req.prompt[:-1]:
                    self.pending_tok[slot] = int(tok)
                    self._advance(decode_slots=[s for s in self.slot_req if s != slot])
            finally:
                self._prefill_rid = None
            self.pending_tok[slot] = int(req.prompt[-1])
        return True

    # -------------------------------------------------------------- step
    def _forward(self) -> np.ndarray:
        """One batched model forward over all slots (the seam subclasses
        override). Returns each slot's greedy next token on the host
        (slots,) and updates ``self.cache``."""
        tokens = self._dispatch()
        with TraceAnnotation("engine.fetch"):
            # the ids and the cache's running count of experts run, together
            tokens, total = jax.device_get((tokens, self.cache["experts_run"]))
        self.stats.experts_run += (int(total) - self._experts_total) % 2 ** 32
        self._experts_total = int(total)
        return tokens

    def _dispatch(self) -> jax.Array:
        """Copy the step's inputs to the device and launch the decode step;
        returns its greedy tokens, still on the device. Only the occupied
        slots, the one being prefilled among them, are live: the MoE
        layers route no free slot's stale token."""
        with TraceAnnotation("engine.inputs"):
            live = np.zeros(self.slots, bool)
            live[list(self.slot_req)] = True
            batch = {"token": jnp.asarray(self.pending_tok), "live": jnp.asarray(live)}
            positions = jnp.asarray(self.positions)
        with TraceAnnotation("engine.dispatch"):
            tokens, self.cache = self._step(self.params, self.cache, batch, positions)
        return tokens

    def _advance(self, decode_slots):
        if self._prefill_rid is None:
            tags = {"kind": "decode"}
        else:
            tags = {"kind": "prefill", "rid": self._prefill_rid}
        with TraceAnnotation("engine.step", slots=len(self.slot_req), **tags):
            tokens = self._forward()
            return self._commit(tokens, decode_slots)

    def _commit(self, tokens, decode_slots):
        """Book one forward's results: count it, bump positions, append
        each decoding slot's greedy token (``tokens``, from ``_forward``),
        retire finished requests and free their slots."""
        with TraceAnnotation("engine.commit"):
            active = list(self.slot_req)
            st = self.stats
            st.steps += 1
            st.prefill_steps += self._prefill_rid is not None
            st.tokens_processed += len(active)
            st.context += int(self.positions[active].sum()) + len(active)
            st.decode_tokens += len(decode_slots)
            self.positions[active] += 1
            now = time.perf_counter()
            for slot in decode_slots:
                req = self.slot_req[slot]
                nxt = int(tokens[slot])
                if not req.out:
                    req.first_token_at = now
                req.out.append(nxt)
                self.pending_tok[slot] = nxt
                if len(req.out) >= req.max_new_tokens or self.positions[slot] >= self.max_seq - 1:
                    req.done = True
                    del self.slot_req[slot]
        return tokens

    def step(self):
        """One decode step for every active slot (batched)."""
        if not self.slot_req:
            return
        self._advance(decode_slots=list(self.slot_req))

    def run_to_completion(self, max_steps=4096):
        for _ in range(max_steps):
            if not self.slot_req:
                break
            self.step()

    # ---------------------------------------------------------- reporting
    def collective_report(self, rules=None, tuner=None) -> dict:
        """What the price-driven autotuner picks for this engine's MoE
        dispatch site (the §3 all-to-all boundary): chosen strategy, its
        source (measured/cache/analytic/forced), and the paper's priced
        rounds. ``rules`` defaults to the active sharding rules; an
        unsharded engine (single device, no launcher) reports n/a."""
        from repro.dist import sharding as SH
        from repro.runtime import autotune

        if rules is None:
            act = SH.active()
            rules = act[0] if act else None
        if rules is None:
            return {"status": "n/a", "reason": "no active sharding rules"}
        return autotune.moe_site_report(
            self.cfg, rules, n_tokens=self.slots, tuner=tuner
        )
