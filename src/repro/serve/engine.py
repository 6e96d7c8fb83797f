"""Serving engine: batched decode with CONTINUOUS BATCHING — requests
join/leave slots at step boundaries; per-slot positions flow into the
decode step (scalar-or-(B,) position support in the attention caches).

The engine drives the pure ``decode_step``; prefill feeds prompt tokens
through the same cached path (functionally exact). Pod-scale shapes are
exercised via the dry-run; this engine runs for real on CPU-scale configs.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (len,) int32
    max_new_tokens: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


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
        self._step = jax.jit(
            lambda p, c, b, pos: M.decode_step(p, c, b, pos, self.cfg)
        )
        self.steps_run = 0
        self.tokens_out = 0  # decoded (committed) tokens, for tokens/s
        # host seconds per batched forward, logits on the host included;
        # the first one includes the decode step's compile
        self.forward_s: list[float] = []

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
        self.slot_req[slot] = req
        self.positions[slot] = 0
        # prefill: feed prompt tokens through the cached decode path; the
        # other slots advance with their own pending tokens (no stalls).
        for tok in req.prompt[:-1]:
            self.pending_tok[slot] = int(tok)
            self._advance(decode_slots=[s for s in self.slot_req if s != slot])
        self.pending_tok[slot] = int(req.prompt[-1])
        return True

    # -------------------------------------------------------------- step
    def _forward(self) -> np.ndarray:
        """One batched model forward over all slots (the seam subclasses
        override — ``serve.fleet.FleetEngine`` runs the staged decode here
        so MoE boundaries can be serviced by a combined host program).
        Returns host logits (slots, vocab) and updates ``self.cache``."""
        batch = {"token": jnp.asarray(self.pending_tok)}
        logits, self.cache = self._step(
            self.params, self.cache, batch, jnp.asarray(self.positions)
        )
        return np.asarray(logits, np.float32)

    def _advance(self, decode_slots):
        t0 = time.perf_counter()
        logits = self._forward()
        self.forward_s.append(time.perf_counter() - t0)
        return self._commit(logits, decode_slots)

    def _commit(self, logits, decode_slots):
        """Book one forward's results: bump positions, argmax-append for the
        decoding slots, retire finished requests and free their slots."""
        self.steps_run += 1
        self.positions[list(self.slot_req)] += 1
        for slot in decode_slots:
            req = self.slot_req[slot]
            nxt = int(np.argmax(logits[slot]))
            req.out.append(nxt)
            self.tokens_out += 1
            self.pending_tok[slot] = nxt
            if len(req.out) >= req.max_new_tokens or self.positions[slot] >= self.max_seq - 1:
                req.done = True
                del self.slot_req[slot]
        return logits

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
