"""Open-loop serving through the program's continuous-batching engine.

Requests arrive on a schedule, whether or not earlier ones have finished:
a Poisson process at ``rate_per_s``. Each block of ``block`` requests holds
the same inter-arrival gaps (exponential quantiles) and the same prompt
and output lengths (lognormal quantiles), in an order and pairing that
changes from block to block, so the supply never runs out. The order is
the same for every seed: at four fifths of the knee the queue in front of
the serialized prefill makes the time to first token depend on the order
of arrivals far more than on anything else, so an order drawn from the
seed would change the work with the seed. The seed draws the token ids,
uniform over the vocabulary, and with them the routing, the served tokens
and the sample that the check compares.
Requests are admitted through ``Engine.admit`` and decoded through
``Engine.step`` as ``repro.launch.serve`` drives them, greedily; a
request's time to first token runs from its scheduled arrival.

Set-up compiles the decode step, then runs the schedule for
``warm_seconds`` so that the window starts with the queue and the slots
as the load keeps them. The window then runs the schedule on for its
seconds; a traced window continues it.

Correctness: after the windows, the longest finished request and others
drawn from the seed are run through the float32 reference over prompt and
served tokens. At each served position the gap is how far the served
token's reference logit lies below the reference's best. The number
compared, ``off_best_share``, is the share of served tokens whose gap
exceeds ``check.gap_tolerance``. Not the widest gap: where the float32
router's k-th and (k+1)-th logits nearly tie, the bfloat16 router may
rightly pick the other expert, and that one token's logits (and, through
attention, a few after it) then move by whole units, so the widest gap
reads the closest tie of the sample, for the program as for any other.
"""

from __future__ import annotations

import statistics
import time

import jax
import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lognormal quantiles (median, sigma), rounded and clipped."""
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    v = spec["median"] * np.exp(spec["sigma"] * np.asarray(z))
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential quantiles of mean ``1 / rate`` seconds."""
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate


class Schedule:
    """Request ``i``: its arrival offset in seconds, prompt ids and
    output length, made block by block from the seed as needed."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.t, self.seed, self.vocab = traffic, seed, vocab
        b = traffic["block"]
        self.pl = quantiles(traffic["prompt_tokens"], b)
        self.ol = quantiles(traffic["output_tokens"], b)
        self.gap = gaps(traffic["rate_per_s"], b)
        self.blocks: list = []
        self.at = 0.0

    def __getitem__(self, i: int):
        b = self.t["block"]
        while len(self.blocks) * b <= i:
            order = np.random.default_rng([len(self.blocks)])
            ids = np.random.default_rng([self.seed, len(self.blocks)])
            block = []
            for g, p, o in zip(order.permutation(self.gap), order.permutation(self.pl),
                               order.permutation(self.ol)):
                self.at += g
                block.append((self.at, ids.integers(0, self.vocab, int(p)).astype(np.int32),
                              int(o)))
            self.blocks.append(block)
        return self.blocks[i // b][i % b]


def _engine_class():
    from repro.serve.engine import Engine

    class TimedEngine(Engine):
        """The program's engine with the benchmark's clock and counters
        around its own seams; the arithmetic is the engine's."""

        def __init__(self, *args):
            super().__init__(*args)
            self.last_token: dict[int, float] = {}
            self.slot_of: dict[int, int] = {}  # rid -> the slot it was seated in
            self._prefilling = False
            self.reset_counters()

        def reset_counters(self):
            self.n = {"steps": 0, "prefill_steps": 0, "decode_tokens": 0,
                      "tokens_processed": 0, "context": 0, "top_slot": 0}
            self.firsts: list[tuple[int, float]] = []  # (rid, first token time)
            self.gaps: list[float] = []                # between tokens
            self.finished: list = []

        def admit(self, req):
            if self.free_slots:
                self.slot_of[req.rid] = self.free_slots[0]
            self._prefilling = True
            try:
                with jax.profiler.TraceAnnotation("bench.admit"):
                    return super().admit(req)
            finally:
                self._prefilling = False

        def _forward(self):
            n = self.n
            n["steps"] += 1
            n["prefill_steps"] += self._prefilling
            active = list(self.slot_req)
            n["tokens_processed"] += len(active)
            n["context"] += int(sum(self.positions[s] + 1 for s in active))
            n["top_slot"] = max(n["top_slot"], max(active, default=0))
            with jax.profiler.TraceAnnotation("bench.forward"):
                return super()._forward()

        def _commit(self, logits, decode_slots):
            reqs = [self.slot_req[s] for s in decode_slots]
            with jax.profiler.TraceAnnotation("bench.commit"):
                out = super()._commit(logits, decode_slots)
            now = time.perf_counter()
            self.n["decode_tokens"] += len(reqs)
            for r in reqs:
                prev = self.last_token.get(r.rid)
                if prev is None:
                    self.firsts.append((r.rid, now))
                else:
                    self.gaps.append(now - prev)
                self.last_token[r.rid] = now
                if r.done:
                    self.finished.append(r)
            return out

    return TimedEngine


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic

    # -------------------------------------------------------------- setup
    def setup(self):
        from repro.serve.engine import Request

        fam, t = self.ctx.family, self.traffic
        params = fam.make_params(self.cfg, self.ctx.seed)
        eng = _engine_class()(fam.program_config(self.cfg), params,
                              t["slots"], t["max_seq"])
        # compile the one decode-step shape, then forget the warm request
        eng.admit(Request(-1, np.zeros(2, np.int32), 1))
        eng.step()
        eng.slot_req.clear()
        eng.positions[:] = 0
        eng.last_token.clear()
        self.eng = eng
        self.schedule = Schedule(t, self.ctx.seed,
                                 self.cfg.get("token_vocab", self.cfg["vocab_size"]))
        self.next_req = self.rid_base = 0
        self.submitted: dict[int, float] = {}
        self.queue: list = []
        self.finished: list = []
        self.t0 = time.perf_counter()
        self.run(t["warm_seconds"])

    def restart(self, rate: float):
        """Finish the requests in flight, drop the queue, and start the
        schedule anew at ``rate`` requests per second (for a sweep)."""
        eng = self.eng
        while eng.slot_req:
            eng.step()
        self.finished += eng.finished
        eng.finished = []
        self.queue.clear()
        self.traffic = dict(self.traffic, rate_per_s=rate)
        self.schedule = Schedule(self.traffic, self.ctx.seed, self.schedule.vocab)
        self.rid_base += self.next_req
        self.next_req = 0
        self.t0 = time.perf_counter()

    def _turn(self):
        """Queue what has arrived, admit what fits, then one decode step;
        with nothing in flight, wait for the next arrival."""
        from repro.serve.engine import Request

        eng, now = self.eng, time.perf_counter()
        while True:
            at, prompt, n_out = self.schedule[self.next_req]
            if self.t0 + at > now:
                break
            rid = self.rid_base + self.next_req
            self.submitted[rid] = self.t0 + at
            self.queue.append(Request(rid, prompt, n_out))
            self.next_req += 1
        while self.queue and eng.free_slots:
            eng.admit(self.queue.pop(0))
        if eng.slot_req:
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
        else:
            time.sleep(max(0.0, min(self.t0 + at - now, 0.05)))
        self.finished += eng.finished
        eng.finished = []

    # ------------------------------------------------------------- window
    def run(self, seconds: float) -> dict:
        eng = self.eng
        eng.reset_counters()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._turn()
        w = dict(eng.n, seconds=time.perf_counter() - t0, slots=eng.slots,
                 queued=len(self.queue))
        w["ttft"] = [at - self.submitted[rid] for rid, at in eng.firsts]
        w["itl"] = list(eng.gaps)
        return w

    def end_to_end(self, w: dict) -> dict:
        return {"ttft_p90_ms": 1e3 * float(np.percentile(w["ttft"], 90)),
                "itl_p95_ms": 1e3 * float(np.percentile(w["itl"], 95))}

    def describe(self, w: dict) -> str:
        return (f"{w['steps']} engine steps ({w['prefill_steps']} carrying a "
                f"prompt token), {w['decode_tokens']} tokens, {len(w['ttft'])} "
                f"first tokens, {len(w['itl'])} token gaps, {w['queued']} queued "
                f"at the end, highest slot in use {w['top_slot']}, "
                f"{len(self.finished)} requests finished so far")

    def attempted(self, w: dict) -> tuple[int, int]:
        """Requests whose first token fell in the window; none fails
        quietly (an exception ends the run)."""
        return len(w["ttft"]), 0

    def release(self):
        self.eng = None

    # -------------------------------------------------------- correctness
    def sample(self) -> list:
        """The longest finished request and ``check.requests - 1`` others
        drawn from the seed."""
        done = sorted(self.finished, key=lambda r: r.rid)
        if not done:
            return []
        longest = max(done, key=lambda r: (len(r.out), -r.rid))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([self.ctx.seed, 1])
        k = min(len(rest), self.traffic["check"]["requests"] - 1)
        pick = rng.choice(len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def check(self, quant=None) -> dict:
        """``off_best_share`` of the sample; with ``quant`` the reference at
        that precision stands in for the program (the control): the token
        it puts first at each served position is judged instead."""
        reqs = self.sample()
        self._refs = getattr(self, "_refs", {})
        if not reqs:
            return {"off_best_share": 1.0}
        ref = self.ctx.reference
        pad = self.traffic["check"]["pad_to"]
        gaps = []
        for r in reqs:
            P, n = len(r.prompt), len(r.out)
            seq = np.zeros(pad, np.int32)
            seq[:P] = r.prompt
            seq[P:P + n - 1] = r.out[:-1]
            if r.rid not in self._refs:
                self._refs[r.rid] = np.asarray(
                    ref.logits(self.cfg, self.ctx.seed, seq))[P - 1:P - 1 + n]
            z = self._refs[r.rid]
            if quant is None:
                chosen = np.asarray(r.out)
            else:
                zq = np.asarray(ref.logits(self.cfg, self.ctx.seed, seq, quant))
                chosen = zq[P - 1:P - 1 + n].argmax(-1)
            gaps.append(z.max(-1) - z[np.arange(n), chosen])
        g = np.concatenate(gaps)
        tol = self.traffic["check"]["gap_tolerance"]
        self.ctx.log(f"compared {g.size} served tokens of {len(reqs)} requests "
                     f"(rids {[r.rid for r in reqs]}): {int((g > tol).sum())} lie more "
                     f"than {tol} below the reference's best; widest gap {float(g.max())!r}, "
                     f"99th percentile {float(np.percentile(g, 99))!r} (not compared)")
        return {"off_best_share": float(np.mean(g > tol))}
