"""Training steps back to back through the program's train step.

The step is ``repro.train.train_step.make_train_step`` with the mix's
settings and optimizer, jitted with the parameters and optimizer state
donated, as ``repro.launch.train`` runs it; the loss of every step is read
on the host, ``ahead_steps`` steps behind the step last dispatched.
Batches are ``batch`` x ``seq`` token ids, uniform over the vocabulary,
made on the device from the seed: ``distinct_batches`` of them, used in
turn.

Set-up builds the state, compiles the step, and drives that same step
through its first ``check.steps`` steps on the first batches, keeping for
the check each step's loss, the norm of each leaf's first gradient as the
optimizer took it (its first moment after one step, over ``1 - beta1``),
and the norm of each leaf's change over those steps. The window then runs
the same step on the same state.

Correctness compares those readings with the float32 reference's
(``bench/reference/decoder.py``): ``loss_gap``, the largest |difference|
of a step's loss; ``grad_gap`` and ``change_gap``, the worst leaf's
|difference| of norms over the larger of the reference's norm of that
leaf and of the median leaf. Leaves whose reference gradient is under a
thousandth of the median leaf's are left out of ``change_gap``: AdamW
moves them by round-off alone.
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

_STACK = "['stack']"


def _sq(path, a):
    """Per-leaf squared norm; per layer for a leaf stacked over layers."""
    a = a.astype(jnp.float32) ** 2
    if jax.tree_util.keystr(path).startswith(_STACK):
        return jnp.sum(a, axis=tuple(range(1, a.ndim)))
    return jnp.sum(a)[None]


@jax.jit
def _norms(tree):
    return jax.tree.map(jnp.sqrt, jax.tree_util.tree_map_with_path(_sq, tree))


@jax.jit
def _change_norms(new, old):
    return _norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                               new, old))


def named(norms_tree, n_layers: int) -> dict:
    """Norms keyed by the reference's leaf names (``embed``,
    ``L<i>.<name>``)."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(norms_tree)[0]:
        v = np.asarray(v)
        s = jax.tree_util.keystr(path)
        if s.startswith(_STACK):
            leaf = path[-1].key
            out.update({f"L{i}.{leaf}": float(x) for i, x in enumerate(v)})
        elif s.startswith("['embed']"):
            out["embed"] = float(v[0])
        else:
            out[s] = float(v[0])
    return out


def leaf_gaps(a: dict, b: dict, keys) -> dict:
    """Each leaf's |difference| of norms over the larger of the reference
    leaf's norm and the median leaf's."""
    med = float(np.median(list(b.values())))
    return {k: abs(a[k] - b[k]) / max(b[k], med) for k in keys}


def compare(prog: dict, ref: dict, log=None) -> dict:
    """The three numbers compared (see the module docstring); ``log`` is
    told the worst leaf of each."""
    g = ref["grad_norms"]
    g_med = float(np.median(list(g.values())))
    moved = [k for k, v in g.items() if v >= 1e-3 * g_med]
    grad = leaf_gaps(prog["grad_norms"], g, list(g))
    change = leaf_gaps(prog["change_norms"], ref["change_norms"], moved)
    if log:
        for name, gaps in (("grad_gap", grad), ("change_gap", change)):
            k = max(gaps, key=gaps.get)
            log(f"{name}: worst leaf {k} {gaps[k]!r}, median leaf "
                f"{float(np.median(list(gaps.values())))!r}")
    return {"loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"])),
            "grad_gap": max(grad.values()), "change_gap": max(change.values())}


def make_batches(cfg: dict, traffic: dict, seed: int, n: int) -> list:
    vocab = cfg.get("token_vocab", cfg["vocab_size"])
    shape = (traffic["batch"], traffic["seq"])
    make = jax.jit(lambda k, i: jax.random.randint(
        jax.random.fold_in(W.key_for(k, "batch"), i), shape, 0, vocab, jnp.int32))
    key = W.seed_key(seed)
    return [make(key, i) for i in range(n)]


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic

    def _opt(self):
        from repro.train.optimizer import OptConfig

        o = dict(self.traffic["optimizer"])
        o["betas"] = tuple(o["betas"])
        return OptConfig(**o)

    # -------------------------------------------------------------- setup
    def setup(self):
        from repro.train import optimizer as O
        from repro.train.train_step import TrainSettings, make_train_step

        fam, t = self.ctx.family, self.traffic
        opt = self._opt()
        params = fam.make_params(self.cfg, self.ctx.seed)
        opt_state = jax.jit(lambda p: O.init_state(p, opt))(params)
        self.step_fn = jax.jit(
            make_train_step(fam.program_config(self.cfg), opt,
                            TrainSettings(**t["settings"])),
            donate_argnums=(0, 1))
        self.data = make_batches(self.cfg, t, self.ctx.seed, t["distinct_batches"])
        L = self.cfg["num_hidden_layers"]
        start = jax.tree.map(jnp.copy, params)
        losses, grad_norms = [], None
        for i in range(t["check"]["steps"]):
            params, opt_state, metrics = self.step_fn(params, opt_state, self._batch(i))
            losses.append(float(metrics["loss"]))
            if i == 0:
                m = jax.tree.map(lambda s: s["m"], opt_state["mu"],
                                 is_leaf=lambda s: isinstance(s, dict) and "m" in s)
                grad_norms = {k: v / (1 - opt.betas[0])
                              for k, v in named(_norms(m), L).items()}
        change = named(_change_norms(params, start), L)
        del start
        self.readings = {"losses": losses, "grad_norms": grad_norms,
                         "change_norms": change}
        self.params, self.opt_state = params, opt_state
        self.step = t["check"]["steps"]

    def _batch(self, i: int) -> dict:
        tok = self.data[i % len(self.data)]
        return {"tokens": tok, "labels": tok}

    # ------------------------------------------------------------- window
    def run(self, seconds: float) -> dict:
        """Steps dispatched ``ahead_steps`` ahead of the loss read on the
        host, so a host that stalls leaves the chip fed. When the time is
        up nothing more is sent; the window ends once every step sent has
        finished and its loss has been read."""
        t = self.traffic
        pending: collections.deque = collections.deque()
        n = bad = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, self._batch(self.step))
            pending.append(metrics["loss"])
            n += 1
            self.step += 1
            if len(pending) > t["ahead_steps"]:
                with jax.profiler.TraceAnnotation("bench.read_loss"):
                    bad += not np.isfinite(float(pending.popleft()))
        with jax.profiler.TraceAnnotation("bench.read_loss"):
            while pending:
                bad += not np.isfinite(float(pending.popleft()))
        return {"seconds": time.perf_counter() - t0, "steps": n, "nonfinite": bad,
                "tokens": n * t["batch"] * t["seq"], "seq": t["seq"],
                "batch": t["batch"]}

    def end_to_end(self, w: dict) -> dict:
        return {"tokens_per_s": w["tokens"] / w["seconds"]}

    def describe(self, w: dict) -> str:
        return (f"{w['steps']} steps of {w['batch']}x{w['seq']} tokens, "
                f"{w['nonfinite']} non-finite losses; first steps' losses "
                f"{self.readings['losses']}")

    def attempted(self, w: dict) -> tuple[int, int]:
        return w["steps"], w["nonfinite"]

    def kernel_names(self) -> dict:
        """Instruction names of the Pallas kernels (``tpu_custom_call``) in
        the compiled step, by kernel: the trace names its events so."""
        text = self.step_fn.lower(self.params, self.opt_state,
                                  self._batch(0)).compile().as_text()
        names = [line.split("=", 1)[0].strip().lstrip("%")
                 for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        return {"flash_attention": [n for n in names if "flash" in n]}

    def release(self):
        self.params = self.opt_state = self.step_fn = self.data = None

    # -------------------------------------------------------- correctness
    def _reference(self, quant=None, rows=None) -> dict:
        """The reference's readings over the first steps' batches (all
        rows, or the first ``rows``), kept once computed."""
        key = (quant, rows)
        if key not in self._refs:
            t = self.traffic
            batches = [np.asarray(b)[:rows] for b in
                       make_batches(self.cfg, t, self.ctx.seed, t["check"]["steps"])]
            self._refs[key] = self.ctx.reference.train(
                self.cfg, self.ctx.seed, batches, dict(t["optimizer"]), quant)
        return self._refs[key]

    def check(self, quant=None) -> dict:
        """The three numbers; with ``quant`` the reference at that
        precision stands in for the program (the control)."""
        self._refs = getattr(self, "_refs", {})
        ref = self._reference()
        prog = self.readings if quant is None else self._reference(quant)
        self.ctx.log(f"losses {prog['losses']}, reference {ref['losses']}")
        return compare(prog, ref, self.ctx.log)

    def faults(self) -> dict:
        """The numbers when half of each batch is left out (the mean taken
        over the rest), the reference standing in for the program."""
        self._refs = getattr(self, "_refs", {})
        half = self._reference(rows=self.traffic["batch"] // 2)
        return {"half_batch": compare(half, self._reference())}
