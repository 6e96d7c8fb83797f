"""Optimizers: AdamW (full) and AdaFactor-style factored second moment
(for the 400-700B archs where full Adam state would not fit), with global
gradient-norm clipping and cosine LR schedule. Pure-functional: no optax
dependency (offline container).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    betas: tuple = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    factored: bool = False       # AdaFactor-style v factorization
    state_dtype: str = "float32"


def lr_at(cfg: OptConfig, step):
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0, 1.0,
    )
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (0.1 + 0.9 * cos)


def _factorable(shape) -> bool:
    return len(shape) >= 2 and shape[-1] >= 128 and shape[-2] >= 128


def init_state(params, cfg: OptConfig):
    dt = jnp.dtype(cfg.state_dtype)

    def leaf(p):
        if cfg.factored and _factorable(p.shape):
            return {
                "m": jnp.zeros(p.shape, dt),
                "vr": jnp.zeros(p.shape[:-1], dt),      # row stats
                "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], dt),  # col stats
            }
        return {"m": jnp.zeros(p.shape, dt), "v": jnp.zeros(p.shape, dt)}

    return {"mu": jax.tree.map(leaf, params), "step": jnp.zeros((), jnp.int32)}


def state_specs(param_specs, cfg: OptConfig, param_shapes=None, zero_fn=None):
    """Optimizer-state PartitionSpecs mirror the param specs (optionally
    ZeRO-extended by zero_fn: spec -> spec). ``param_shapes`` (a matching
    tree of ShapeDtypeStructs) decides per-leaf factorability — it must
    match init_state's structure exactly."""
    zf = zero_fn or (lambda s: s)

    def leaf(spec, shaped=None):
        full = zf(spec)
        if cfg.factored and shaped is not None and _factorable(shaped.shape):
            # factored leaves: row/col stats drop one axis each; vr keeps
            # the spec minus its last axis, vc minus its second-to-last.
            axes = list(spec) + [None] * (len(shaped.shape) - len(spec))
            vr = P(*axes[:-1])
            vc = P(*(axes[:-2] + axes[-1:]))
            return {"m": full, "vr": vr, "vc": vc}
        return {"m": full, "v": full}

    if param_shapes is None:
        mu = jax.tree.map(leaf, param_specs, is_leaf=lambda x: isinstance(x, P))
    else:
        mu = jax.tree.map(
            leaf, param_specs, param_shapes, is_leaf=lambda x: isinstance(x, P)
        )
    return {"mu": mu, "step": P()}


def global_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


@jax.named_scope("optimizer.adamw")
def apply_updates(params, grads, state, cfg: OptConfig):
    """One AdamW/AdaFactor step. Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    b1, b2 = cfg.betas
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    lr = lr_at(cfg, step)

    def leaf(p, g, s):
        g = g.astype(jnp.float32) * scale
        m = b1 * s["m"].astype(jnp.float32) + (1 - b1) * g
        mhat = m / (1 - b1 ** step.astype(jnp.float32))
        if "v" in s:
            v = b2 * s["v"].astype(jnp.float32) + (1 - b2) * g * g
            vhat = v / (1 - b2 ** step.astype(jnp.float32))
            upd = mhat / (jnp.sqrt(vhat) + cfg.eps)
            new_s = {"m": m.astype(s["m"].dtype), "v": v.astype(s["v"].dtype)}
        else:
            vr = b2 * s["vr"].astype(jnp.float32) + (1 - b2) * jnp.mean(g * g, axis=-1)
            vc = b2 * s["vc"].astype(jnp.float32) + (1 - b2) * jnp.mean(g * g, axis=-2)
            rc = vr[..., None] * vc[..., None, :] / jnp.maximum(
                jnp.mean(vr, axis=-1)[..., None, None], 1e-30
            )
            upd = mhat / (jnp.sqrt(rc / (1 - b2 ** step.astype(jnp.float32))) + cfg.eps)
            new_s = {
                "m": m.astype(s["m"].dtype),
                "vr": vr.astype(s["vr"].dtype),
                "vc": vc.astype(s["vc"].dtype),
            }
        if p.ndim >= 2:  # decoupled weight decay on matrices only
            upd = upd + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), new_s

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_s = treedef.flatten_up_to(state["mu"])
    new_p, new_s = zip(*[leaf(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)])
    return (
        jax.tree.unflatten(treedef, new_p),
        {"mu": jax.tree.unflatten(treedef, new_s), "step": step},
        {"grad_norm": gnorm, "lr": lr},
    )
