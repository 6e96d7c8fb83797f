"""Language model wrapper: embedding -> (dense prefix) -> main stack ->
final norm -> logits, plus the DeepSeek-style MTP head, loss, and the
decode step. All entry points are pure functions of (params, batch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import transformer as T
from repro.configs.base import ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ------------------------------------------------------------------ init
def init_params(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    ks = jax.random.split(key, 8)
    p = {
        "embed": L.embed_init(ks[0], cfg.vocab, cfg.d_model, dt),
        "final_norm": L.make_norm(cfg.norm, cfg.d_model, dt)[0],
        "stack": T.stack_init(ks[1], cfg, dt),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = {"w": L.truncated_normal(ks[2], (cfg.d_model, cfg.vocab), dt, cfg.d_model ** -0.5)}
    if cfg.first_dense_layers:
        members = [
            T.member_init(jax.random.fold_in(ks[3], i), cfg, "attn", "mlp", dt)
            for i in range(cfg.first_dense_layers)
        ]
        p["prefix"] = (jax.tree.map(lambda *xs: jnp.stack(xs), *members),)
    if cfg.mtp_depth:
        p["mtp"] = {
            "proj": L.truncated_normal(ks[4], (2 * cfg.d_model, cfg.d_model), dt, (2 * cfg.d_model) ** -0.5),
            "norm": L.make_norm(cfg.norm, cfg.d_model, dt)[0],
            "block": T.member_init(ks[5], cfg, "attn", "mlp", dt),
        }
    return p


def param_specs(cfg: ModelConfig, rules):
    s = {
        "embed": {"table": rules.embed((cfg.vocab, cfg.d_model))},
        "final_norm": L.norm_specs(cfg.norm),
        "stack": T.stack_specs(cfg, rules),
    }
    if not cfg.tie_embeddings:
        s["unembed"] = {"w": rules.attn_in((cfg.d_model, cfg.vocab))}
    if cfg.first_dense_layers:
        member = T.member_specs(cfg, rules, "attn", "mlp")
        s["prefix"] = (
            jax.tree.map(lambda sp: P(None, *sp), member, is_leaf=lambda x: isinstance(x, P)),
        )
    if cfg.mtp_depth:
        s["mtp"] = {
            "proj": P(None, None),
            "norm": L.norm_specs(cfg.norm),
            "block": T.member_specs(cfg, rules, "attn", "mlp"),
        }
    return s


# --------------------------------------------------------------- forward
def _embed_inputs(params, batch, cfg):
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.compute_dtype))
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = L.embed_apply(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    mrope = batch.get("mrope_positions")
    return x, positions, mrope


def forward_train(params, batch, cfg: ModelConfig, use_kernel: bool = True, remat: bool = True,
                  unroll: bool = False):
    """-> (logits (B, S, vocab), aux_loss, hidden (B, S, d))."""
    x, positions, mrope = _embed_inputs(params, batch, cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.first_dense_layers:
        def pre_fn(x, member):
            x, a = T.member_train(member, x, cfg, "attn", "mlp", positions, mrope, use_kernel)
            return x, a
        pf = jax.checkpoint(pre_fn) if remat else pre_fn
        if unroll:
            for i in range(cfg.first_dense_layers):
                x, a = pf(x, jax.tree.map(lambda v: v[i], params["prefix"][0]))
                aux += a
        else:
            x, auxs = jax.lax.scan(pf, x, params["prefix"][0])
            aux += auxs.sum()
    x, aux2 = T.stack_train(params["stack"], x, cfg, positions, mrope, use_kernel, remat, unroll)
    aux += aux2
    h = T.norm_fn(cfg)(params["final_norm"], x)
    logits = _unembed(params, h, cfg)
    return logits, aux, h


@jax.named_scope("unembed.logits")
def _unembed(params, h, cfg):
    if cfg.tie_embeddings:
        return L.unembed_apply(params["embed"], h)
    return h @ params["unembed"]["w"]


def mtp_logits(params, h, batch, cfg, use_kernel=True):
    """DeepSeek MTP: predict token t+2 from [h_t ; emb(token_{t+1})]
    through one extra block sharing the embedding/unembedding."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    e = L.embed_apply(params["embed"], nxt).astype(h.dtype)
    z = jnp.concatenate([T.norm_fn(cfg)(params["mtp"]["norm"], h), e], axis=-1)
    z = z @ params["mtp"]["proj"]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    z, _ = T.member_train(params["mtp"]["block"], z, cfg, "attn", "mlp", positions, None, use_kernel)
    return _unembed(params, z, cfg)


@jax.named_scope("loss.xent")
def softmax_xent(logits, labels, valid=None):
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if valid is None:
        return nll.mean()
    return (nll * valid).sum() / jnp.clip(valid.sum(), 1)


def loss_fn(params, batch, cfg: ModelConfig, use_kernel: bool = True, remat: bool = True,
            unroll: bool = False):
    logits, aux, h = forward_train(params, batch, cfg, use_kernel, remat, unroll)
    labels = batch["labels"]
    loss = softmax_xent(logits[:, :-1], labels[:, 1:])
    metrics = {"ce": loss}
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
        metrics["moe_aux"] = aux
    if cfg.mtp_depth and "tokens" in batch:
        ml = mtp_logits(params, h, batch, cfg, use_kernel)
        mtp_loss = softmax_xent(ml[:, :-2], labels[:, 2:])
        loss = loss + 0.3 * mtp_loss
        metrics["mtp"] = mtp_loss
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------- decode
def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Every layer's decode state for ``batch`` slots of ``max_seq``
    positions, and ``experts_run``: the held experts the MoE layers have
    run since, summed over steps and layers (int32, wrapping; counted by
    ``decode_step``)."""
    dt = dtype or jnp.dtype(cfg.compute_dtype)
    cache = {"stack": T.stack_cache_init(cfg, batch, max_seq, dt),
             "experts_run": jnp.zeros((), jnp.int32)}
    if cfg.first_dense_layers:
        one = T.member_cache_init(cfg, "attn", batch, max_seq, dt)
        cache["prefix"] = (
            jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.first_dense_layers, *a.shape)), one),
        )
    return cache


def reset_states(cache, cfg: ModelConfig, slot):
    """``cache`` with slot ``slot``'s recurrent states (Mamba, mLSTM, sLSTM)
    zeroed, as ``init_cache`` makes them: a request seated in a used slot
    starts from them. Attention rows need no reset: a slot attends only to
    the positions it has written since its position went back to 0."""
    stack = tuple(
        c if mixer == "attn" else jax.tree.map(lambda a: a.at[:, slot].set(0), c)
        for c, (mixer, _) in zip(cache["stack"], cfg.layer_kinds())
    )
    return dict(cache, stack=stack)


def cache_specs(cfg: ModelConfig, rules, long_context: bool):
    s = {"stack": T.stack_cache_specs(cfg, rules, long_context), "experts_run": P()}
    if cfg.first_dense_layers:
        s["prefix"] = (T.stack_cache_specs(cfg, rules, long_context)[0],)
    return s


def decode_step(params, cache, batch, position, cfg: ModelConfig, unroll: bool = False):
    """One token for the whole batch at ``position`` (scalar or (B,)).

    batch: {'token': (B,)} or {'embed': (B, d)} (+ mrope positions), and
    optionally 'live' (B,) bool: the rows that hold a request, the only
    ones the MoE layers route and MLA attention reads (all when absent).
    Returns (logits (B, vocab), new_cache); the new cache's
    ``experts_run`` adds the held experts this step's MoE layers ran.
    """
    if cfg.embeds_input and "embed" in batch:
        x = batch["embed"][:, None].astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = L.embed_apply(params["embed"], batch["token"][:, None]).astype(
            jnp.dtype(cfg.compute_dtype)
        )
    mrope, live = batch.get("mrope_positions"), batch.get("live")
    new_cache = dict(cache)
    if cfg.first_dense_layers:
        def pre_fn(carry, inputs):
            x, c = carry
            member, i = inputs
            x, c, _ = T.member_decode(member, x, c, i, cfg, "attn", "mlp", position, mrope,
                                      live)
            return (x, c), None
        carry = (x, cache["prefix"][0])
        if unroll:
            for i in range(cfg.first_dense_layers):
                carry, _ = pre_fn(carry, (jax.tree.map(lambda a: a[i], params["prefix"][0]), i))
        else:
            carry, _ = jax.lax.scan(
                pre_fn, carry, (params["prefix"][0], jnp.arange(cfg.first_dense_layers))
            )
        x, pc = carry
        new_cache["prefix"] = (pc,)
    x, nsc, experts_run = T.stack_decode(params["stack"], x, cache["stack"], cfg, position,
                                         mrope, unroll, live)
    new_cache["stack"] = nsc
    new_cache["experts_run"] = cache["experts_run"] + experts_run
    h = T.norm_fn(cfg)(params["final_norm"], x)
    logits = _unembed(params, h, cfg)
    return logits[:, 0], new_cache
