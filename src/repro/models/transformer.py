"""Composable decoder: blocks = mixer (attn/mamba/mLSTM/sLSTM) + optional
FFN (dense MLP / MoE), pre-norm residual. Layers are stacked as repeating
GROUPS (the arch's block pattern period) and scanned with lax.scan +
jax.checkpoint — one trace per distinct member, n_layers/period iterations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import mamba as MB
from repro.models import xlstm as XL


# ------------------------------------------------------------ one member
def member_init(key, cfg, mixer: str, ffn: str, dtype):
    k1, k2 = jax.random.split(key)
    p = {"norm1": L.make_norm(cfg.norm, cfg.d_model, dtype)[0]}
    if mixer == "attn":
        p["mixer"] = A.mla_init(k1, cfg, dtype) if cfg.attention == "mla" else A.gqa_init(k1, cfg, dtype)
    elif mixer == "mamba":
        p["mixer"] = MB.mamba_init(k1, cfg, dtype)
    elif mixer == "mlstm":
        p["mixer"] = XL.mlstm_init(k1, cfg, dtype)
    elif mixer == "slstm":
        p["mixer"] = XL.slstm_init(k1, cfg, dtype)
    else:
        raise ValueError(mixer)
    if ffn != "none":
        p["norm2"] = L.make_norm(cfg.norm, cfg.d_model, dtype)[0]
        p["ffn"] = MOE.moe_init(k2, cfg, dtype) if ffn == "moe" else L.mlp_init(
            k2, cfg.d_model, cfg.d_ff, dtype, gated=cfg.mlp_gated
        )
    return p


def member_specs(cfg, rules, mixer: str, ffn: str):
    s = {"norm1": L.norm_specs(cfg.norm)}
    if mixer == "attn":
        s["mixer"] = A.mla_specs(cfg, rules) if cfg.attention == "mla" else A.gqa_specs(cfg, rules)
    elif mixer == "mamba":
        s["mixer"] = MB.mamba_specs(cfg, rules)
    elif mixer == "mlstm":
        s["mixer"] = XL.mlstm_specs(cfg, rules)
    elif mixer == "slstm":
        s["mixer"] = XL.slstm_specs(cfg, rules)
    if ffn != "none":
        s["norm2"] = L.norm_specs(cfg.norm)
        s["ffn"] = MOE.moe_specs(cfg, rules) if ffn == "moe" else L.mlp_specs(
            rules, gated=cfg.mlp_gated
        )
    return s


def norm_fn(cfg):
    norm = L.rmsnorm if cfg.norm == "rmsnorm" else L.layernorm
    return functools.partial(norm, eps=cfg.norm_eps)


def member_train(params, x, cfg, mixer, ffn, positions, mrope_positions, use_kernel):
    from repro.dist import sharding as SH

    act = SH.active()
    if act is not None and act[0].seq_parallel:
        # sequence parallelism: residual stream sharded over the tensor
        # axis between blocks — XLA turns the TP all-reduces into
        # reduce-scatter + all-gather pairs (half the collective bytes).
        x = SH.constrain(x, act[0].batch_axes, act[0].tensor_axis, None)
    norm = norm_fn(cfg)
    h = norm(params["norm1"], x)
    if mixer == "attn":
        if cfg.attention == "mla":
            mx = A.mla_train(params["mixer"], h, cfg, positions, use_kernel=use_kernel)
        else:
            mx = A.gqa_train(params["mixer"], h, cfg, positions, mrope_positions, use_kernel)
    elif mixer == "mamba":
        mx = MB.mamba_train(params["mixer"], h, cfg)
    elif mixer == "mlstm":
        mx = XL.mlstm_train(params["mixer"], h, cfg)
    else:
        mx = XL.slstm_train(params["mixer"], h, cfg)
    x = x + mx
    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        h2 = norm(params["norm2"], x)
        if ffn == "moe":
            y, aux = MOE.moe_apply_auto(params["ffn"], h2, cfg)
        else:
            y = L.mlp_apply(
                params["ffn"], h2, act=jax.nn.silu if cfg.mlp_gated else jax.nn.gelu
            )
        x = x + y
    return x, aux


def member_decode(params, x, cache, layer, cfg, mixer, ffn, position, mrope_positions,
                  live=None):
    """One decode member: pre-norm mixer + residual, then the FFN.
    ``cache`` is the member's, every layer's (leading axis): attention
    writes the new token's rows into layer ``layer`` and reads the layer
    where it lies; a recurrent mixer's new state replaces its layer's. An
    MoE member's expert weights are every layer's, stacked
    (``stack_decode`` keeps them out of the layer loop's slices), and its
    routing skips the tokens of rows where ``live`` is False
    (``moe.moe_decode``), as MLA attention skips their cached rows.
    Returns (x, cache, the held experts run: 0 but for an MoE member)."""
    norm = norm_fn(cfg)
    h = norm(params["norm1"], x)
    if mixer == "attn":
        if cfg.attention == "mla":
            mx, cache = A.mla_decode(params["mixer"], h, cache, layer, cfg, position, live)
        else:
            mx, cache = A.gqa_decode(
                params["mixer"], h, cache, layer, cfg, position, mrope_positions
            )
    else:
        state = jax.tree.map(lambda a: a[layer], cache)
        if mixer == "mamba":
            mx, state = MB.mamba_decode(params["mixer"], h, state, cfg)
        elif mixer == "mlstm":
            mx, state = XL.mlstm_decode(params["mixer"], h, state, cfg)
        else:
            mx, state = XL.slstm_decode(params["mixer"], h, state, cfg)
        cache = jax.tree.map(lambda c, s: c.at[layer].set(s.astype(c.dtype)), cache, state)
    x = x + mx
    n_run = jnp.int32(0)
    if ffn != "none":
        h2 = norm(params["norm2"], x)
        if ffn == "moe":
            y, n_run = MOE.moe_decode(params["ffn"], h2, cfg, live, layer)
        else:
            y = L.mlp_apply(
                params["ffn"], h2, act=jax.nn.silu if cfg.mlp_gated else jax.nn.gelu
            )
        x = x + y
    return x, cache, n_run


def member_cache_init(cfg, mixer, batch, max_seq, dtype):
    if mixer == "attn":
        if cfg.attention == "mla":
            return A.mla_cache_init(cfg, batch, max_seq, dtype)
        return A.gqa_cache_init(cfg, batch, max_seq, dtype)
    if mixer == "mamba":
        return MB.mamba_state_init(cfg, batch, dtype)
    if mixer == "mlstm":
        return XL.mlstm_state_init(cfg, batch, dtype)
    return XL.slstm_state_init(cfg, batch, dtype)


# -------------------------------------------------------------- the stack
def stack_init(key, cfg, dtype):
    """Returns a tuple (one entry per group member) of param trees stacked
    over the n_groups axis (leading dim)."""
    pattern = cfg.layer_kinds()
    period = len(pattern)
    n_groups = cfg.n_groups  # excludes the dense prefix (deepseek)
    members = []
    for mi, (mixer, ffn) in enumerate(pattern):
        per_group = [
            member_init(jax.random.fold_in(key, g * period + mi), cfg, mixer, ffn, dtype)
            for g in range(n_groups)
        ]
        members.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group))
    return tuple(members)


def stack_specs(cfg, rules):
    pattern = cfg.layer_kinds()

    def add_lead(spec):
        return P(None, *spec)

    return tuple(
        jax.tree.map(
            add_lead,
            member_specs(cfg, rules, mixer, ffn),
            is_leaf=lambda x: isinstance(x, P),
        )
        for mixer, ffn in pattern
    )


def stack_train(stack_params, x, cfg, positions, mrope_positions=None, use_kernel=True,
                remat: bool = True, unroll: bool = False):
    pattern = cfg.layer_kinds()

    def group_fn(x, group_params):
        aux_total = jnp.zeros((), jnp.float32)
        for mi, (mixer, ffn) in enumerate(pattern):
            x, aux = member_train(
                group_params[mi], x, cfg, mixer, ffn, positions, mrope_positions, use_kernel
            )
            aux_total += aux
        return x, aux_total

    if remat:
        group_fn = jax.checkpoint(group_fn)

    if unroll:
        # Python loop over groups — used by the dry-run's cost-analysis
        # compiles (XLA counts while-loop bodies once; unrolling makes
        # flops/bytes scale with depth so per-group deltas are exact).
        aux_total = jnp.zeros((), jnp.float32)
        for g in range(cfg.n_groups):
            group = jax.tree.map(lambda a: a[g], stack_params)
            x, aux = group_fn(x, group)
            aux_total += aux
        return x, aux_total

    x, auxs = jax.lax.scan(group_fn, x, stack_params)
    return x, auxs.sum()


def stack_decode(stack_params, x, caches, cfg, position, mrope_positions=None,
                 unroll: bool = False, live=None):
    """One token through the stack. The stacked ``caches`` ride in the layer
    loop's carry: each layer writes its update into its layer of them and
    reads that layer where it lies, so a donated cache is updated in place.
    The MoE members' expert weights stay whole, outside the loop's
    per-layer slices, for the expert kernel to read in place; ``live``
    (B,) masks free slots out of their routing. Returns (x, new_caches,
    the held experts run, summed over the MoE layers)."""
    pattern = cfg.layer_kinds()
    experts, sliced = [], []  # per member: expert weights whole; the rest, sliced
    for p, (_, ffn) in zip(stack_params, pattern):
        whole = MOE.EXPERT_WEIGHTS if ffn == "moe" else ()
        experts.append({k: p["ffn"][k] for k in whole})
        if whole:
            p = dict(p, ffn={k: a for k, a in p["ffn"].items() if k not in whole})
        sliced.append(p)
    sliced = tuple(sliced)

    def group_fn(carry, inputs):
        x, caches, n_run = carry
        group_params, g = inputs
        caches = list(caches)
        for mi, (mixer, ffn) in enumerate(pattern):
            p = group_params[mi]
            if experts[mi]:
                p = dict(p, ffn=dict(p["ffn"], **experts[mi]))
            x, caches[mi], n = member_decode(
                p, x, caches[mi], g, cfg, mixer, ffn, position, mrope_positions, live
            )
            n_run = n_run + n
        return (x, tuple(caches), n_run), None

    carry = (x, tuple(caches), jnp.int32(0))
    # the loop and its carry of the stacked caches
    with jax.named_scope("decode.layers"):
        if unroll:
            for g in range(cfg.n_groups):
                carry, _ = group_fn(carry, (jax.tree.map(lambda a: a[g], sliced), g))
        else:
            carry, _ = jax.lax.scan(group_fn, carry, (sliced, jnp.arange(cfg.n_groups)))
    return carry


def stack_cache_init(cfg, batch, max_seq, dtype):
    pattern = cfg.layer_kinds()
    n_groups = cfg.n_groups
    caches = []
    for mixer, _ in pattern:
        one = member_cache_init(cfg, mixer, batch, max_seq, dtype)
        caches.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (n_groups, *a.shape)), one))
    return tuple(caches)


def stack_cache_specs(cfg, rules, long_context: bool):
    """Decode caches are SEQUENCE-sharded over the tensor axis (kv-head
    counts like 8 don't divide a 16-wide model axis; seq always does).
    Recurrent states shard their inner/feature dims instead."""
    pattern = cfg.layer_kinds()
    b = rules.batch_axes
    t = rules.tensor_axis
    specs = []
    for mixer, _ in pattern:
        if mixer == "attn":
            if cfg.attention == "mla":
                specs.append({"latent": P(None, b, t, None)})  # (G, B, S, r + dr)
            else:
                specs.append({
                    "k": P(None, b, t, None, None),  # (G, B, S, kvh, hd)
                    "v": P(None, b, t, None, None),
                })
        elif mixer == "mamba":
            specs.append({
                "conv": P(None, b, None, t),  # (G, B, d_conv-1, di)
                "ssm": P(None, b, t, None),   # (G, B, di, N)
            })
        elif mixer == "mlstm":
            specs.append({
                "C": P(None, b, None, t, None),  # (G, B, H, dh, dh)
                "n": P(None, b, None, t),
                "m": P(None, b, None),
            })
        else:  # slstm: (G, B, d)
            specs.append({
                "c": P(None, b, t),
                "n": P(None, b, t),
                "h": P(None, b, t),
                "m": P(None, b, t),
            })
    return tuple(specs)
