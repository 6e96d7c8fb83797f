"""Mixture-of-Experts: top-k router, shared experts, dense-dispatch einsum
formulation (shardable over the expert axis by pjit), plus the shard_map
expert-parallel path that uses the paper's doubly-parallel all-to-all.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.ops import default_impl
from repro.kernels.moe.expert_ffn import expert_ffn
from repro.models import layers as L

# the routed experts' weights, (E, ...) in a layer's params
EXPERT_WEIGHTS = ("w_in", "w_gate", "w_out")


def moe_init(key, cfg, dtype):
    """The router over all ``num_experts``; the weights of the experts this
    chip holds (``MoEConfig.held_experts``); the shared experts as one MLP."""
    m = cfg.moe
    d = cfg.d_model
    E = m.n_held
    ks = jax.random.split(key, 4)
    s_in = d ** -0.5
    s_out = m.d_ff_expert ** -0.5
    p = {
        "router": L.truncated_normal(ks[0], (d, m.num_experts), dtype, s_in),
        "w_in": L.truncated_normal(ks[1], (E, d, m.d_ff_expert), dtype, s_in),
        "w_gate": L.truncated_normal(ks[2], (E, d, m.d_ff_expert), dtype, s_in),
        "w_out": L.truncated_normal(ks[3], (E, m.d_ff_expert, d), dtype, s_out),
    }
    if m.shared_experts:
        p["shared"] = L.mlp_init(
            jax.random.fold_in(key, 7), d, m.d_ff_expert * m.shared_experts, dtype
        )
    return p


def moe_specs(cfg, rules):
    E = cfg.moe.n_held
    p = {
        "router": P(None, None),
        "w_in": rules.expert((E, 0, 0), ff_dim=2, n_experts=E),
        "w_gate": rules.expert((E, 0, 0), ff_dim=2, n_experts=E),
        "w_out": rules.expert((E, 0, 0), ff_dim=1, n_experts=E),
    }
    if cfg.moe.shared_experts:
        p["shared"] = L.mlp_specs(rules)
    return p


def overlap_fused_atol(ref) -> float:
    """Largest |difference| allowed between the ``dragonfly_overlap_fused``
    output and a sequential path's output ``ref``: 8 machine epsilons of
    ``ref``'s dtype times its largest magnitude. The wave-batched expert
    einsums may sum in another order than the sequential paths' single
    contraction, which moves the last bits of an element, not more (f32 on
    the CPU, smoke MoE: 3.3e-9 against 9.1e-9)."""
    ref = np.asarray(ref)
    return 8 * float(jnp.finfo(ref.dtype).eps) * float(
        np.abs(ref.astype(np.float32)).max())


def router_topk(logits: jax.Array, k: int, norm_probs: bool):
    """logits: (..., E) -> (weights (..., k), indices (..., k))."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    if norm_probs:  # mixtral/deepseek renormalize the selected gates
        w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)
    return w, idx


def moe_apply(params, x, cfg):
    """Dense-dispatch formulation: one-hot combine weights -> einsum over
    experts. The expert dim shards over the 'model' axis (EP); XLA turns
    the dispatch/combine contractions into all-to-alls on that axis —
    the §3 collective in fused form. O(T·E) routing memory, exact top-k
    (no capacity drops) — the reference semantics for the EP fast path.
    With ``held_experts`` set, routing is over all experts and only the
    held experts' part of the result is computed (see
    ``moe_apply_sparse``).
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = xt @ params["router"]
    w, idx = router_topk(logits, m.top_k, m.norm_topk_probs)
    # combine[t, e] = sum_k w[t,k] * [idx[t,k] == e]
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)  # (T, k, E)
    combine = (onehot * w[..., None]).sum(axis=1)  # (T, E)
    if m.held_experts is not None:
        first, n = m.held_experts
        combine = combine[:, first:first + n]
    # dispatch: every expert sees all tokens weighted by membership.
    # grouped einsum keeps peak memory at (E, T, ff) tiles XLA can shard.
    h_in = jnp.einsum("td,edf->etf", xt, params["w_in"])
    h_gate = jnp.einsum("td,edf->etf", xt, params["w_gate"])
    h = jax.nn.silu(h_gate) * h_in
    y_e = jnp.einsum("etf,efd->etd", h, params["w_out"])  # (E, T, d)
    y = jnp.einsum("etd,te->td", y_e.astype(jnp.float32), combine)
    y = y.astype(x.dtype)
    if "shared" in params:
        with jax.named_scope("moe.shared_ffn"):
            y = y + L.mlp(params["shared"], xt)
    aux = load_balance_loss(logits, idx, m.num_experts, m.top_k)
    return y.reshape(B, S, d), aux


def moe_apply_sparse(params, x, cfg, capacity_factor: float | None = None, live=None):
    """Capacity-bounded sparse dispatch (production path): tokens gather
    into per-expert buffers of size C = cf·T·k/E; overflow drops (standard
    Switch/Mixtral-style). This is the formulation whose dispatch IS an
    all-to-all over the EP axis — bound to dragonfly_all_to_all in the
    shard_map training variant (train/step_dragonfly.py).

    Expert share: with ``held_experts`` = (first, n) the layer is one chip
    of an expert-parallel deployment. It routes each token over all
    ``num_experts`` with the full-width router, builds and runs buffers
    for its n experts only, at the capacity the global count gives, and
    returns their part of the result; assignments to experts held
    elsewhere contribute nothing here. Shared experts, which every chip
    computes alike, are added once. Summed over the shares of a
    deployment (shared experts counted once) the parts give the uncut
    layer.

    ``live`` (B,) bool, optional: tokens of a batch row where it is False
    take no capacity row and get nothing from the routed experts (the
    serving engine's free slots); None routes every token."""
    y, aux, _ = _sparse(params, x, cfg, capacity_factor, live)
    return y, aux


def moe_decode(params, x, cfg, live=None, layer=None):
    """The MoE layer of a decode step: ``moe_apply_auto``'s output for
    ``x`` (B, 1, d), with the tokens of rows where ``live`` is False
    masked out of routing as in ``moe_apply_sparse``, and the number of
    held experts that ran (int32; a shard_map path runs them all).

    With ``layer`` given, ``params``' expert weights are every layer's,
    stacked ``(L, E, ...)`` (the decode loop keeps them out of its
    per-layer slices), and layer ``layer``'s are used. Then on a TPU,
    where the buffers are decode-sized (capacity at most 128 rows, bound
    by the weights' bytes), the experts' FFN is the Pallas kernel
    ``expert_ffn``, which reads only the weights of the experts some kept
    row reaches, in place in the stacks; elsewhere the three einsums run
    over every held expert."""
    path = _sharded_path(cfg, x)
    if path is not None:
        if layer is not None:
            params = dict(params, **{k: params[k][layer] for k in EXPERT_WEIGHTS})
        return path(params, x, cfg)[0], jnp.int32(cfg.moe.n_held)
    y, _, n_run = _sparse(params, x, cfg, None, live, layer)
    return y, n_run


def _sparse(params, x, cfg, capacity_factor, live, layer=None):
    """``moe_apply_sparse``, and ``moe_decode``'s with stacked weights
    (``layer``): (y, aux, the number of held experts some kept row
    reaches)."""
    from repro.dist import sharding as SH

    m = cfg.moe
    if capacity_factor is None:
        capacity_factor = m.capacity_factor
    B, S, d = x.shape
    T = B * S
    E = m.num_experts
    C = max(1, int(capacity_factor * T * m.top_k / E))
    C = -(-C // 16) * 16  # round up so the capacity dim shards evenly
    # expert-buffer sharding: EP puts experts on the tensor axis and
    # capacity on the batch axes; the TP fallback (E ∤ axis) shards the
    # hidden dims instead. Constraints are no-ops outside a launcher.
    act = SH.active()
    ep = act is not None and act[0].expert_parallel(E)
    t_ax = act[0].tensor_axis if act else None
    b_ax = act[0].batch_axes if act else None
    xt = x.reshape(T, d)
    with jax.named_scope("moe.router"):
        logits = xt @ params["router"]
        w, idx = router_topk(logits, m.top_k, m.norm_topk_probs)  # (T,k)
    with jax.named_scope("moe.dispatch"):
        flat_e = idx.reshape(-1)  # (T*k,)
        # position of each (t, k) within its expert's buffer; a dead
        # token's assignments take none
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
        if live is not None:
            live_k = jnp.repeat(live, S * m.top_k)  # (T*k,)
            onehot = onehot * live_k[:, None]
        pos_in_e = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # (T*k, E)
        slot = pos_in_e.sum(-1)  # (T*k,)
        keep = slot < C
        if live is not None:
            keep &= live_k
        if m.held_experts is not None:  # this chip's experts, locally indexed
            first, n = m.held_experts
            keep &= (flat_e >= first) & (flat_e < first + n)
            flat_e = jnp.clip(flat_e - first, 0, n - 1)
        buf = jnp.zeros((m.n_held, C, d), xt.dtype)
        src_tok = jnp.repeat(jnp.arange(T), m.top_k)
        buf = buf.at[flat_e, jnp.clip(slot, 0, C - 1)].add(
            jnp.where(keep[:, None], xt[src_tok], 0)
        )
        # the held experts some kept row reaches
        reached = jnp.zeros((m.n_held,), jnp.int32).at[flat_e].add(keep.astype(jnp.int32)) > 0
        n_run = reached.sum(dtype=jnp.int32)
        if act:  # the §3 all-to-all boundary: tokens -> expert-major buffers
            buf = SH.constrain(buf, t_ax if ep else None, b_ax, None)
    with jax.named_scope("moe.expert_ffn"):
        w_gate, w_in, w_out = (params[k] for k in ("w_gate", "w_in", "w_out"))
        if layer is not None and C <= 128 and not act and default_impl() == "pallas":
            order = jnp.argsort(~reached, stable=True).astype(jnp.int32)  # reached first
            y_buf = expert_ffn(buf, w_gate, w_in, w_out, layer, order, n_run)
        else:
            if layer is not None:
                w_gate, w_in, w_out = w_gate[layer], w_in[layer], w_out[layer]
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
                "ecd,edf->ecf", buf, w_in
            )
            if act:
                h = SH.constrain(h, t_ax if ep else None, b_ax, None if ep else t_ax)
            y_buf = jnp.einsum("ecf,efd->ecd", h, w_out)  # (E, C, d)
    with jax.named_scope("moe.combine"):
        if act:  # combine all-to-all boundary
            y_buf = SH.constrain(y_buf, t_ax if ep else None, b_ax, None)
        y = jnp.zeros((T, d), jnp.float32)
        gathered = y_buf[flat_e, jnp.clip(slot, 0, C - 1)]
        y = y.at[src_tok].add(
            jnp.where(keep[:, None], gathered.astype(jnp.float32) * w.reshape(-1)[:, None], 0)
        )
        y = y.astype(x.dtype)
    if "shared" in params:
        with jax.named_scope("moe.shared_ffn"):
            y = y + L.mlp(params["shared"], xt)
    aux = load_balance_loss(logits, idx, E, m.top_k)
    return y.reshape(B, S, d), aux, n_run


def moe_apply_ep(params, x, cfg):
    """Expert-parallel MoE via shard_map: the dispatch/combine are EXPLICIT
    all-to-alls over the tensor axis — the §3 collective boundary. Used
    when the active rules report E % model_axis == 0 (deepseek: 256/16,
    jamba: 16/16); each model shard owns E/n_model experts outright and
    token buffers travel (E, C_loc, d) -> (E_loc, n_model·C_loc, d).

    The ``--collectives dragonfly`` variant swaps lax.all_to_all for the
    doubly-parallel ppermute schedule: the §3 Schedule IR emitted by
    core/alltoall.py, lowered to a CollectiveProgram by
    runtime/lowering.py, replayed by the jax_ppermute backend (via
    dist/collectives.py) — same payload, K·M²/s visible rounds.
    ``dragonfly_overlap`` replays the same program
    in start_step order so independent ppermutes overlap.
    ``dragonfly_overlap_fused`` goes further: dispatch, expert FFN and
    combine become ONE fused round trip (``dragonfly_all_to_all_compute``
    on the §3 pipelined schedule) where each wave's ppermutes issue while
    the previous wave's arrivals run through the experts. ``auto`` asks
    the price-driven autotuner (runtime/autotune.py) which of the four
    wins at this site's key — D3 view of the axis, per-destination buffer
    bytes, the expert FFN's ``moe_compute_us`` — and runs that; the
    decision happens here in Python, BEFORE shard_map, so the traced
    collective is whichever fixed path the tuner picked.
    """
    from repro.dist import sharding as SH
    from jax.sharding import PartitionSpec as PS

    rules, mesh = SH.active()
    m = cfg.moe
    E = m.num_experts
    t_ax = rules.tensor_axis
    b_ax = rules.batch_axes
    B, S, d = x.shape
    n_model = rules.model_axis_size
    E_loc = E // n_model
    # tokens shard over BOTH the batch axes and the tensor axis (sequence-
    # parallel dispatch): each chip routes its own T/(data·model) slice —
    # without this the model-axis replicas all dispatch identical buffers
    # and the expert compute is n_model-times redundant.
    b_axes = b_ax if isinstance(b_ax, tuple) else (b_ax,)
    tok_axes = (*b_axes, t_ax)

    moe_coll = rules.moe_collectives
    if moe_coll == "auto":
        # resolve the strategy OUTSIDE shard_map (tuner runs real closures;
        # it cannot measure inside a trace). Key: the dispatch/combine
        # all-to-all over the model axis' D3 view at this config's
        # per-destination buffer size, C_loc from the capacity bound.
        from repro.runtime import autotune

        t_loc = max(1, (B * S) // max(1, rules.data_axis_size * n_model))
        c_loc = max(8, int(m.capacity_factor * t_loc * m.top_k / E))
        c_loc = -(-c_loc // 8) * 8
        chunk = E_loc * c_loc * d * jnp.dtype(x.dtype).itemsize
        dec = autotune.get_autotuner().decide(
            "alltoall", autotune.layout_for(n_model), chunk,
            dtype=str(x.dtype), site="shard",
            compute_us=autotune.moe_compute_us(
                E_loc, c_loc, n_model, d, m.d_ff_expert))
        moe_coll = {"xla": "xla", "loop": "dragonfly",
                    "overlap": "dragonfly_overlap",
                    "overlap_fused": "dragonfly_overlap_fused"}[dec.strategy]

    def local_fn(xt, w_in, w_gate, w_out, router):
        T_loc = xt.shape[0]
        logits = xt @ router
        w, idx = router_topk(logits, m.top_k, m.norm_topk_probs)
        C_loc = max(8, int(m.capacity_factor * T_loc * m.top_k / E))
        C_loc = -(-C_loc // 8) * 8
        flat_e = idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        slot = ((jnp.cumsum(onehot, 0) - 1) * onehot).sum(-1)
        keep = slot < C_loc
        src = jnp.repeat(jnp.arange(T_loc), m.top_k)
        buf = jnp.zeros((E, C_loc, d), xt.dtype)
        buf = buf.at[flat_e, jnp.clip(slot, 0, C_loc - 1)].add(
            jnp.where(keep[:, None], xt[src], 0)
        )
        # ---- dispatch all-to-all (paper §3 boundary). "dragonfly" uses
        # the doubly-parallel round schedule (K·M²/s conflict-free rounds
        # of ppermutes on the D3 view of the axis) via the program
        # executor; "dragonfly_overlap" the same program replayed in
        # start_step order (cross-round ppermute overlap, hiding round
        # latency behind per-round compute); "dragonfly_overlap_fused"
        # the whole dispatch -> expert FFN -> combine round trip as ONE
        # Schedules 1-3 pipeline (expert compute for arrived capacity
        # chunks overlaps the next wave's ppermutes); "xla" the fused op.
        buf = buf.reshape(n_model, E_loc, C_loc, d)
        if moe_coll == "dragonfly_overlap_fused":
            from repro.dist.collectives import dragonfly_all_to_all_compute
            from repro.dist.mesh import dragonfly_layout
            from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend

            def expert_chunk(chunks):
                # one wave's arrivals, (V, E_loc, C_loc, d): the same
                # silu-gated FFN as the sequential path, batched over the
                # wave. The batched einsums may sum in another order than
                # the one big contraction: agreement is overlap_fused_atol,
                # not bit for bit
                h = jax.nn.silu(
                    jnp.einsum("...ecd,edf->...ecf", chunks, w_gate)
                ) * jnp.einsum("...ecd,edf->...ecf", chunks, w_in)
                return jnp.einsum("...ecf,efd->...ecd", h, w_out)

            back = dragonfly_all_to_all_compute(
                buf, t_ax, dragonfly_layout(n_model), expert_chunk,
                backend=JaxPpermuteBackend(overlap_fused=True),
            ).reshape(E, C_loc, d)
        else:
            if moe_coll.startswith("dragonfly"):
                from repro.dist.collectives import dragonfly_all_to_all
                from repro.dist.mesh import dragonfly_layout
                from repro.runtime.backends.jax_ppermute import JaxPpermuteBackend

                layout = dragonfly_layout(n_model)
                a2a_backend = JaxPpermuteBackend(
                    overlap=moe_coll == "dragonfly_overlap"
                )
                recv = dragonfly_all_to_all(buf, t_ax, layout,
                                            backend=a2a_backend)
            else:
                recv = jax.lax.all_to_all(buf, t_ax, split_axis=0,
                                          concat_axis=0)
            recv = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_model * C_loc, d)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, w_gate)) * jnp.einsum(
                "ecd,edf->ecf", recv, w_in
            )
            y = jnp.einsum("ecf,efd->ecd", h, w_out)
            # ---- combine all-to-all
            y = y.reshape(E_loc, n_model, C_loc, d).transpose(1, 0, 2, 3)
            if moe_coll.startswith("dragonfly"):
                back = dragonfly_all_to_all(y, t_ax, layout,
                                            backend=a2a_backend)
            else:
                back = jax.lax.all_to_all(y, t_ax, split_axis=0, concat_axis=0)
            back = back.reshape(E, C_loc, d)
        out = jnp.zeros((T_loc, d), xt.dtype)
        g = back[flat_e, jnp.clip(slot, 0, C_loc - 1)]
        out = out.at[src].add(
            jnp.where(keep[:, None], g * w.reshape(-1)[:, None].astype(g.dtype), 0)
        )
        aux = jax.lax.pmean(load_balance_loss(logits, idx, E, m.top_k), tok_axes)
        return out.astype(xt.dtype), aux

    xt = x.reshape(B * S, d)
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            PS(tok_axes, None),
            PS(t_ax, None, None),
            PS(t_ax, None, None),
            PS(t_ax, None, None),
            PS(None, None),
        ),
        out_specs=(PS(tok_axes, None), PS()),
        check_vma=False,
    )(xt, params["w_in"], params["w_gate"], params["w_out"], params["router"])
    y = out
    if "shared" in params:
        y = y + L.mlp_apply(params["shared"], xt)
    return y.reshape(B, S, d), aux


def moe_apply_tp(params, x, cfg):
    """TP-experts shard_map path (E ∤ tensor axis, e.g. mixtral's 8 on a
    16-wide axis): experts replicated, their FFN dims sharded over the
    tensor axis; dispatch is LOCAL (per data shard), the only collective
    is the per-layer psum of the d-dim partial outputs — no token
    all-gather (the pjit sparse path's scatter pulled the full global
    token set to every chip)."""
    from repro.dist import sharding as SH
    from jax.sharding import PartitionSpec as PS

    rules, mesh = SH.active()
    m = cfg.moe
    E = m.num_experts
    t_ax = rules.tensor_axis
    b_ax = rules.batch_axes
    B, S, d = x.shape
    b_axes = b_ax if isinstance(b_ax, tuple) else (b_ax,)

    def local_fn(xt, w_in, w_gate, w_out, router):
        T_loc = xt.shape[0]
        logits = xt @ router
        w, idx = router_topk(logits, m.top_k, m.norm_topk_probs)
        C_loc = max(8, int(m.capacity_factor * T_loc * m.top_k / E))
        C_loc = -(-C_loc // 8) * 8
        flat_e = idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        slot = ((jnp.cumsum(onehot, 0) - 1) * onehot).sum(-1)
        keep = slot < C_loc
        src = jnp.repeat(jnp.arange(T_loc), m.top_k)
        buf = jnp.zeros((E, C_loc, d), xt.dtype)
        buf = buf.at[flat_e, jnp.clip(slot, 0, C_loc - 1)].add(
            jnp.where(keep[:, None], xt[src], 0)
        )
        # w_in/w_gate local: (E, d, f/n); w_out local: (E, f/n, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
            "ecd,edf->ecf", buf, w_in
        )
        y_part = jnp.einsum("ecf,efd->ecd", h, w_out)  # partial over ff shards
        y_buf = jax.lax.psum(y_part.astype(xt.dtype), t_ax)
        out = jnp.zeros((T_loc, d), xt.dtype)
        g = y_buf[flat_e, jnp.clip(slot, 0, C_loc - 1)].astype(xt.dtype)
        out = out.at[src].add(
            jnp.where(keep[:, None], g * w.reshape(-1)[:, None].astype(g.dtype), 0)
        )
        aux = jax.lax.pmean(load_balance_loss(logits, idx, E, m.top_k), b_axes)
        return out.astype(xt.dtype), aux

    xt = x.reshape(B * S, d)
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            PS(b_ax, None),
            PS(None, None, t_ax),
            PS(None, None, t_ax),
            PS(None, t_ax, None),
            PS(None, None),
        ),
        out_specs=(PS(b_ax, None), PS()),
        check_vma=False,
    )(xt, params["w_in"], params["w_gate"], params["w_out"], params["router"])
    y = out
    if "shared" in params:
        y = y + L.mlp_apply(params["shared"], xt)
    return y.reshape(B, S, d), aux


def _sharded_path(cfg, x):
    """The shard_map path matching the expert layout when a launcher
    registered rules and the tokens divide over its axes; else None."""
    from repro.dist import sharding as SH

    act = SH.active()
    if act is None or cfg.moe.held_experts is not None:
        return None
    rules = act[0]
    T = x.shape[0] * x.shape[1]
    if rules.expert_parallel(cfg.moe.num_experts):
        if T % (rules.model_axis_size * rules.data_axis_size) == 0:
            return moe_apply_ep
    elif T % rules.data_axis_size == 0:
        return moe_apply_tp
    return None


def moe_apply_auto(params, x, cfg):
    """Pick the shard_map path matching the expert layout when a launcher
    registered rules; otherwise the sparse pjit path (single device, or
    one chip's expert share)."""
    return (_sharded_path(cfg, x) or moe_apply_sparse)(params, x, cfg)


def load_balance_loss(logits, idx, E, k):
    """Switch-style aux loss: E · Σ_e f_e · p_e."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    p_mean = probs.mean(axis=0)
    f = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=(0, 1)) / (idx.shape[0] * k)
    return E * jnp.sum(f * p_mean)
