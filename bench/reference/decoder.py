"""Plain float32 reference of a decoder language model (Mixtral, OLMo):
the forward pass for serving, and loss, gradients and AdamW for training.

It imports nothing of the program. Its weights are the configuration's
bfloat16 grid values (``bench.weights``) regenerated from the seed, one
layer or one expert at a time, and widened to float32; every matrix
product runs at ``Precision.HIGHEST``. It follows the published
architectures: pre-norm blocks; RMSNorm (Mixtral) or LayerNorm without
parameters (OLMo), eps 1e-5; rotary embeddings that rotate the two halves
of each head; grouped-query causal attention; top-2 routing renormalised
over the chosen experts with SwiGLU experts, dropping nothing (Mixtral);
a SwiGLU MLP (OLMo); untied (Mixtral) or tied (OLMo) unembedding.

``quant="fp8"`` is the lower-precision control: both operands of every
matrix product rounded to float8 e4m3 with a scale per tensor, and their
gradients to float8 e5m2 with a scale per tensor.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int
    heads: int
    kv: int
    hd: int
    layers: int
    vocab: int
    experts: int
    top_k: int
    theta: float
    norm: str
    tied: bool
    std: float
    dtype: str

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(cfg["hidden_size"], cfg["intermediate_size"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"], cfg["num_hidden_layers"], cfg["vocab_size"],
                   cfg.get("num_local_experts") or 0,
                   cfg.get("num_experts_per_tok") or 0, float(cfg["rope_theta"]),
                   cfg["norm"], bool(cfg["tie_word_embeddings"]),
                   float(cfg["initializer_range"]), cfg["param_dtype"])


# ---------------------------------------------------------------- pieces
def _round(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def _fp8(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    # gradients as fp8 training keeps them: e5m2, scaled per tensor
    return (_round(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _q(x, quant):
    if quant is None:
        return x.astype(F32)
    if quant != "fp8":
        raise ValueError(quant)
    return _fp8(x.astype(F32))


def mm(a, b, quant=None):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=HIGHEST)


def norm(x, kind):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


def rope(x, theta):
    """x: (S, heads, hd); position p rotates pair (i, i + hd/2) by
    p * theta**(-2i/hd)."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, dm: Dims, quant):
    S = h.shape[0]
    q = rope(mm(h, w["wq"], quant).reshape(S, dm.heads, dm.hd), dm.theta)
    k = rope(mm(h, w["wk"], quant).reshape(S, dm.kv, dm.hd), dm.theta)
    v = mm(h, w["wv"], quant).reshape(S, dm.kv, dm.hd)
    g = dm.heads // dm.kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _q(q, quant), _q(k, quant),
                   precision=HIGHEST) / np.sqrt(dm.hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _q(p, quant), _q(v, quant), precision=HIGHEST)
    return mm(o.reshape(S, dm.heads * dm.hd), w["wo"], quant)


def swiglu(h, w, quant):
    return mm(jax.nn.silu(mm(h, w["w_gate"], quant)) * mm(h, w["w_in"], quant),
              w["w_out"], quant)


def route(h, router, dm: Dims, quant):
    """(S, E) weights: the top-k softmax probabilities renormalised, zero
    for the experts not chosen."""
    probs = jax.nn.softmax(mm(h, router, quant), axis=-1)
    top, idx = jax.lax.top_k(probs, dm.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, dm.experts, dtype=F32) * top[..., None], 1)


# ------------------------------------------------------------- weights
def _w(key, name, shape, dm: Dims):
    return W.tensor(key, (name,), shape, dm.std, jnp.dtype(dm.dtype)).astype(F32)


def attn_weights(key, dm: Dims):
    """``key`` is the layer's key (``("layer", i)`` folded in)."""
    d, H, KV, hd = dm.d, dm.heads, dm.kv, dm.hd
    return {"wq": _w(key, "wq", (d, H * hd), dm), "wk": _w(key, "wk", (d, KV * hd), dm),
            "wv": _w(key, "wv", (d, KV * hd), dm), "wo": _w(key, "wo", (H * hd, d), dm)}


def ffn_weights(key, dm: Dims):
    """``key`` is the layer's key, or an expert's (``("expert", e)``
    folded into the layer's)."""
    return {"w_in": _w(key, "w_in", (dm.d, dm.f), dm),
            "w_gate": _w(key, "w_gate", (dm.d, dm.f), dm),
            "w_out": _w(key, "w_out", (dm.f, dm.d), dm)}


# ------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("dm",))
def _embed(key, tokens, dm: Dims):
    return _w(key, "embed", (dm.vocab, dm.d), dm)[tokens]


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _attn_block(layer_key, x, dm: Dims, quant):
    return x + attention(norm(x, dm.norm), attn_weights(layer_key, dm), dm, quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _router(layer_key, x, dm: Dims, quant):
    h = norm(x, dm.norm)
    return route(h, _w(layer_key, "router", (dm.d, dm.experts), dm), dm, quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _expert_add(expert_key, x, y, gate, dm: Dims, quant):
    """y + gate[:, None] * expert(norm(x))."""
    return y + gate[:, None] * swiglu(norm(x, dm.norm), ffn_weights(expert_key, dm), quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _mlp_block(layer_key, x, dm: Dims, quant):
    return x + swiglu(norm(x, dm.norm), ffn_weights(layer_key, dm), quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _head(key, x, dm: Dims, quant):
    h = norm(x, dm.norm)
    if dm.tied:
        return mm(h, _w(key, "embed", (dm.vocab, dm.d), dm).T, quant)
    return mm(h, _w(key, "unembed", (dm.d, dm.vocab), dm), quant)


@functools.partial(jax.jit, static_argnames=("dm",))
def _router_margin(layer_key, x, dm: Dims):
    """(S,) how far the k-th largest router logit lies above the next."""
    z = mm(norm(x, dm.norm), _w(layer_key, "router", (dm.d, dm.experts), dm))
    top = jax.lax.top_k(z, dm.top_k + 1)[0]
    return top[:, dm.top_k - 1] - top[:, dm.top_k]


def logits(cfg: dict, seed: int, tokens, quant=None) -> jax.Array:
    """(S, vocab) float32 logits of one causal sequence ``tokens`` (S,),
    computed layer by layer and expert by expert."""
    return logits_and_margins(cfg, seed, tokens, quant)[0]


def logits_and_margins(cfg: dict, seed: int, tokens, quant=None):
    """``logits`` and, at each position, the smallest router margin over
    the layers (``_router_margin``; infinite for a dense model)."""
    dm = Dims.of(cfg)
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), dm)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    for i in range(dm.layers):
        lk = W.key_for(key, "layer", i)
        x = _attn_block(lk, x, dm, quant)
        if dm.experts:
            margin = jnp.minimum(margin, _router_margin(lk, x, dm))
            gates = _router(lk, x, dm, quant)
            y = jnp.zeros_like(x)
            for e in range(dm.experts):
                y = _expert_add(W.key_for(lk, "expert", e), x, y, gates[:, e], dm, quant)
            x = x + y
        else:
            x = _mlp_block(lk, x, dm, quant)
    return _head(key, x, dm, quant), margin


# ------------------------------------------------------------ training
def _train_dims_ok(dm: Dims):
    if dm.experts or dm.norm != "nonparametric" or not dm.tied:
        raise NotImplementedError(
            "the training reference covers dense decoders with parameter-free "
            "norms and tied embeddings (OLMo)")


@functools.partial(jax.jit, static_argnames=("dm",))
def _layer_params(layer_key, dm: Dims):
    return {**attn_weights(layer_key, dm), **ffn_weights(layer_key, dm)}


def init_train_params(cfg: dict, seed: int) -> dict:
    """Float32 copies of the bfloat16 weights, keyed ``embed`` and
    ``L<i>.<name>``."""
    dm = Dims.of(cfg)
    _train_dims_ok(dm)
    key = W.seed_key(seed)
    p = {"embed": jax.jit(functools.partial(_w, name="embed", shape=(dm.vocab, dm.d),
                                            dm=dm))(key)}
    for i in range(dm.layers):
        for name, a in _layer_params(W.key_for(key, "layer", i), dm).items():
            p[f"L{i}.{name}"] = a
    return p


def row_loss(params, tokens, dm: Dims, quant=None):
    """Mean next-token cross entropy of one row (S,)."""
    x = params["embed"][tokens]

    def block(x, w):
        x = x + attention(norm(x, dm.norm), w, dm, quant)
        return x + swiglu(norm(x, dm.norm), w, quant)

    for i in range(dm.layers):
        w = {k.split(".", 1)[1]: v for k, v in params.items() if k.startswith(f"L{i}.")}
        x = jax.checkpoint(block)(x, w)
    z = mm(norm(x, dm.norm), params["embed"].T, quant)[:-1]
    nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, tokens[1:, None], -1)[:, 0]
    return jnp.mean(nll)


@functools.partial(jax.jit, static_argnames=("dm", "quant"), donate_argnums=(2,))
def _row_grad_add(params, tokens, acc, dm: Dims, quant):
    loss, g = jax.value_and_grad(row_loss)(params, tokens, dm, quant)
    return loss, jax.tree.map(jnp.add, acc, g)


@jax.jit
def _adam_leaf(p, g, m, v, scale, lr, t, b1, b2, eps, wd, dtype_probe):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    upd = upd + wd * p  # every trained leaf here is a matrix
    new = (p - lr * upd).astype(dtype_probe.dtype).astype(F32)
    return new, m, v


def lr_at(opt: dict, t: int) -> float:
    warm = min(t / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((t - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * prog)))


def train(cfg: dict, seed: int, batches, opt: dict, quant=None) -> dict:
    """AdamW steps on ``batches`` (a list of (B, S) token arrays) with
    parameters stored in the configuration's dtype between steps, as the
    configuration states. Rows are processed one at a time and the moments
    live on the host, so the reference fits one chip beside nothing else.

    Returns the loss of each step, the norm of each leaf's first gradient
    as the optimizer takes it (clipped), and the norm of each leaf's
    change over all the steps."""
    dm = Dims.of(cfg)
    params = init_train_params(cfg, seed)
    start = {k: np.asarray(v) for k, v in params.items()}
    mom = {k: (np.zeros(v.shape, np.float32), np.zeros(v.shape, np.float32))
           for k, v in start.items()}
    b1, b2 = opt["betas"]
    probe = jnp.zeros((), jnp.dtype(dm.dtype))
    losses, first_grad = [], None
    for t, batch in enumerate(batches, start=1):
        acc = jax.tree.map(jnp.zeros_like, params)
        loss = 0.0
        rows = np.asarray(batch)
        for row in rows:
            l, acc = _row_grad_add(params, jnp.asarray(row), acc, dm, quant)
            loss += float(l)
        grads = jax.tree.map(lambda a: a / len(rows), acc)
        del acc
        gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
        scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        if first_grad is None:
            first_grad = {k: float(jnp.linalg.norm(g)) * scale for k, g in grads.items()}
        lr = lr_at(opt, t)
        for k in list(params):
            m, v = mom[k]
            p, m, v = _adam_leaf(params[k], grads[k], m, v, scale, lr, float(t),
                                 b1, b2, opt["eps"], opt["weight_decay"], probe)
            params[k] = p
            mom[k] = (np.asarray(m), np.asarray(v))
        del grads
        losses.append(loss / len(rows))
    change = {k: float(np.linalg.norm(np.asarray(params[k]) - start[k])) for k in params}
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change}
