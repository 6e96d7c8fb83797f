"""Public GQA attention entry: handles (B, S, H, D) layouts, KV-head
grouping, and implementation dispatch:

    impl="pallas" — the Mosaic TPU kernel (interpret=True on CPU tests)
    impl="xla"    — chunked online-softmax scans (any backend; dry-run)
    impl="naive"  — materialized-score oracle (small shapes / unrolled
                    cost-analysis compiles, where loop bodies would be
                    counted once — see launch/dryrun.py)

All paths keep the 4-D (B, H, S, D) layout (no B·H flattening) so batch-
and head-shardings propagate cleanly through SPMD.
"""

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.xla_flash import flash_attention_xla
from repro.kernels.flash_attention.ref import attention_ref


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _naive_4d(q, k, v, causal, window, scale):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "impl", "interpret")
)
def gqa_attention_impl(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    impl: str = "xla",
    interpret: bool = True,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    q4 = q.transpose(0, 2, 1, 3)  # (B, Hq, Sq, D)
    k4 = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3)
    v4 = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3)
    if impl == "pallas":
        qf = q4.reshape(B * Hq, Sq, D)
        kf = k4.reshape(B * Hq, Sk, D)
        vf = v4.reshape(B * Hq, Sk, D)
        # outside ``flash_attention``'s jit: a TPU compile names the
        # kernel's custom call after the innermost scope
        with jax.named_scope("attn.flash_fwd"):
            of = flash_attention(qf, kf, vf, causal=causal, window=window,
                                 interpret=interpret)
        of = of.reshape(B, Hq, Sq, D)
    elif impl == "xla":
        of = flash_attention_xla(q4, k4, v4, causal=causal, window=window)
    else:
        of = _naive_4d(q4, k4, v4, causal, window, scale)
    return of.transpose(0, 2, 1, 3)


def gqa_attention(q, k, v, *, causal=True, window=None, use_kernel=True, interpret=None):
    """Boolean entry: use_kernel=True picks the best fused path for the
    backend; use_kernel=False uses the materializing oracle."""
    impl = default_impl() if use_kernel else "naive"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return gqa_attention_impl(
        q, k, v, causal=causal, window=window, impl=impl, interpret=interpret
    )


__all__ = [
    "gqa_attention",
    "gqa_attention_impl",
    "flash_attention",
    "flash_attention_xla",
    "attention_ref",
]
