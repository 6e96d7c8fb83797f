"""Compile-only checks of the Pallas kernels for a TPU v5e that is
described, not attached: the TPU compiler refuses here what it would
refuse on the chip (unaligned slices, too much VMEM, unsupported ops).

Nothing runs. The topology is described inside a module-scoped fixture
(never at import time), so every pytest-xdist worker collects the same
tests and only the worker given this file loads the TPU library; the
tests skip where no topology can be described. The compilation cache is
off around these compiles: entries written for an absent chip cannot be
read back.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_block_matmul_compiles(one_chip):
    from repro.kernels.block_matmul.block_matmul import block_matmul

    a = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((4096, 14336), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(block_matmul, a, b)


# (BH, S, D, window): Mixtral 8x7B and TinyLlama 1.1B heads, batch 1
FLASH_SHAPES = {"mixtral": (32, 2048, 128, 4096), "tinyllama": (32, 2048, 64, None)}


@pytest.mark.parametrize("arch", sorted(FLASH_SHAPES))
def test_flash_attention_compiles(one_chip, arch):
    from repro.kernels.flash_attention.flash_attention import flash_attention

    BH, S, D, window = FLASH_SHAPES[arch]
    q = jax.ShapeDtypeStruct((BH, S, D), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window),
        q, q, q)
    assert "tpu_custom_call" in text


def test_flash_attention_grad_compiles(one_chip):
    """Training takes the loss and its gradients through the kernel:
    forward in Pallas, backward through the custom VJP's XLA recompute.
    (``jax.grad`` alone would drop the unused forward, kernel and all.)"""
    from repro.kernels.flash_attention.flash_attention import flash_attention

    BH, S, D, _ = FLASH_SHAPES["tinyllama"]
    q = jax.ShapeDtypeStruct((BH, S, D), jnp.bfloat16, sharding=one_chip)
    grad = jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))
    assert "tpu_custom_call" in _compiled_text(grad, q, q, q)


@pytest.mark.parametrize("kernel", ["reduce_rounds", "combine_group"])
def test_table_kernels_compile(one_chip, kernel):
    """Both pallas_fused table kernels at n=8 routers, 1 MiB per row."""
    from repro.runtime.backends import pallas_fused as pf

    n, F, R, K = 8, 262144, 3, 2
    x = jax.ShapeDtypeStruct((n, F), jnp.float32, sharding=one_chip)
    if kernel == "reduce_rounds":
        tab = jax.ShapeDtypeStruct((R, K, n), jnp.int32, sharding=one_chip)
        text = _compiled_text(pf.reduce_rounds, tab, tab, x)
    else:
        tab = jax.ShapeDtypeStruct((K, n), jnp.int32, sharding=one_chip)
        text = _compiled_text(pf.combine_group, tab, tab, x, x)
    assert "tpu_custom_call" in text


def test_rdma_ring_compiles(topo, monkeypatch):
    """The §4 remote-DMA ring (``allreduce_shard``) inside shard_map on a
    described 4-chip mesh."""
    from repro.dist.collectives import allreduce_program
    from repro.dist.mesh import dragonfly_layout
    from repro.runtime.backends import pallas_fused as pf

    n = 4
    layout = dragonfly_layout(n)
    prog = allreduce_program(layout)
    mesh = Mesh(np.array(topo.devices[:n]), ("df",))
    be = pf.PallasFusedBackend(interpret=False)
    ring = jax.shard_map(lambda s: be.allreduce_shard(s, "df", prog),
                         mesh=mesh, in_specs=P("df"), out_specs=P("df"),
                         check_vma=False)
    x = jax.ShapeDtypeStruct((n, 65536), jnp.float32,
                             sharding=NamedSharding(mesh, P("df")))
    # off the chip the backend refuses the compiled ring; steer it here
    monkeypatch.setattr(pf, "_on_tpu", lambda: True)
    assert "tpu_custom_call" in _compiled_text(ring, x)


def test_decode_attention_compiles(one_chip):
    """The decode kernel over the serve cell's stacked f32 cache (Mixtral
    8x7B heads, 2 layers, 16 slots of 2048 positions): the kernel reads
    the cache through a bitcast, with no copy and no scratch in HBM."""
    from repro.kernels.flash_attention.decode import decode_attention

    L, B, S, kv, hd, H = 2, 16, 2048, 8, 128, 32
    cache = jax.ShapeDtypeStruct((L, B, S, kv, hd), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v, layer, pos: decode_attention(
        q, k, v, layer, pos, interpret=False)).lower(
        jax.ShapeDtypeStruct((B, H, hd), jnp.float32, sharding=one_chip), cache, cache,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _steer_to_expert_kernel(monkeypatch):
    """The MoE path a TPU takes (the backend here is the CPU)."""
    import functools

    from repro.kernels.moe import expert_ffn
    from repro.models import moe

    monkeypatch.setattr(moe, "default_impl", lambda: "pallas")
    monkeypatch.setattr(moe, "expert_ffn",
                        functools.partial(expert_ffn.expert_ffn, interpret=False))


def _engine_step_text(cfg, B, S, one_chip) -> str:
    """The engine's decode step (cache donated, free slots masked) for
    ``B`` slots of ``S`` f32 positions, compiled as ``Engine`` jits it."""
    from repro.models import model as M
    from repro.serve.engine import greedy_step

    on = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    params = on(jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg)))
    cache = on(jax.eval_shape(lambda: M.init_cache(cfg, B, S, dtype=jnp.float32)))
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    step = jax.jit(lambda p, c, b, pos: greedy_step(p, c, b, pos, cfg), donate_argnums=(1,))
    return step.lower(params, cache, {"token": vec, "live": live}, vec).compile().as_text()


def _expert_weights_made(text, cfg) -> list:
    """Arrays of an expert-weight shape (a layer's or the stack's, either
    orientation) that an instruction outside fusions makes, but the
    parameters and the loop's pass-through of them."""
    from _hlo import materialized

    m = cfg.moe
    size = lambda dims: tuple(sorted(d for d in dims if d != 1))
    weights = {size(s) for s in [(cfg.d_model, m.d_ff_expert),
                                 (m.n_held, cfg.d_model, m.d_ff_expert),
                                 (cfg.n_groups, m.n_held, cfg.d_model, m.d_ff_expert)]}
    allowed = {"parameter", "get-tuple-element", "bitcast"}
    return [x for x in materialized(text) if size(x[1]) in weights and x[2] not in allowed]


def test_serve_decode_step_updates_cache_in_place(one_chip, monkeypatch):
    """The engine's decode step (cache donated) at the serve cell's shapes:
    Mixtral 8x7B widths, 2 layers, 16 slots of 2048 f32 positions. Every
    cache leaf shares its buffer with an output, and outside fusions no
    instruction makes an array of a layer's or of the stack's K/V dims, in
    any axis order, but the in-place row update; the attention is the
    kernel. The experts' FFN is the expert kernel, which reads the
    stacked expert weights in place: no array of a layer's or the stack's
    expert-weight shape is made."""
    import dataclasses
    import functools

    from _hlo import aliased_parameter_dims, materialized
    from repro.configs import get_config
    from repro.kernels.flash_attention import decode
    from repro.models import attention
    from repro.models import model as M

    # the attention path a TPU takes (the backend here is the CPU)
    monkeypatch.setattr(attention, "default_impl", lambda: "pallas")
    monkeypatch.setattr(attention, "decode_attention",
                        functools.partial(decode.decode_attention, interpret=False))
    _steer_to_expert_kernel(monkeypatch)
    cfg = dataclasses.replace(get_config("mixtral-8x7b"), n_layers=2)
    B, S = 16, 2048
    cache = jax.eval_shape(lambda: M.init_cache(cfg, B, S, dtype=jnp.float32))
    text = _engine_step_text(cfg, B, S, one_chip)

    leaves = jax.tree.leaves(cache)
    assert aliased_parameter_dims(text) == sorted(leaf.shape for leaf in leaves)
    size = lambda dims: tuple(sorted(d for d in dims if d != 1))
    kv_sizes = {size(s) for leaf in jax.tree.leaves(cache["stack"])
                for s in (leaf.shape, leaf.shape[1:])}
    made = [m for m in materialized(text) if size(m[1]) in kv_sizes]
    allowed = {"parameter", "get-tuple-element", "bitcast", "fusion:scatter"}
    assert [m for m in made if m[2] not in allowed] == []
    assert any(kind == "fusion:scatter" for _, _, kind in made)
    assert 'custom_call_target="tpu_custom_call"' in text
    assert re.search(r"%expert_ffn\S* = .*tpu_custom_call", text)
    assert _expert_weights_made(text, cfg) == []


def test_mla_decode_attention_compiles(one_chip):
    """The MLA decode kernel over the longgen cell's stacked f32 latent
    cache (DeepSeek-V2-Lite: 27 layers, 32 slots of 2048 positions, rows
    of 512 + 64 padded to 640, 16 heads), with the live-slot mask: no copy
    and no scratch in HBM, and the kernel's instruction is named for the
    trace."""

    from repro.kernels.flash_attention.mla_decode import mla_decode_attention

    L, B, S, r, w, H = 27, 32, 2048, 512, 640, 16
    latent = jax.ShapeDtypeStruct((L, B, S, w), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda q, c, layer, pos, live: mla_decode_attention(
        q, c, layer, pos, live, r=r, scale=0.1, interpret=False)).lower(
        jax.ShapeDtypeStruct((B, H, w), jnp.float32, sharding=one_chip), latent,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(re.match(r"\s*(ROOT )?%mla_decode", c) for c in calls)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# (L, E, C, d, f): the serve cells' expert stacks, Mixtral 8x7B (2 layers,
# 8 experts) and DeepSeek-V2-Lite's share (26 MoE layers, 8 held)
EXPERT_SHAPES = {"mixtral": (2, 8, 16, 4096, 14336), "deepseek": (26, 8, 16, 2048, 1408)}


@pytest.mark.parametrize("arch", sorted(EXPERT_SHAPES))
def test_expert_ffn_compiles(one_chip, arch):
    """The expert-FFN kernel over a serve cell's stacked bf16 expert
    weights: it fits the VMEM it asks for, uses no scratch in HBM, and its
    instruction is named for the trace."""
    from repro.kernels.moe.expert_ffn import expert_ffn

    L, E, C, d, f = EXPERT_SHAPES[arch]
    sds = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(lambda x, g, i, o, layer, experts, n: expert_ffn(
        x, g, i, o, layer, experts, n, interpret=False)).lower(
        sds(E, C, d), sds(L, E, d, f), sds(L, E, d, f), sds(L, E, f, d),
        sds(dt=jnp.int32), sds(E, dt=jnp.int32), sds(dt=jnp.int32)).compile()
    assert re.search(r"%expert_ffn\S* = .*tpu_custom_call", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_mla_serve_decode_step_reads_the_latent_cache_in_place(one_chip, monkeypatch):
    """The engine's decode step (cache donated) at the longgen cell's
    shapes: DeepSeek-V2-Lite at its published widths, 27 layers (the dense
    one and 26 of 8 held experts), 32 slots of 2048 f32 latent rows. Every
    cache leaf shares its buffer with an output; outside fusions no
    instruction makes an array of a layer's or of the stack's latent dims
    but the in-place row update; no array anywhere has the (slots,
    positions, heads) dims of keys or values expanded from the latent
    rows; the attention is the MLA kernel; and the held experts' FFN is
    the expert kernel, with no array of an expert-weight shape made."""
    import dataclasses
    import functools

    from _hlo import aliased_parameter_dims, materialized
    from repro.configs import get_config
    from repro.kernels.flash_attention import mla_decode
    from repro.models import attention
    from repro.models import model as M

    monkeypatch.setattr(attention, "default_impl", lambda: "pallas")
    monkeypatch.setattr(attention, "mla_decode_attention",
                        functools.partial(mla_decode.mla_decode_attention, interpret=False))
    _steer_to_expert_kernel(monkeypatch)
    base = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe, held_experts=(0, 8)))
    B, S = 32, 2048
    cache = jax.eval_shape(lambda: M.init_cache(cfg, B, S, dtype=jnp.float32))
    text = _engine_step_text(cfg, B, S, one_chip)

    leaves = jax.tree.leaves(cache)
    assert aliased_parameter_dims(text) == sorted(leaf.shape for leaf in leaves)
    latent_dims = {d for leaf in leaves if leaf.ndim for d in (leaf.shape, leaf.shape[1:])}
    made = [m for m in materialized(text) if m[1] in latent_dims]
    allowed = {"parameter", "get-tuple-element", "bitcast", "fusion:scatter"}
    assert [m for m in made if m[2] not in allowed] == []
    H = cfg.n_heads
    expanded = [d for d in re.findall(r"\[([\d,]+)\]", text)
                if d.startswith(f"{B},{S},{H},") or d.startswith(f"{B},{H},{S},")]
    assert expanded == []
    assert re.search(r"%mla_decode\S* = .*tpu_custom_call", text)
    assert re.search(r"%expert_ffn\S* = .*tpu_custom_call", text)
    assert _expert_weights_made(text, cfg) == []
