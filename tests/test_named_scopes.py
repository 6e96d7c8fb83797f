"""Every named scope of the model step reaches the compiled program's
``op_name`` metadata: the decode step (MoE, cached attention, the layer
scan; MLA with shared experts) and the train step (Pallas flash forward
with its XLA backward, loss, AdamW), compiled on the CPU at smoke size."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.kernels.flash_attention import ops as OPS
from repro.models import model as M
from repro.train import optimizer as O
from repro.train.train_step import TrainSettings, make_train_step


def _scopes(text: str) -> str:
    """Every ``op_name`` of a compiled program's text, one per line."""
    return "\n".join(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def decode_ops():
    cfg = get_smoke_config("mixtral-8x7b")
    params = M.init_params(jax.random.key(0), cfg)
    cache = M.init_cache(cfg, 4, 64, dtype=jnp.float32)
    step = jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, cfg))
    return _scopes(step.lower(params, cache, {"token": jnp.zeros(4, jnp.int32)},
                              jnp.zeros(4, jnp.int32)).compile().as_text())


@pytest.fixture(scope="module")
def train_ops():
    cfg = get_smoke_config("olmo-1b")
    params = M.init_params(jax.random.key(0), cfg)
    opt = O.OptConfig()
    step = jax.jit(make_train_step(cfg, opt, TrainSettings()))
    tok = jnp.zeros((2, 32), jnp.int32)
    mp = pytest.MonkeyPatch()
    mp.setattr(OPS, "default_impl", lambda: "pallas")  # interpreted off the TPU
    try:
        lowered = step.lower(params, O.init_state(params, opt),
                             {"tokens": tok, "labels": tok})
    finally:
        mp.undo()
    return _scopes(lowered.compile().as_text())


@pytest.mark.parametrize("scope", [
    "embed.lookup", "attn.qkv", "attn.kv_update", "attn.decode", "attn.out",
    "moe.router", "moe.dispatch", "moe.expert_ffn", "moe.combine",
    "decode.layers", "unembed.logits"])
def test_decode_step_scopes(decode_ops, scope):
    assert re.search(rf"(^|[/(]){re.escape(scope)}[/)]", decode_ops, re.M), scope


@pytest.mark.parametrize("scope", [
    "embed.lookup", "attn.qkv", "attn.flash_fwd", "attn.flash_bwd", "attn.out",
    "mlp.ffn", "unembed.logits", "loss.xent", "optimizer.adamw"])
def test_train_step_scopes(train_ops, scope):
    assert re.search(rf"(^|[/(]){re.escape(scope)}[/)]", train_ops, re.M), scope


@pytest.fixture(scope="module")
def mla_decode_ops():
    """DeepSeek-V2-Lite's decode step: absorbed MLA, shared experts."""
    cfg = get_smoke_config("deepseek-v2-lite")
    params = M.init_params(jax.random.key(0), cfg)
    cache = M.init_cache(cfg, 4, 64, dtype=jnp.float32)
    step = jax.jit(lambda p, c, b, pos: M.decode_step(p, c, b, pos, cfg))
    return _scopes(step.lower(params, cache, {"token": jnp.zeros(4, jnp.int32)},
                              jnp.zeros(4, jnp.int32)).compile().as_text())


@pytest.mark.parametrize("scope", [
    "attn.qkv", "attn.absorb", "attn.kv_update", "attn.decode", "attn.out",
    "moe.router", "moe.expert_ffn", "moe.shared_ffn", "mlp.ffn", "decode.layers"])
def test_mla_decode_step_scopes(mla_decode_ops, scope):
    assert re.search(rf"(^|[/(]){re.escape(scope)}[/)]", mla_decode_ops, re.M), scope
