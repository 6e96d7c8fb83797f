"""Compile-only checks of each cell's timed step at its real shapes, for a
TPU v5e that is described and not attached (one chip of a 2x2 host).
Nothing runs: the TPU compiler refuses what
the chip would refuse, and ``memory_analysis`` gives the bytes each step
needs on a chip, which must fit its 16 GB.

The topology is described inside a module fixture, with JAX's compilation
cache off around these compiles. The Pallas flash kernel is selected the
way a TPU selects it (``default_impl`` asks the backend, which here is the
CPU), by steering it in the test.
"""

import json
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = pathlib.Path(__file__).resolve().parents[2]
HBM = 16e9


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _traffic(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


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


@pytest.fixture
def pallas_flash(monkeypatch):
    from repro.kernels.flash_attention import ops
    from repro.models import attention

    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    monkeypatch.setattr(attention, "gqa_attention",
                        lambda *a, **k: ops.gqa_attention(*a, interpret=False, **k))


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
                        tree)


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, f"{k}_size_in_bytes")) for k in
           ("argument", "output", "temp", "alias", "generated_code")}
    out["total"] = out["argument"] + out["output"] + out["temp"] - out["alias"]
    print(json.dumps(out))
    return out


def test_serve_decode_step_compiles(topo):
    """mixtral-serve-decode: the engine's decode step at the mix's slots
    and positions, float32 cache, not donated (as ``Engine`` jits it)."""
    from bench.families import decoder
    from repro.models import model as M

    cfg, t = _cfg("mixtral-8x7b-2l"), _traffic("alpaca-poisson")
    mcfg = decoder.program_config(cfg)
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(lambda: M.init_params(jax.random.key(0), mcfg)))
    cache = _on(one, jax.eval_shape(lambda: M.init_cache(mcfg, t["slots"], t["max_seq"],
                                                         dtype=jnp.float32)))
    tok = {"token": jax.ShapeDtypeStruct((t["slots"],), jnp.int32, sharding=one)}
    pos = jax.ShapeDtypeStruct((t["slots"],), jnp.int32, sharding=one)
    step = jax.jit(lambda p, c, b, q: M.decode_step(p, c, b, q, mcfg))
    assert _bytes(step.lower(params, cache, tok, pos).compile())["total"] < HBM


def test_train_step_compiles(topo, pallas_flash):
    """olmo-train-2k: the donated train step, 4 x 2048 tokens, with the
    Pallas flash kernel in the forward."""
    from bench.families import decoder
    from bench.loops.train_steps import Loop
    from repro.models import model as M
    from repro.train import optimizer as O
    from repro.train.train_step import TrainSettings, make_train_step

    cfg, t = _cfg("olmo-1b-8l"), _traffic("pretrain-4x2048")
    mcfg = decoder.program_config(cfg)
    opt = Loop.__new__(Loop)
    opt.traffic = t
    opt_cfg = Loop._opt(opt)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), mcfg))
    state = _on(one, jax.eval_shape(lambda p: O.init_state(p, opt_cfg), params))
    params = _on(one, params)
    tok = jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32, sharding=one)
    step = jax.jit(make_train_step(mcfg, opt_cfg, TrainSettings(**t["settings"])),
                   donate_argnums=(0, 1))
    compiled = step.lower(params, state, {"tokens": tok, "labels": tok}).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    assert _bytes(compiled)["total"] < HBM
