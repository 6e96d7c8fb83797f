"""moe_collectives="auto" end-to-end — run as a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (set before jax import,
see test_autotune.py). The acceptance check for the autotuner wiring:
whatever strategy the tuner picks for the MoE EP dispatch/combine site,
``auto`` must be BIT-EXACT against that fixed path; the sequential paths
agree bit for bit, and ``dragonfly_overlap_fused`` within
``moe.overlap_fused_atol``. Exits 0 on success."""

import os
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# keep the tuner's cache out of the repo tree for this run
os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="autotune_"), "cache.json"
)

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_smoke_config
from repro.dist import sharding as SH
from repro.models import moe as MOE


def main():
    assert jax.device_count() >= 8, jax.device_count()
    cfg = get_smoke_config("mixtral-8x7b")
    E = cfg.moe.num_experts
    n_model, n_data = 4, 2
    assert E % n_model == 0, (E, n_model)
    mesh = Mesh(
        np.array(jax.devices()[: n_data * n_model]).reshape(n_data, n_model),
        ("data", "model"),
    )
    base = SH.ShardRules(model_axis_size=n_model, data_axis_size=n_data)
    params = MOE.moe_init(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(0)
    B, S = 2, 16  # T=32 tokens, 8 shards -> T_loc=4
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)) * 0.1, jnp.float32)

    outs = {}
    for mode in ("xla", "dragonfly", "dragonfly_overlap",
                 "dragonfly_overlap_fused", "auto"):
        rules = dataclasses.replace(base, moe_collectives=mode)
        SH.set_active(rules, mesh)
        y, aux = MOE.moe_apply_ep(params, x, cfg)
        outs[mode] = (np.asarray(y), float(aux))
        print(f"{mode}: aux={outs[mode][1]:.6f}")

    # the sequential paths move the same bits; the overlap_fused path
    # batches the expert FFN per wave and agrees within its tolerance
    for mode in ("dragonfly", "dragonfly_overlap"):
        np.testing.assert_array_equal(outs[mode][0], outs["xla"][0])
        assert outs[mode][1] == outs["xla"][1], (mode, outs)
    ref = outs["xla"][0]
    fused = outs["dragonfly_overlap_fused"][0]
    diff = float(np.abs(fused - ref).max())
    atol = MOE.overlap_fused_atol(ref)
    print(f"dragonfly_overlap_fused: max |diff| {diff:.3e} <= atol {atol:.3e}")
    assert diff <= atol, (diff, atol)
    assert abs(outs["dragonfly_overlap_fused"][1] - outs["xla"][1]) <= 1e-6

    # "auto" runs whichever path the tuner chose, bit for bit
    from repro.runtime.autotune import get_autotuner

    rows = get_autotuner().report()
    assert rows, "auto path never consulted the tuner"
    chosen = {"xla": "xla", "loop": "dragonfly", "overlap": "dragonfly_overlap",
              "overlap_fused": "dragonfly_overlap_fused"}[rows[0]["strategy"]]
    np.testing.assert_array_equal(outs["auto"][0], outs[chosen][0])
    assert outs["auto"][1] == outs[chosen][1], (chosen, outs)
    print("auto decision:", rows[0]["strategy"], f"({rows[0]['source']})")
    print("MOE AUTO CHECKS PASSED")


if __name__ == "__main__":
    main()
