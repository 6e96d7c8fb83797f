"""The ``deepseek`` family at test size: the tiny DeepSeek-V2-Lite cell
(``tiny_deepseek``; its correctness, end-to-end metrics and failing
float8 control are checked with every tiny cell in ``test_harness``)
reports the longgen cell's per-layer metrics, and the family's weights
are the reference's, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench import weights as W
from bench.families import deepseek as F
from bench.peaks import PEAKS
from bench.reference import deepseek as REF
from bench.tests import tiny
from bench.tests.tiny_deepseek import CELL, CONFIG

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def _cache_off():
    was = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def test_traced_run_reports_the_longgen_readings(root):
    res = R.run(R.load_cell(root, CELL), SEED, 0.5, True, PEAKS["TPU v5 lite"],
                trace_platform="cpu")
    assert res["correct"], res["compared"]
    # no MLA kernel runs off the TPU, so its roofline finds nothing to read
    assert set(res["metrics"]) == {"decode_roofline.longgen", "prefill_step_share.serve"}


def test_family_weights_are_the_references():
    params = F.make_params(CONFIG, SEED)
    dm = REF.Dims.of(CONFIG)
    key = W.seed_key(SEED)
    moe = params["stack"][0]["ffn"]
    for layer in (1, 2):
        lk = W.key_for(key, "layer", layer)
        for j, e in enumerate(dm.held):
            want = REF.ffn_weights(W.key_for(lk, "expert", e), (), dm.expert_ff, dm)
            for name in ("w_in", "w_gate", "w_out"):
                np.testing.assert_array_equal(
                    np.asarray(moe[name][layer - 1, j], np.float32), np.asarray(want[name]))
        np.testing.assert_array_equal(
            np.asarray(params["stack"][0]["mixer"]["wq"][layer - 1], np.float32),
            np.asarray(REF.attn_weights(lk, dm)["wq"]))
    dense = REF.ffn_weights(W.key_for(key, "layer", 0), (), dm.dense_ff, dm)
    np.testing.assert_array_equal(
        np.asarray(params["prefix"][0]["ffn"]["w_out"][0], np.float32),
        np.asarray(dense["w_out"]))
    assert params["embed"]["table"].dtype == jnp.bfloat16
