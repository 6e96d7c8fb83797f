"""The tiny cell of ``dsv2lite-serve-longgen``: DeepSeek-V2-Lite's
structure (direct query projection, YaRN, a dense layer, 2 of 8 routed
experts held, 2 shared) at test size, served open-loop. ``register()``
adds it to ``tiny``'s maps, so every root ``tiny.make_root`` builds runs
it and maps onto it the metrics that name the real cell; importing
``bench.tests`` registers it."""

from __future__ import annotations

import json

from bench.tests import tiny

CONFIG = json.loads((tiny.BENCH / "configs" / "deepseek-v2-lite-ep8.json").read_text())
CONFIG.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
              num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
              num_hidden_layers=3, vocab_size=256, n_routed_experts=2,
              first_held_expert=2, published={"n_routed_experts": 8},
              num_experts_per_tok=2, initializer_range=0.125)
CELL = "tiny-dsv2lite-serve"
# off_best_share on the CPU, program / float8 control: 0.0 / 0.159-0.288 over
# seeds 1, 2, 3, 5 and 2**31 + 77
LIMITS = {"off_best_share": 0.08}


def register():
    tiny.CONFIGS["tiny-dsv2lite"] = CONFIG
    tiny.CELLS[CELL] = ("tiny-dsv2lite", "tiny-serve", 1, LIMITS, "dsv2lite-serve-longgen")
