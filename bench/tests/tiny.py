"""A checkout-shaped directory holding the benchmark's code and tiny
cells, for running the harness on the CPU. Each tiny cell is a real cell
cut to test size: the same families, loops, references and readers."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

DECODER = {"family": "decoder", "hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
           "vocab_size": 256, "max_position_embeddings": 64,
           "rope_theta": 10000.0, "hidden_act": "silu", "initializer_range": 0.125,
           "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
CONFIGS = {
    "tiny-moe": {**DECODER, "model_type": "mixtral", "num_key_value_heads": 2,
                 "num_local_experts": 4, "num_experts_per_tok": 2, "norm": "rmsnorm",
                 "sliding_window": None, "tie_word_embeddings": False,
                 "capacity_factor": 1.25},
    "tiny-dense": {**DECODER, "model_type": "olmo", "num_key_value_heads": 4,
                   "norm": "nonparametric", "tie_word_embeddings": True},
}
TRAFFIC = {
    "tiny-serve": {"loop": "serve_open", "rate_per_s": 40.0, "slots": 4, "max_seq": 64,
                   "prompt_tokens": {"median": 4, "sigma": 0.3, "min": 2, "max": 8},
                   "output_tokens": {"median": 12, "sigma": 0.3, "min": 6, "max": 24},
                   "block": 8, "warm_seconds": 0.5,
                   "trace_seconds": 0.5,
                   "check": {"requests": 3, "pad_to": 32, "gap_tolerance": 0.1}},
    "tiny-train": {"loop": "train_steps", "batch": 2, "seq": 32, "distinct_batches": 4,
                   "ahead_steps": 2,
                   "settings": {"use_kernel": True, "remat": True, "microbatches": 1},
                   "optimizer": {"lr": 0.01, "betas": [0.9, 0.95], "eps": 1e-05,
                                 "weight_decay": 0.1, "grad_clip": 1.0,
                                 "warmup_steps": 0, "total_steps": 1000},
                   "trace_seconds": 0.5, "check": {"steps": 3}},
}
# tiny cell -> (config, traffic, chips, limits, the real cell it stands for).
# Limits from CPU readings (program max / fp8 control min): off_best_share
# 0.022 / 0.167 over seeds 1, 2, 3, 5, 6 and 2**31 + 77. The tiny train
# cell's fp8 control is close to its program on some seeds (seed 2:
# loss_gap 0.0124 / 0.0058);
# at the test's seed the program reads 0.0097 / 0.0011 / 0.0019 and the
# control 0.069 / 0.041 / 0.013 (loss, grad, change).
CELLS = {
    "tiny-serve-decode": ("tiny-moe", "tiny-serve", 1, {"off_best_share": 0.08},
                          "mixtral-serve-decode"),
    "tiny-train": ("tiny-dense", "tiny-train", 1,
                   {"loss_gap": 0.03, "grad_gap": 0.01, "change_gap": 0.008},
                   "olmo-train-2k"),
}


def make_root(tmp: pathlib.Path, extra=None) -> pathlib.Path:
    """A directory with ``bench/`` copied and a BENCHMARK.json naming the
    tiny cells in place of the real ones, with the real metrics mapped onto
    them. ``extra(root, bench)`` may add files and entries before the file
    is written."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for name, cfg in CONFIGS.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, t in TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    rename = {c[4]: w for w, c in CELLS.items()}

    def moved(m):
        if "workloads" not in m:
            return m
        return dict(m, workloads=[rename[w] for w in m["workloads"]])

    bench = dict(
        real, end_to_end=[moved(m) for m in real["end_to_end"]],
        per_layer=[moved(m) for m in real["per_layer"]],
        configs=[{"name": c, "source": "test", "file": f"bench/configs/{c}.json",
                  "reduced": [], "why": "test"} for c in CONFIGS],
        workloads=[{"name": w, "config": c[0], "traffic": c[1], "chips": c[2],
                    "why": "test"} for w, c in CELLS.items()])
    for w, c in CELLS.items():
        (root / "bench" / "limits" / f"{w}.json").write_text(json.dumps(c[3]))
    if extra:
        extra(root, bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
