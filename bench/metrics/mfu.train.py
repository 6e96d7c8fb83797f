"""Forward and backward FLOP per token (recomputation not counted) times
the measured window's tokens per second, over the chips' bf16 peak, in %."""

from bench import flops as FL


def read(m):
    w = m.window
    if not w["steps"]:
        return None
    per_token = FL.train_flops_per_token(m.config, w["seq"])
    return 100.0 * per_token * w["tokens"] / w["seconds"] / (
        m.peak.flops_bf16 * m.cell.chips)
