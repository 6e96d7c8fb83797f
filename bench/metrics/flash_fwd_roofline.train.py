"""The Pallas flash-attention forward kernel's calls in the traced window:
the sum over calls of the least time the chip could take (the larger of
causal FLOP over the bf16 peak and q, k, v, o bytes over the HBM
bandwidth) over the sum of the kernel's device time, in %."""

from bench import flops as FL


def read(m):
    calls = m.trace.ops_named(m.kernels.get("flash_attention", []))
    if not calls:
        return None
    c = m.config
    bh = m.traced["batch"] * c["num_attention_heads"]
    seq, hd = m.traced["seq"], c["head_dim"]
    bound = max(FL.flash_fwd_flops(bh, seq, hd) / m.peak.flops_bf16,
                FL.flash_fwd_bytes(bh, seq, hd) / m.peak.hbm_bytes_s)
    return 100.0 * len(calls) * bound / (sum(e.dur for e in calls) * 1e-9)
