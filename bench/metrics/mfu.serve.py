"""Model FLOP of the tokens the engine processed in the traced window
(prompt and decoded tokens of occupied slots; top-k experts only, no
capacity padding; attention over the positions in use), over the device
time of the decode steps' programs in the trace times the bf16 peak, in
%: the step's share of the chip's peak while it runs."""

from bench import flops as FL


def read(m):
    t = m.traced
    runs = m.trace.module_runs("")
    if not t.get("tokens_processed"):
        return None
    work = (t["tokens_processed"] * FL.decoder_token_flops(m.config)
            + FL.attn_score_flops(m.config, t["context"]))
    # as in decode_roofline.serve: only the decode step runs on the device
    device_s = sum(r.dur for r in runs) * 1e-9 if runs else m.trace.busy_s(0)
    return 100.0 * work / (device_s * m.peak.flops_bf16)
