"""Bytes the decode steps of the traced window need (every weight they
multiply by, the held experts their tokens reach under uniform routing,
the shared experts, the latent rows in use, all at bf16;
``bench/flops_mla.py``) over the HBM bandwidth, divided by the device time
of those steps' programs in the trace, in %."""

from bench import flops_mla as FM


def read(m):
    t = m.traced
    if not t.get("steps"):
        return None
    tokens = t["tokens_processed"] / t["steps"]
    need = (t["steps"] * FM.decode_step_bytes(m.config, tokens, 0)
            + FM.decode_step_bytes(m.config, tokens, t["context"])
            - FM.decode_step_bytes(m.config, tokens, 0))
    runs = m.trace.module_runs("")
    # only the decode step (and the engine's argmax of its logits, about
    # 0.1% of it) runs on the device in this window; where the trace has
    # no program line, its operations' busy time is that step's
    device_s = sum(r.dur for r in runs) * 1e-9 if runs else m.trace.busy_s(0)
    return 100.0 * need / m.peak.hbm_bytes_s / device_s
