"""Bytes the decode steps of the traced window need (every weight they
multiply by, the experts their tokens reach, the K/V positions in use,
all at the configured bf16) over the HBM bandwidth, divided by the device
time of those steps' programs in the trace, in %."""

from bench import flops as FL


def read(m):
    t = m.traced
    if not t.get("steps"):
        return None
    tokens = t["tokens_processed"] / t["steps"]
    need = (t["steps"] * FL.decode_step_bytes(m.config, tokens, 0)
            + FL.decode_step_bytes(m.config, tokens, t["context"])
            - FL.decode_step_bytes(m.config, tokens, 0))
    runs = m.trace.module_runs("")
    # only the decode step runs on the device in this window; where the
    # trace has no program line, its operations' busy time is that step's
    device_s = sum(r.dur for r in runs) * 1e-9 if runs else m.trace.busy_s(0)
    return 100.0 * need / m.peak.hbm_bytes_s / device_s
