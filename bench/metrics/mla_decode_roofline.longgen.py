"""The MLA decode kernel's calls in the traced window (operations whose
instruction name starts with ``mla_decode``): the least time the chip
could take for the attention over the positions in use (the larger of its
FLOP over the bf16 peak and its latent rows, at bf16, over the HBM
bandwidth; ``bench/flops_mla.py``) over the kernel's device time, in %.
None where the program has no such kernel."""

from bench import flops_mla as FM
from bench import trace_reduce as TR

PREFIX = "mla_decode"


def read(m):
    names = {TR.op_name(e.name) for evs in m.trace.ops.values() for e in evs}
    calls = m.trace.ops_named(n for n in names if n.startswith(PREFIX))
    if not calls or not m.traced.get("context"):
        return None
    ctx = m.traced["context"]
    bound = max(FM.mla_decode_flops(m.config, ctx) / m.peak.flops_bf16,
                FM.mla_decode_bytes(m.config, ctx) / m.peak.hbm_bytes_s)
    return 100.0 * bound / (sum(e.dur for e in calls) * 1e-9 / len(m.trace.ops))
