"""Share of the engine's steps in the measured window that carry a prompt
token (steps run inside ``Engine.admit``), in %."""


def read(m):
    w = m.window
    return 100.0 * w["prefill_steps"] / w["steps"] if w["steps"] else None
