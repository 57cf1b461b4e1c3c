"""Device busy time of the traced window over the decode steps in it,
ms per step; the busy time includes the prefills admitted meanwhile."""


def read(w):
    steps = w.stats["decode_steps"]
    if w.trace is None or not steps:
        return None
    return w.trace.busy_s * 1e3 / steps
