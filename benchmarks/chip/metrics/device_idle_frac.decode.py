"""1 minus the union of device op intervals over the traced window."""


def read(w):
    if w.trace is None or not w.trace_s:
        return None
    return 1.0 - w.trace.busy_s / w.trace_s
