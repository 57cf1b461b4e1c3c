"""Device time of the paged decode kernel (qattn_paged) over the
device busy time of the traced window."""


def read(w):
    if w.trace is None or not w.trace.busy_s:
        return None
    k = w.trace.time_of("qattn_paged")
    return k / w.trace.busy_s if k else None
