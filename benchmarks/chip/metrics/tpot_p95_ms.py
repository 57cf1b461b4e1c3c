"""95th percentile of time per output token over the requests that
completed inside the window (RequestResult.tpot_s), in ms."""
import numpy as np


def read(w):
    v = [r.tpot_s for r in w.completed]
    return float(np.percentile(v, 95)) * 1e3 if v else None
