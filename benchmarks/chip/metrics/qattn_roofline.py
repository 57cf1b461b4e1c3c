"""Share of its roofline the paged decode kernel (qattn_paged) reached
in the traced window, in %: the least time the work needs (the larger of
its FLOPs over peak FLOP/s and its bytes over peak HBM bandwidth, counted
by roofline.py from the live contexts of the tokens decoded) over the
kernel device time in the trace."""
import roofline


def read(w):
    if w.trace is None or not w.decode_tokens:
        return None
    t = w.trace.time_of("qattn_paged")
    if not t:
        return None
    flops, nbytes = roofline.qattn_work(w.model, w.quant, w.decode_tokens,
                                        w.decode_contexts)
    least = max(flops / w.peaks["bf16_flops"],
                nbytes / w.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
