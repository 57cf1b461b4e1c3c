"""Whole-step model FLOP utilization of the traced window, in %: the
FLOPs the model needs for every token the window processed (decode and
prefill, counted by roofline.model_flops) over the traced span and the
chip bf16 peak."""
import roofline


def read(w):
    if w.trace is None or not w.trace_s:
        return None
    f = roofline.model_flops(w.model, w.decode_contexts, w.decode_tokens,
                             w.prefills)
    return 100.0 * f / (w.trace_s * w.peaks["bf16_flops"] * w.trace.chips)
