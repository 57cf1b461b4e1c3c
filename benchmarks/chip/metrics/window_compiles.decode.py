"""XLA programs compiled, or loaded from the persistent cache, while
the measured window ran (counted from jax.monitoring events); must be 0."""


def read(w):
    return w.compiles
