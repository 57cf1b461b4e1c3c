"""Peak device memory in use over the memory JAX may use on the chip
(memory_stats peak_bytes_in_use / bytes_limit)."""


def read(w):
    return w.memory_peak / w.memory_limit if w.memory_limit else None
