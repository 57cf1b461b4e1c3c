"""Process start to the start of the measured window, seconds."""


def read(w):
    return w.setup_s
