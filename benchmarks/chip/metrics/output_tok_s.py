"""All output tokens emitted inside the window over its seconds."""


def read(w):
    return w.out_tokens / w.seconds
