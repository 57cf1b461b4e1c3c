"""A test-only architecture: the dense decoder with its sizes under other
key names (GPT-2's `n_layer`, `n_embd`, ...) in its configuration file.
Each function renames the keys and hands over to `dense_decoder.py`, so a
run of a cell through this module reads no dense-decoder key unless some
code outside the architecture modules does."""
import arch
import reference

dense = arch.load("dense_decoder")

#: this file's key -> the dense decoder's
NAMES = {"n_layer": "num_hidden_layers", "n_embd": "hidden_size",
         "n_inner": "intermediate_size", "n_head": "num_attention_heads",
         "n_kv_head": "num_key_value_heads", "n_head_dim": "head_dim",
         "n_vocab": "vocab_size"}
BACK = {v: k for k, v in NAMES.items()}


def as_dense(c: dict) -> dict:
    return {NAMES.get(k, k): v for k, v in c.items()}


def model_config(c):
    return dense.model_config(as_dense(c))


init_leaf = dense.init_leaf
prefill_key = dense.prefill_key


def served_logits(params, tokens, n_prompt, *, model, **kw):
    return dense.served_logits(params, tokens, n_prompt,
                               model=reference.freeze(as_dense(dict(model))),
                               **kw)


def matmul_params(m):
    return dense.matmul_params(as_dense(m))


def attention_layers(m):
    return dense.attention_layers(as_dense(m))


def shrink(c):
    return {BACK.get(k, k): v for k, v in dense.shrink(as_dense(c)).items()}
