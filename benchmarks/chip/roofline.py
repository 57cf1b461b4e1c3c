"""The work that the served models' kernels and steps need, counted from
the configuration's sizes alone (never from what an implementation
happens to read), so that no change to the program moves the yardstick.

`m` is a configuration file's dict; its architecture module
(`arch/<arch>.py`) says which weights a token multiplies through and
which layers attend through the page pool. `q` is its `serving.kv_quant`
block.
"""
from __future__ import annotations

import math

import arch


def matmul_params(m: dict) -> int:
    """Weights a token multiplies through in one forward pass, LM head
    included (the embedding lookup is a gather, not a product)."""
    return sum(arch.of(m).matmul_params(m))


def _attention_width(m: dict) -> int:
    """Multiply-adds of one query token's q.k (and of its p.v) per context
    position, all layers: q heads x head_dim summed over the layers."""
    return sum(nq * hd for nq, _, hd in arch.of(m).attention_layers(m))


def attention_flops(m: dict, context: int) -> int:
    """Exact attention of one query token over `context` positions, all
    layers: q.k and p.v, two FLOPs per multiply-add."""
    return 4 * _attention_width(m) * context


def kv_bits(q: dict, head_dim: int, num_layers: int) -> list:
    """Nominal bits per element, K + V, for each layer (TurboAngle eq. 3):
    log2(bins)/2 angle bits, norm bits / 2, and 64/head_dim for each
    vector's f32 min and max."""
    e = min(int(q["n_early"]), num_layers)
    out = []
    for layer in range(num_layers):
        nk = q["boost_k"] if layer < e else q["base_k"]
        nv = q["boost_v"] if layer < e else q["base_v"]
        out.append(math.log2(nk) / 2 + q["k_norm_bits"] / 2 + 64 / head_dim
                   + math.log2(nv) / 2 + q["v_norm_bits"] / 2
                   + 64 / head_dim)
    return out


def kv_bytes_per_position(m: dict, q: dict) -> float:
    """Packed K and V codes of one cached position over all layers that
    keep K/V in the pool."""
    layers = arch.of(m).attention_layers(m)
    return sum(nkv * hd * kv_bits(q, hd, len(layers))[i] / 8
               for i, (_, nkv, hd) in enumerate(layers))


def qattn_work(m: dict, q: dict, tokens: int, context_sum: int
               ) -> tuple[float, float]:
    """(FLOPs, bytes) the paged decode attention needs for `tokens` decoded
    tokens whose contexts add up to `context_sum` positions: the packed
    codes of every live position once per layer, plus each token's f32
    query and output per layer."""
    width = _attention_width(m)
    flops = 4 * width * context_sum
    nbytes = (context_sum * kv_bytes_per_position(m, q)
              + tokens * 2 * width * 4)
    return float(flops), float(nbytes)


def model_flops(m: dict, decode_contexts: int, decode_tokens: int,
                prefills: list) -> float:
    """FLOPs the model needs for the tokens a window processed.

    decode tokens: every matmul weight (LM head included) and exact
    attention over its context. prefills: (prompt_len, cached_prefix)
    pairs; each computed prompt position costs the layer matmuls and
    causal attention over its position + 1, and one LM head row per
    request produces its first token."""
    p_layers, p_head = arch.of(m).matmul_params(m)
    p_all = p_layers + p_head
    attn = 4.0 * _attention_width(m)
    total = 2.0 * p_all * decode_tokens
    total += attn * decode_contexts
    for plen, cached in prefills:
        n = plen - cached
        ctx = (cached + 1 + plen) * n / 2  # sum of positions + 1
        total += 2.0 * (p_all - p_head) * n + 2.0 * p_head
        total += attn * ctx
    return total
