"""The dense decoder (Qwen3, DeepSeek LLM): pre-norm RMSNorm blocks, GQA
attention with rotate-half RoPE and optional per-head q/k RMSNorm, a
SwiGLU MLP, a final RMSNorm and a tied or untied LM head. Configuration
files name its sizes in Hugging Face keys.

`served_logits` is the plain reference, written from the published model
descriptions; like `reference.py` it imports nothing of the program.
`model_config` and `prefill_key` are the two functions that speak to the
program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import (F32, fake_quant, hadamard, layer_bins, make_dot,
                       rms_norm, rope, rotation_signs)


def model_config(c: dict):
    from repro.configs.base import ModelConfig

    s = c["serving"]
    return ModelConfig(
        name=c["name"], family="decoder",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["head_dim"], qk_norm=c["qk_norm"],
        qkv_bias=c["attention_bias"], rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        act=c["hidden_act"], param_dtype=s["param_dtype"],
        compute_dtype=s["compute_dtype"])


def init_leaf(name: str, shape: tuple, z):
    """Norm gains 1 + N(0, 0.1), the embedding N(0, 0.02), every matrix
    N(0, 1/fan_in) with fan_in the second-to-last axis."""
    if "norm" in name:
        return 1.0 + 0.1 * z
    if name == "embed":
        return 0.02 * z
    return z / np.sqrt(shape[-2])


def prefill_key(engine, plen: int, skip: int) -> tuple:
    """(suffix width, cached prefix) of the prefill an admission of a
    `plen`-token prompt with `skip` cached tokens dispatches."""
    return (engine._bucket_width(plen - skip), skip)


def matmul_params(m: dict) -> tuple[int, int]:
    d, f = m["hidden_size"], m["intermediate_size"]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    return m["num_hidden_layers"] * (attn + 3 * d * f), d * m["vocab_size"]


def attention_layers(m: dict) -> list:
    return [(m["num_attention_heads"], m["num_key_value_heads"],
             m["head_dim"])] * m["num_hidden_layers"]


#: rehearsal widths; every cell keeps its own structure (GQA or MHA, q/k
#: norms, tied head)
TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            head_dim=16, vocab_size=512)


def shrink(c: dict) -> dict:
    kv_per_q = c["num_key_value_heads"] / c["num_attention_heads"]
    c.update(TINY, num_attention_heads=4,
             num_key_value_heads=max(1, int(4 * kv_per_q)))
    return c


@functools.partial(jax.jit, static_argnames=("model", "quant", "chunk",
                                             "requant", "rows", "mode"))
def served_logits(params, tokens, n_prompt, *, model, quant, chunk: int,
                  requant: bool, rows: int, mode: str):
    """Logits at positions n_prompt-1 ... n_prompt-1+rows-1 of `tokens`
    (padded to a multiple of `chunk`): the positions whose argmax is the
    1st, 2nd, ... generated token. `model`/`quant` are hashable tuples of
    the configuration's items (see `reference.freeze`)."""
    m, q = dict(model), dict(quant)
    dot = make_dot(mode)
    t_pad = tokens.shape[0]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["head_dim"]
    eps = float(m["rms_norm_eps"])
    h = jnp.asarray(hadamard(hd))
    signs = rotation_signs(int(q["rotation_seed"]), hd)
    nk, nv = layer_bins(q, len(attention_layers(m)))
    pos = jnp.arange(t_pad)
    g = nq // nkv
    scale = 1.0 / np.sqrt(hd)

    def attend(qh, k, v, kq, vq):
        """qh (T, nq, hd); k, v raw and kq, vq quantized (T, nkv, hd)."""
        qg = qh.reshape(t_pad, nkv, g, hd)

        def block(b):
            s0 = b * chunk
            t = s0 + jnp.arange(chunk)
            qb = jax.lax.dynamic_slice_in_dim(qg, s0, chunk, 0)
            causal = pos[None, :] <= t[:, None]  # (C, T)
            prompt_row = (t < n_prompt)[:, None]
            sq = dot("qngh,knh->ngqk", qb, kq) * scale
            if requant:  # raw only inside the row's own chunk
                loc = jnp.arange(chunk)
                raw = prompt_row & (loc[None, :] <= loc[:, None])
                kr = jax.lax.dynamic_slice_in_dim(k, s0, chunk, 0)
                vr = jax.lax.dynamic_slice_in_dim(v, s0, chunk, 0)
                sr = dot("qngh,knh->ngqk", qb, kr) * scale
                slab = jax.lax.dynamic_slice_in_dim(sq, s0, chunk, 3)
                s = jax.lax.dynamic_update_slice_in_dim(
                    sq, jnp.where(raw, sr, slab), s0, 3)
                p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
                pslab = jax.lax.dynamic_slice_in_dim(p, s0, chunk, 3)
                pq = jax.lax.dynamic_update_slice_in_dim(
                    p, jnp.where(raw, 0.0, pslab), s0, 3)
                return (dot("ngqk,knh->qngh", pq, vq)
                        + dot("ngqk,knh->qngh", jnp.where(raw, pslab, 0.0),
                              vr))
            raw = prompt_row & causal  # prompt rows read raw everywhere
            s = jnp.where(raw, dot("qngh,knh->ngqk", qb, k) * scale, sq)
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return (dot("ngqk,knh->qngh", jnp.where(raw, 0.0, p), vq)
                    + dot("ngqk,knh->qngh", jnp.where(raw, p, 0.0), v))

        out = jax.lax.map(block, jnp.arange(t_pad // chunk))
        return out.reshape(t_pad, nq * hd)

    def layer(x, xs):
        lp, bk, bv = xs
        a = lp["attn"]
        hx = rms_norm(x, lp["norm1"], eps)
        qh = dot("td,dk->tk", hx, a["wq"]).reshape(t_pad, nq, hd)
        k = dot("td,dk->tk", hx, a["wk"]).reshape(t_pad, nkv, hd)
        v = dot("td,dk->tk", hx, a["wv"]).reshape(t_pad, nkv, hd)
        if m["qk_norm"]:
            qh = rms_norm(qh, a["q_norm"], eps)
            k = rms_norm(k, a["k_norm"], eps)
        qh = rope(qh, pos, m["rope_theta"])
        k = rope(k, pos, m["rope_theta"])
        kq = fake_quant(k, bk, q["k_norm_bits"], q["k_norm_log"], signs, h,
                        dot)
        vq = fake_quant(v, bv, q["v_norm_bits"], q["v_norm_log"], signs, h,
                        dot)
        x = x + dot("tk,kd->td", attend(qh, k, v, kq, vq), a["wo"])
        hx = rms_norm(x, lp["norm2"], eps)
        mp = lp["mlp"]
        up = dot("td,df->tf", hx, mp["w_up"])
        gate = jax.nn.silu(dot("td,df->tf", hx, mp["w_gate"]))
        return x + dot("tf,fd->td", gate * up, mp["w_down"]), None

    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(layer, x, (params["layers"], jnp.asarray(nk),
                                   jnp.asarray(nv)))
    x = jax.lax.dynamic_slice_in_dim(x, n_prompt - 1, rows, 0)
    x = rms_norm(x, params["final_norm"], eps)
    head = (params["embed"].T if m["tie_word_embeddings"]
            else params["lm_head"])
    return dot("td,dv->tv", x, head)
