"""Model assembly for all architecture families.

Families:
  decoder     — dense / MoE causal LM (llama3, deepseek, qwen*, granite,
                mixtral, paligemma backbone)
  encoder     — bidirectional encoder (hubert) with stub frame frontend
  hybrid_ssm  — zamba2: Mamba2 stacks with a *shared* attention block every
                `attn_every` layers (weight sharing; 9 KV caches for 54L)
  xlstm       — groups of (slstm_every-1) mLSTM blocks + 1 sLSTM block

All families scan over layer-stacked params; per-layer TurboAngle codebook
sizes ride along as scan xs so one traced body serves every layer. The
forward paths optionally apply a KV fake-quant hook (paper-style PPL evals)
and optionally emit quantized KV stacks (prefill).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.quantizer import KVQuantizer, QuantizedKV
from repro.core import rates
from repro.models import attention, common, mlp, moe, ssm, xlstm
from repro.models.common import Leaf


# ============================================================ init =========
def _init_block(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "norm1": common.scale_param(cfg.d_model, ("embed",), dtype),
        "norm2": common.scale_param(cfg.d_model, ("embed",), dtype),
        "attn": attention.init_attention(ks[0], cfg, dtype),
    }
    if cfg.moe_experts:
        p["moe"] = moe.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp.init_mlp(ks[1], cfg, dtype)
    return p


def init_params(key, cfg: ModelConfig):
    """Returns (params, specs) pytrees."""
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    tree: dict[str, Any] = {
        "embed": Leaf(
            common.normal_init(ks[0], (cfg.vocab_size, cfg.d_model), 0.02,
                               dtype),
            ("vocab", "embed"),
        ),
        "final_norm": common.scale_param(cfg.d_model, ("embed",), dtype),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = common.dense(
            ks[1], cfg.d_model, cfg.vocab_size, ("embed", "vocab"), dtype
        )

    if cfg.family in ("decoder", "encoder"):
        tree["layers"] = common.stack_layers(
            ks[2], cfg.num_layers, lambda k: _init_block(k, cfg, dtype)
        )
    elif cfg.family == "hybrid_ssm":
        n_groups = cfg.num_layers // cfg.attn_every
        tree["mamba"] = common.stack_layers(
            ks[2],
            n_groups,
            lambda k: common.stack_layers(
                k, cfg.attn_every,
                lambda k2: {
                    "norm": common.scale_param(cfg.d_model, ("embed",), dtype),
                    "ssm": ssm.init_mamba2(k2, cfg, dtype),
                },
            ),
        )
        tree["shared_attn"] = {
            "norm": common.scale_param(cfg.d_model, ("embed",), dtype),
            "attn": attention.init_attention(ks[3], cfg, dtype),
        }
    elif cfg.family == "xlstm":
        per = cfg.slstm_every
        n_groups = cfg.num_layers // per
        tree["groups"] = common.stack_layers(
            ks[2],
            n_groups,
            lambda k: {
                "mlstm": common.stack_layers(
                    k, per - 1, lambda k2: xlstm.init_mlstm(k2, cfg, dtype)
                ),
                "slstm": xlstm.init_slstm(
                    jax.random.fold_in(k, 999), cfg, dtype
                ),
            },
        )
    else:
        raise ValueError(cfg.family)

    if cfg.frontend == "patch_stub":
        tree["patch_proj"] = common.scale_param(cfg.d_model, ("embed",), dtype)
    if cfg.frontend == "frame_stub":
        tree["frame_proj"] = common.scale_param(cfg.d_model, ("embed",), dtype)
    return common.split(tree)


def abstract_params(cfg: ModelConfig):
    """(ShapeDtypeStruct params, logical specs) without any allocation."""
    box = {}

    def initp(k):
        p, s = init_params(k, cfg)
        box["specs"] = s  # static strings captured at trace time
        return p

    shapes = jax.eval_shape(initp, jax.random.PRNGKey(0))
    return shapes, box["specs"]


# ===================================================== embedding / head ====
def embed_inputs(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Assemble the input embedding sequence (B, S, D) from the batch."""
    parts = []
    if cfg.frontend == "patch_stub" and "patch_embeds" in batch:
        # precomputed patch embeddings (B, P, D) — SigLIP stub per assignment.
        # Absent at decode time (patches live in the prefilled cache).
        parts.append(batch["patch_embeds"].astype(jnp.dtype(cfg.compute_dtype))
                     * params["patch_proj"])
    if cfg.frontend == "frame_stub":
        return (batch["frames"].astype(jnp.dtype(cfg.compute_dtype))
                * params["frame_proj"])
    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    parts.append(tok.astype(jnp.dtype(cfg.compute_dtype)))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


@jax.named_scope("lm_head")
def lm_logits(params, cfg: ModelConfig, x: jax.Array, cstr=None) -> jax.Array:
    cstr = cstr if cstr is not None else (lambda t, kind="residual": t)
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return cstr(jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype)), "logits")


# ================================================ kv-quant scan plumbing ===
def _layer_bins(quantizer: Optional[KVQuantizer], n_attn_layers: int):
    if quantizer is None:
        return (jnp.full((n_attn_layers,), 0, jnp.int32),) * 2
    return quantizer.layer_bins()


def _fake_quant_hook(quantizer: Optional[KVQuantizer]):
    """Returns fn(k, v, nk, nv) -> (k, v) applying round-trip quantization."""
    if quantizer is None:
        return None

    def hook(k, v, nk, nv):
        kq = quantizer.fake_quant(k, nk, quantizer.config.k_norm)
        vq = quantizer.fake_quant(v, nv, quantizer.config.v_norm)
        return kq.astype(k.dtype), vq.astype(v.dtype)

    return hook


# ============================================================ forward ======
def ffn_residual(layer_params, x, cfg: ModelConfig, cstr=None,
                 shard=None) -> jax.Array:
    """Post-attention half of a decoder block: norm2 -> MoE/MLP -> residual.

    Shared by every decoder-layer body (full forward, prefill, decode step,
    paged decode, chunked prefill) so the block math lives in one place.
    `shard` makes an MoE FFN expert-parallel inside a shard_map (see
    `moe.moe_block`); dense MLPs ignore it (they stay replicated — the
    mesh's win there is the kv-head pool split, not the FFN).
    """
    cstr = cstr if cstr is not None else (lambda t, kind="residual": t)
    inner = common.rms_norm(x, layer_params["norm2"], cfg.norm_eps)
    if cfg.moe_experts:
        return common.radd(
            x, moe.moe_block(layer_params["moe"], inner, cfg, cstr,
                             shard=shard))
    return common.radd(x, mlp.mlp_block(layer_params["mlp"], inner, cfg, cstr))


def _decoder_layer(
    params, x, positions, cfg: ModelConfig, nk, nv, fake_hook, *, causal,
    cstr=None
):
    h, _ = attention.attention_block(
        params["attn"],
        common.rms_norm(x, params["norm1"], cfg.norm_eps),
        positions,
        cfg,
        causal=causal,
        kv_override=(
            None if fake_hook is None
            else (lambda k, v: fake_hook(k, v, nk, nv))
        ),
        cstr=cstr,
    )
    return ffn_residual(params, common.radd(x, h), cfg, cstr)


def forward(
    params,
    cfg: ModelConfig,
    batch: dict,
    *,
    quantizer: Optional[KVQuantizer] = None,
    fake_quant: bool = False,
    remat: bool = True,
    constraint: Optional[Callable[[jax.Array], jax.Array]] = None,
    param_constraint: Optional[Callable] = None,
) -> jax.Array:
    """Full-sequence forward -> logits. fake_quant round-trips each layer's
    K/V through the quantizer (the paper's PPL evaluation mode).

    param_constraint(layer_params) anchors the per-layer FSDP weight gather
    INSIDE the scan body (otherwise GSPMD hoists the all-gather of the whole
    layer stack out of the loop — 50 GiB/device at 405B)."""
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    causal = cfg.family != "encoder"
    cstr = constraint if constraint is not None else (lambda t, kind="residual": t)
    pcstr = param_constraint if param_constraint is not None else (lambda t: t)
    fake_hook = _fake_quant_hook(quantizer) if fake_quant else None

    if cfg.family in ("decoder", "encoder"):
        nk, nv = _layer_bins(quantizer, cfg.num_layers)

        def body(carry, xs):
            layer_params, lnk, lnv = xs
            layer_params = pcstr(layer_params)
            out = _decoder_layer(
                layer_params, carry, positions, cfg, lnk, lnv, fake_hook,
                causal=causal, cstr=cstr,
            )
            return cstr(out), None

        body_fn = jax.checkpoint(body) if remat else body
        x, _ = common.uscan(body_fn, cstr(x), (params["layers"], nk, nv))

    elif cfg.family == "hybrid_ssm":
        n_groups = cfg.num_layers // cfg.attn_every
        nk, nv = _layer_bins(quantizer, n_groups)
        shared = params["shared_attn"]

        def group_body(carry, xs):
            group_params, lnk, lnv = xs

            def mamba_body(c, lp):
                lp = pcstr(lp)
                out = common.radd(c, ssm.mamba2_block(
                    lp["ssm"],
                    common.rms_norm(c, lp["norm"], cfg.norm_eps), cfg
                ))
                return cstr(out), None

            mb = jax.checkpoint(mamba_body) if remat else mamba_body
            h, _ = common.uscan(mb, carry, group_params)
            a, _ = attention.attention_block(
                shared["attn"],
                common.rms_norm(h, shared["norm"], cfg.norm_eps),
                positions,
                cfg,
                causal=True,
                kv_override=(
                    None if fake_hook is None
                    else (lambda k, v: fake_hook(k, v, lnk, lnv))
                ),
                cstr=cstr,
            )
            return cstr(common.radd(h, a)), None

        x, _ = common.uscan(group_body, cstr(x), (params["mamba"], nk, nv))

    elif cfg.family == "xlstm":

        def group_body(carry, group_params):
            def mbody(c, lp):
                lp = pcstr(lp)
                return cstr(common.radd(c, xlstm.mlstm_block(lp, c, cfg))), None

            mb = jax.checkpoint(mbody) if remat else mbody
            h, _ = common.uscan(mb, carry, group_params["mlstm"])
            h = common.radd(h, xlstm.slstm_block(group_params["slstm"], h, cfg))
            return cstr(h), None

        x, _ = common.uscan(group_body, cstr(x), params["groups"])
    else:
        raise ValueError(cfg.family)

    return lm_logits(params, cfg, x, cstr)


def train_loss(
    params,
    cfg: ModelConfig,
    batch: dict,
    *,
    quantizer: Optional[KVQuantizer] = None,
    fake_quant: bool = False,
    remat: bool = True,
    constraint=None,
    param_constraint=None,
) -> jax.Array:
    logits = forward(
        params, cfg, batch, quantizer=quantizer, fake_quant=fake_quant,
        remat=remat, constraint=constraint, param_constraint=param_constraint,
    )
    labels = batch["labels"]
    if cfg.frontend == "patch_stub":
        # loss only over the text region (patches are prefix context)
        logits = logits[:, -labels.shape[1]:]
    mask = batch.get("loss_mask")
    return common.softmax_xent(logits, labels, mask)


# ============================================================ prefill ======
class PrefillResult(NamedTuple):
    last_logits: jax.Array  # (B, V)
    kv_quant: Any  # per-layer-stacked QuantizedKV pair (K, V) or raw (k, v)
    last_hidden: jax.Array  # (B, D)
    states: Any = None  # recurrent states (hybrid_ssm / xlstm), layer-stacked


def _gather_last(x: jax.Array, last_index: Optional[jax.Array]) -> jax.Array:
    """(B, S, D) -> (B, 1, D) at per-row `last_index` (ragged prompts),
    or simply the final position when last_index is None."""
    if last_index is None:
        return x[:, -1:]
    idx = jnp.broadcast_to(
        last_index.astype(jnp.int32)[:, None, None],
        (x.shape[0], 1, x.shape[-1]))
    return jnp.take_along_axis(x, idx, axis=1)


def forward_prefill(
    params,
    cfg: ModelConfig,
    batch: dict,
    *,
    quantizer: Optional[KVQuantizer],
    remat: bool = True,
    constraint=None,
    param_constraint=None,
    last_index: Optional[jax.Array] = None,
) -> PrefillResult:
    """Full forward emitting the (quantized) KV cache stack as scan outputs.

    For sliding-window configs only the trailing `window` positions are kept
    (ring layout, pos = t mod window).

    `last_index` ((B,) int32, optional) selects each row's last *valid*
    position for last_logits/last_hidden — ragged batches right-pad prompts
    to a common length, and the pad positions must not drive sampling.
    """
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    cstr = constraint if constraint is not None else (lambda t, kind="residual": t)
    pcstr = param_constraint if param_constraint is not None else (lambda t: t)
    window = cfg.sliding_window

    def encode_kv(k, v, lnk, lnv):
        if window is not None and s > window:
            # Ring layout: slot j holds the latest position p < L with
            # p == j (mod window), *per row* — ragged rows (last_index set)
            # keep their own trailing window; rows with L <= window keep
            # slot j = position j. Slots with no such p get an arbitrary
            # in-range position (they stay masked: slots >= min(L, window)).
            b_ = k.shape[0]
            lengths = (last_index + 1 if last_index is not None
                       else jnp.full((b_,), s, jnp.int32))
            j = jnp.arange(window)[None, :]
            pos = j + window * ((lengths[:, None] - 1 - j) // window)
            pos = jnp.clip(pos, 0, s - 1)  # (B, window)
            take = lambda t: jnp.take_along_axis(
                t, pos[:, :, None, None].astype(jnp.int32), axis=1)
            k, v = take(k), take(v)
        if quantizer is None:
            return (k, v)
        kq = quantizer.encode(k, lnk, quantizer.config.k_norm)
        vq = quantizer.encode(v, lnv, quantizer.config.v_norm)
        return (kq, vq)

    if cfg.family == "decoder":
        nk, nv = _layer_bins(quantizer, cfg.num_layers)

        def body(carry, xs):
            layer_params, lnk, lnv = xs
            layer_params = pcstr(layer_params)
            h, (k, v) = attention.attention_block(
                layer_params["attn"],
                common.rms_norm(carry, layer_params["norm1"], cfg.norm_eps),
                positions, cfg, causal=True, cstr=cstr,
            )
            xx = ffn_residual(layer_params, common.radd(carry, h), cfg, cstr)
            return cstr(xx), encode_kv(k, v, lnk, lnv)

        body_fn = jax.checkpoint(body) if remat else body
        x, kv = common.uscan(body_fn, cstr(x), (params["layers"], nk, nv))
        x_last = _gather_last(x, last_index)
        logits = lm_logits(params, cfg, x_last)[:, 0]
        return PrefillResult(logits, kv, x_last[:, 0])

    if cfg.family == "hybrid_ssm":
        n_groups = cfg.num_layers // cfg.attn_every
        nk, nv = _layer_bins(quantizer, n_groups)
        shared = params["shared_attn"]

        def group_body(carry, xs):
            group_params, lnk, lnv = xs

            def mamba_body(c, lp):
                lp = pcstr(lp)
                out, st = ssm.mamba2_block(
                    lp["ssm"], common.rms_norm(c, lp["norm"], cfg.norm_eps),
                    cfg, return_state=True)
                return cstr(common.radd(c, out)), st

            mb = jax.checkpoint(mamba_body) if remat else mamba_body
            h, states = common.uscan(mb, carry, group_params)
            a, (k, v) = attention.attention_block(
                shared["attn"],
                common.rms_norm(h, shared["norm"], cfg.norm_eps),
                positions, cfg, causal=True, cstr=cstr,
            )
            return cstr(common.radd(h, a)), (encode_kv(k, v, lnk, lnv), states)

        x, (kv, states) = common.uscan(
            group_body, cstr(x), (params["mamba"], nk, nv))
        x_last = _gather_last(x, last_index)
        logits = lm_logits(params, cfg, x_last)[:, 0]
        return PrefillResult(logits, kv, x_last[:, 0], states)

    if cfg.family == "xlstm":

        def group_body(carry, group_params):
            def mbody(c, lp):
                q, k, v, lf, li, z = xlstm._mlstm_qkv_gates(lp, c, cfg)
                y, st = xlstm.mlstm_sequence(q, k, v, lf, li)
                b_, s_ = c.shape[0], c.shape[1]
                y = y.reshape(b_, s_, cfg.num_heads * cfg.head_dim
                              ).astype(c.dtype)
                y = common.rms_norm(y, lp["out_norm"], cfg.norm_eps
                                    ) * jax.nn.silu(z)
                out = jnp.einsum("bsk,kd->bsd", y, lp["w_down"])
                return cstr(common.radd(c, out)), st

            mb = jax.checkpoint(mbody) if remat else mbody
            h, mstates = common.uscan(mb, carry, group_params["mlstm"])
            # sLSTM: rerun the scan to obtain the final state (prefill only)
            sp = group_params["slstm"]
            xn = common.rms_norm(h, sp["norm"], cfg.norm_eps)
            wx = jnp.einsum("bsd,dk->bsk", xn, sp["w_in"]) + sp["gate_bias"]
            sstate = xlstm.init_slstm_state(h.shape[0], cfg)
            sfinal, hs = common.uscan(
                lambda c2, w: xlstm._slstm_step(sp, cfg, c2, w),
                sstate, wx.swapaxes(0, 1))
            y = hs.swapaxes(0, 1).reshape(h.shape).astype(h.dtype)
            h = common.radd(h, jnp.einsum("bsd,dk->bsk", y, sp["w_down"]))
            return cstr(h), (mstates, sfinal)

        x, states = common.uscan(group_body, cstr(x), params["groups"])
        # NOTE: last_index only fixes the logits gather here; the recurrent
        # states have processed any padding (ragged xlstm is not exact)
        x_last = _gather_last(x, last_index)
        logits = lm_logits(params, cfg, x_last)[:, 0]
        return PrefillResult(logits, None, x_last[:, 0], states)

    raise ValueError(f"prefill not defined for family {cfg.family}")
