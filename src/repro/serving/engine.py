"""Batched ragged serving engine: prefill + jit-compiled sampling decode loop.

Built entirely on the pluggable attention-backend layer
(`repro.serving.backends`): the same engine serves the raw bf16 cache, the
quantized XLA fallback, and the fused Pallas kernel — the backend is just a
constructor argument.

Ragged batches: prompts arrive right-padded to a common width with a (B,)
`prompt_lengths` vector. Prefill runs once over the padded batch (causal
masking means real tokens never see the pads), the per-row last *valid*
hidden state drives the first sampled token, and decode appends each row at
its own cache slot. Pad slots hold garbage K/V but stay masked until the
row's decode frontier overwrites them.

Decode is a `lax.while_loop` so generation stops as soon as every sequence
has emitted EOS — a batch of short answers does not pay for `max_new_tokens`
steps. Sampling supports temperature / top-k / top-p (greedy when
temperature == 0).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer
from repro.serving import decode as decoding
from repro.serving.backends import AttentionBackend
from repro.serving.telemetry import NULL_TRACER

NEG_INF = -1e30


class SamplingConfig(NamedTuple):
    """temperature == 0 -> greedy (top_k/top_p ignored). top_k == 0 and
    top_p >= 1 disable the respective filter."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    @property
    def is_greedy(self) -> bool:
        """True when sampling is deterministic argmax — the mode whose
        tokens speculative decoding can reproduce losslessly."""
        return self.temperature <= 0.0


class GenerationResult(NamedTuple):
    tokens: jax.Array  # (B, max_new_tokens) int32; pad_id after a row's EOS
    num_generated: jax.Array  # (B,) tokens emitted incl. the EOS itself
    steps: jax.Array  # () decode-loop steps actually executed
    cache: object  # final cache (compression reporting)


@jax.named_scope("sample")
def sample_tokens(rng: jax.Array, logits: jax.Array,
                  sc: SamplingConfig) -> jax.Array:
    """(B, V) logits -> (B,) sampled token ids."""
    if sc.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / sc.temperature
    if sc.top_k > 0 and sc.top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, sc.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if sc.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep every token whose prefix mass (exclusive) is < top_p, so the
        # token crossing the threshold is included; the most-likely token is
        # always kept (top_p <= 0 would otherwise mask the whole vocab)
        keep = (cum - probs) < sc.top_p
        keep = keep.at[..., 0].set(True)
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, NEG_INF, logits)
    return jax.random.categorical(rng, logits).astype(jnp.int32)


class _LoopCarry(NamedTuple):
    state: decoding.DecodeState
    out: jax.Array  # (B, max_new) token buffer
    nxt: jax.Array  # (B, 1) next token to feed
    done: jax.Array  # (B,) bool
    step: jax.Array  # () int32 — tokens emitted so far
    rng: jax.Array


@functools.lru_cache(maxsize=32)
def _build_generate(cfg: ModelConfig, backend: AttentionBackend,
                    sc: SamplingConfig, max_new_tokens: int,
                    eos_id: Optional[int], pad_id: int):
    """jit-compiled (params, prompts, prompt_lengths, rng) -> result pieces.

    Cached per (cfg, backend, sampling, lengths) signature so repeated
    `generate` calls reuse the compiled executable.
    """
    if cfg.family == "encoder":
        raise ValueError("encoder-only families do not generate")

    def generate_batch(params, prompts, prompt_lengths, rng):
        b, s_max = prompts.shape
        total = s_max + max_new_tokens
        pre = transformer.forward_prefill(
            params, cfg, {"tokens": prompts}, quantizer=backend.quantizer,
            remat=False, last_index=prompt_lengths - 1)
        cache = None
        if cfg.has_kv_cache:
            cache = backend.cache_from_prefill(
                pre.kv_quant, prompt_lengths, pad_to=total)
        state = decoding.DecodeState(cache=cache, states=pre.states)

        rng, sub = jax.random.split(rng)
        first = sample_tokens(sub, pre.last_logits, sc)
        done0 = (first == eos_id) if eos_id is not None \
            else jnp.zeros((b,), bool)
        out0 = jnp.full((b, max_new_tokens), pad_id, jnp.int32)
        out0 = out0.at[:, 0].set(first)
        carry = _LoopCarry(state, out0, first[:, None], done0,
                           jnp.asarray(1, jnp.int32), rng)

        def cond(c: _LoopCarry):
            return (c.step < max_new_tokens) & ~jnp.all(c.done)

        def body(c: _LoopCarry):
            rng, sub = jax.random.split(c.rng)
            logits, state = decoding.decode_step(
                params, cfg, c.state, c.nxt, backend=backend)
            tok = sample_tokens(sub, logits, sc)
            tok = jnp.where(c.done, pad_id, tok)
            out = jax.lax.dynamic_update_slice(
                c.out, tok[:, None], (0, c.step))
            done = c.done | ((tok == eos_id) if eos_id is not None
                             else False)
            return _LoopCarry(state, out, tok[:, None], done, c.step + 1,
                              rng)

        final = jax.lax.while_loop(cond, body, carry)
        if eos_id is None:
            num = jnp.full((b,), max_new_tokens, jnp.int32)
        else:
            is_eos = final.out == eos_id
            num = jnp.where(jnp.any(is_eos, axis=1),
                            jnp.argmax(is_eos, axis=1) + 1,
                            jnp.minimum(final.step, max_new_tokens))
        return final.out, num, final.step, final.state.cache

    return jax.jit(generate_batch)


def generate(
    params,
    cfg: ModelConfig,
    backend: AttentionBackend,
    prompts: jax.Array,  # (B, S_max) int32, right-padded
    prompt_lengths=None,  # (B,) valid prompt tokens; None -> full width
    *,
    max_new_tokens: int = 32,
    sampling: SamplingConfig = SamplingConfig(),
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    rng: Optional[jax.Array] = None,
    telemetry=None,
) -> GenerationResult:
    """Generate continuations for a (possibly ragged) batch of prompts.

    The static-batch entry point: one prefill over the padded batch, then
    a jit-compiled `lax.while_loop` of single-token decode steps through
    `backend` (raw / quant-xla / quant-pallas — see `serving.backends`).
    The loop exits as soon as every row has emitted `eos_id`, so a batch
    of short answers does not pay for `max_new_tokens` steps. For
    continuous batching over a shared page pool use
    `serving.scheduler.PagedServingEngine` instead.

    Args:
        params, cfg: model parameters and config (any generating family;
            ragged prompts require `family == "decoder"`).
        backend: the attention-backend dispatch point; its cache
            representation decides memory footprint and decode bandwidth.
        prompts: (B, S_max) int32 token ids, right-padded.
        prompt_lengths: (B,) valid tokens per row; None means every row
            uses the full width. Validated eagerly (>= 1, <= S_max).
        max_new_tokens: decode-step budget per row.
        sampling: temperature / top-k / top-p; temperature 0 is greedy.
        eos_id: stop a row once it samples this id (None: never).
        pad_id: filler written after a row's EOS in the output buffer.
        rng: sampling key (defaults to PRNGKey(0) for reproducibility).
        telemetry: optional `serving.telemetry.Telemetry`; when given, the
            call emits a "generate" span (batch/width/steps) and bumps
            `generate_calls` / `generate_tokens` counters.

    Returns:
        GenerationResult with (B, max_new_tokens) tokens, per-row
        generated counts (EOS included), executed step count, and the
        final cache (for compression reporting).

    Compiled executables are cached per (cfg, backend, sampling, widths)
    signature, so repeated calls at the same shapes are dispatch-only.
    """
    b, s_max = prompts.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), s_max, jnp.int32)
    prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if prompt_lengths.shape != (b,):
        raise ValueError(
            f"prompt_lengths must be shape ({b},), got "
            f"{prompt_lengths.shape}")
    lens_np = np.asarray(prompt_lengths)
    if lens_np.min() < 1:
        raise ValueError(
            f"prompt_lengths must be >= 1 (a row needs at least one real "
            f"token to sample from), got min {lens_np.min()}")
    if lens_np.max() > s_max:
        raise ValueError(
            f"prompt_lengths exceed the prompt width {s_max} "
            f"(max {lens_np.max()})")
    if cfg.family != "decoder" and bool(
            jnp.any(prompt_lengths != s_max)):
        # recurrent states (mamba / xlstm) process pad tokens during a
        # padded prefill — only the KV-cache attention path masks them
        raise ValueError(
            f"ragged prompts are only exact for family 'decoder'; "
            f"{cfg.family!r} needs uniform prompt lengths")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    fn = _build_generate(cfg, backend, sampling, int(max_new_tokens),
                         None if eos_id is None else int(eos_id),
                         int(pad_id))
    tracer = telemetry.tracer if telemetry is not None else NULL_TRACER
    with tracer.region("generate", batch=b, width=s_max) as span:
        tokens, num, steps, cache = fn(params, prompts, prompt_lengths, rng)
        if telemetry is not None:
            n_new = int(jnp.sum(num))
            telemetry.registry.counter(
                "generate_calls", help="static-batch generate() calls").inc()
            telemetry.registry.counter(
                "generate_tokens", help="tokens emitted by generate()"
            ).inc(n_new)
            span.set(steps=int(steps), tokens=n_new)
    return GenerationResult(tokens=tokens, num_generated=num, steps=steps,
                            cache=cache)
