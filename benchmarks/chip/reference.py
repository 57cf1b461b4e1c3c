"""What every architecture's plain reference shares, for the correctness
check: float32 products at the check's precision, RMSNorm, RoPE, and the
TurboAngle paper's quantizer (y = H D x with a seeded +-1 diagonal D and
the normalized Hadamard H; each consecutive pair of y stored as a
uniformly quantized angle, reconstructed at its bin centre, and a min-max
quantized norm, linear or in log space). Each architecture's own forward
pass is `served_logits` in `arch/<arch>.py`. Like them, this imports
nothing of the program under test and takes only the weights the
benchmark made and the configuration's file.

What the served system computes, and so what a reference reproduces:

* prompt positions attend to raw keys/values inside their own prefill
  chunk; to earlier chunks they attend through the quantizer when the
  prefix cache is on ("requant"), raw when it is off;
* every generated position attends to the quantized keys/values of all
  positions up to and including its own (encode-on-write, then read).

A reference runs layer by layer over the whole padded sequence, attention
in query blocks of one prefill chunk, so that a long context fits beside
the weights. `mode="highest"` takes every product at full float32
precision; `mode="high"` takes them in three bfloat16 passes (hi*hi +
hi*lo + lo*hi), written out so that it means the same on any backend: the
control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32, BF16 = jnp.float32, jnp.bfloat16
TWO_PI = 2.0 * np.pi


def make_dot(mode: str):
    """einsum at the precision the check runs in."""
    if mode == "highest":
        def dot(eq, a, b):
            return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=F32)
        return dot
    if mode == "high":
        def split(x):
            hi = x.astype(BF16)
            return hi, (x - hi.astype(F32)).astype(BF16)

        def dot(eq, a, b):
            (ah, al), (bh, bl) = split(a), split(b)

            def f(x, y):
                return jnp.einsum(eq, x, y, preferred_element_type=F32)

            return f(ah, bh) + (f(ah, bl) + f(al, bh))
        return dot
    raise ValueError(f"unknown precision mode {mode!r}")


def hadamard(d: int) -> np.ndarray:
    """Normalized Sylvester Hadamard matrix (symmetric, self-inverse)."""
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < d:
        h = np.block([[h, h], [h, -h]])
    return (h / np.sqrt(d)).astype(np.float32)


def rotation_signs(seed: int, d: int) -> jax.Array:
    """The +-1 diagonal D: a fair coin per coordinate from PRNGKey(seed)."""
    return jnp.where(jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5,
                                          (d,)), 1.0, -1.0).astype(F32)


def fake_quant(x, n_bins, norm_bits: int, log_space: bool, signs, h, dot):
    """TurboAngle round trip of the last axis of x (a power of two)."""
    y = dot("...i,ij->...j", x * signs, h)
    even, odd = y[..., 0::2], y[..., 1::2]
    r = jnp.sqrt(even * even + odd * odd)
    n = jnp.asarray(n_bins, F32)
    k = jnp.floor(jnp.mod(jnp.arctan2(odd, even), TWO_PI) * (n / TWO_PI))
    k = jnp.clip(k, 0.0, n - 1.0)
    theta = (k + 0.5) * (TWO_PI / n)
    levels = float(2 ** norm_bits - 1)
    v = jnp.log(jnp.maximum(r, 1e-12)) if log_space else r
    lo = jnp.min(v, axis=-1, keepdims=True)
    scale = jnp.maximum(jnp.max(v, axis=-1, keepdims=True) - lo, 1e-12)
    code = jnp.clip(jnp.round((v - lo) / scale * levels), 0.0, levels)
    v_hat = code / levels * scale + lo
    r_hat = jnp.exp(v_hat) if log_space else v_hat
    y_hat = jnp.stack([r_hat * jnp.cos(theta), r_hat * jnp.sin(theta)],
                      axis=-1).reshape(y.shape)
    return dot("...i,ij->...j", y_hat, h) * signs


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def rope(x, positions, theta: float):
    """Rotate-half RoPE over the last axis; x (T, heads, hd)."""
    hd = x.shape[-1]
    inv = (1.0 / (np.float32(theta) ** (np.arange(0, hd, 2, dtype=np.float32)
                                        / np.float32(hd)))).astype(np.float32)
    ang = positions[:, None, None].astype(F32) * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_bins(quant: dict, num_layers: int) -> tuple[np.ndarray, np.ndarray]:
    """Angle bins per layer: the first `n_early` layers boosted."""
    e = min(int(quant["n_early"]), num_layers)
    nk = [quant["boost_k"]] * e + [quant["base_k"]] * (num_layers - e)
    nv = [quant["boost_v"]] * e + [quant["base_v"]] * (num_layers - e)
    return np.asarray(nk, np.float32), np.asarray(nv, np.float32)


def freeze(d: dict) -> tuple:
    """Hashable form of a flat configuration dict (static jit argument)."""
    return tuple(sorted((k, v) for k, v in d.items()
                        if not isinstance(v, (dict, list))))


@jax.jit
def gap_of(ref_logits, chosen):
    """Per row: how far the reference's logit of `chosen` lies below the
    reference's best logit (0 where they agree)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - got
