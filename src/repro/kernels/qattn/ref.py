"""Pure-jnp oracle for the quantized-KV decode attention kernel."""
import jax
import jax.numpy as jnp

from repro.core import angular, norms


def _dequant_norms(nq, rmin, rmax, bits, log):
    if bits is None:
        return nq.astype(jnp.float32)
    return norms.dequantize_norms(
        norms.QuantizedNorms(nq.astype(jnp.int32), rmin, rmax), bits,
        log_space=log)


def qattn_ref(q_rot, k_idx, k_nq, k_rmin, k_rmax, v_idx, v_nq, v_rmin,
              v_rmax, length, *, n_bins_k: int, n_bins_v: int,
              k_norm_bits, k_norm_log, v_norm_bits, v_norm_log):
    """Hadamard-domain attention over a quantized cache.

    q_rot: (B, nkv, G, Dp) pre-rotated, pre-scaled queries.
    k/v codes: (B, T, nkv, Dp/2) + per-vector min/max (B, T, nkv, 1).
    length: () uniform or (B,) per-sequence valid-token counts.
    Returns the y-domain output (B, nkv, G, Dp) — caller applies DH.
    """
    y_k = angular.decode_rotated(
        angular.AngularCode(
            k_idx.astype(jnp.int32),
            _dequant_norms(k_nq, k_rmin, k_rmax, k_norm_bits, k_norm_log)),
        n_bins_k)
    y_v = angular.decode_rotated(
        angular.AngularCode(
            v_idx.astype(jnp.int32),
            _dequant_norms(v_nq, v_rmin, v_rmax, v_norm_bits, v_norm_log)),
        n_bins_v)
    scores = jnp.einsum("bngd,btnd->bngt", q_rot.astype(jnp.float32), y_k)
    t = k_idx.shape[1]
    lengths = jnp.asarray(length, jnp.int32).reshape(-1, 1)  # (B,1) or (1,1)
    mask = jnp.arange(t)[None, :] < lengths
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bngt,btnd->bngd", p, y_v)

