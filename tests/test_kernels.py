"""Pallas kernel tests (interpret mode): shape/dtype sweeps vs pure-jnp
oracles, plus end-to-end equivalence with the production XLA path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fwht as core_fwht
from repro.core import mixedkv, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.kernels.decode import ops as dec_ops
from repro.kernels.decode import ref as dec_ref
from repro.kernels.encode import ops as enc_ops
from repro.kernels.encode import ref as enc_ref
from repro.kernels.fwht import ops as fwht_ops
from repro.kernels.fwht import ref as fwht_ref
from repro.kernels.qattn import ops as qattn_ops
from repro.kernels.qattn import qattn as qattn_k
from repro.kernels.qattn import ref as qattn_ref


def _rand(shape, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(dtype))


# ------------------------------------------------------------------ fwht --
@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("rows", [8, 100, 512])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fwht_kernel_matches_ref(d, rows, dtype):
    x = _rand((rows, d), seed=d + rows).astype(dtype)
    got = fwht_ops.fwht_op(x)
    want = fwht_ref.fwht_ref(x)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("d", [64, 128])
def test_rotate_kernel_matches_ref(d):
    signs = core_fwht.make_signs(0, d)
    x = _rand((3, 5, d), seed=1)
    got = fwht_ops.rotate_op(x, signs)
    want = fwht_ref.rotate_ref(x, signs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fwht_kernel_self_inverse():
    x = _rand((64, 128), seed=2)
    np.testing.assert_allclose(
        np.asarray(fwht_ops.fwht_op(fwht_ops.fwht_op(x))), np.asarray(x),
        rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------- encode --
@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("n_bins", [64, 128, 256])
@pytest.mark.parametrize("norm", [(None, False), (8, False), (4, True)])
def test_encode_kernel_matches_ref(d, n_bins, norm):
    bits, log = norm
    signs = core_fwht.make_signs(0, d)
    x = _rand((2, 33, d), seed=d + n_bins)
    got = enc_ops.encode_op(x, signs, n_bins=n_bins, norm_bits=bits,
                            norm_log=log)
    want = enc_ref.encode_ref(x, signs, n_bins=n_bins, norm_bits=bits,
                              norm_log=log)
    # indices: allow off-by-one at bin boundaries (f32 atan2 ULP jitter)
    gi, wi = np.asarray(got[0]), np.asarray(want[0])
    diff = np.minimum(np.abs(gi - wi), n_bins - np.abs(gi - wi))
    assert (diff <= 1).all()
    assert (diff == 0).mean() > 0.999
    if bits is None:
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-5, atol=1e-6)
    else:
        gq, wq = np.asarray(got[1]), np.asarray(want[1])
        assert (np.abs(gq - wq) <= 1).all()
        assert (gq == wq).mean() > 0.999
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got[3]), np.asarray(want[3]),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------- decode --
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("n_bins", [64, 256])
@pytest.mark.parametrize("norm", [(None, False), (8, False), (4, True)])
def test_decode_kernel_matches_ref(d, n_bins, norm):
    bits, log = norm
    signs = core_fwht.make_signs(0, d)
    x = _rand((65, d), seed=3)
    idx, nq, rmin, rmax = enc_ref.encode_ref(
        x, signs, n_bins=n_bins, norm_bits=bits, norm_log=log)
    got = dec_ops.decode_op(idx, nq, rmin, rmax, signs, n_bins=n_bins,
                            norm_bits=bits, norm_log=log)
    want = dec_ref.decode_ref(idx, nq, rmin, rmax, signs, n_bins=n_bins,
                              norm_bits=bits, norm_log=log)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_encode_decode_kernel_roundtrip_distortion():
    """Kernel-path roundtrip hits the analytic angular MSE bound."""
    from repro.core import angular

    d, n_bins = 128, 128
    signs = core_fwht.make_signs(0, d)
    x = _rand((1024, d), seed=4)
    idx, nq, rmin, rmax = enc_ops.encode_op(x, signs, n_bins=n_bins)
    x_hat = dec_ops.decode_op(idx, nq, rmin, rmax, signs, n_bins=n_bins)
    rel = float(jnp.mean((x - x_hat) ** 2) / jnp.mean(x**2))
    bound = angular.angular_mse_bound(n_bins)
    assert rel < 1.5 * bound


# ----------------------------------------------------------------- qattn --
def _mk_cache(b, t, nkv, d, n_bins, bits, log, seed):
    signs = core_fwht.make_signs(0, d)
    kv = _rand((b, t, nkv, d), seed=seed)
    idx, nq, rmin, rmax = enc_ref.encode_ref(
        kv, signs, n_bins=n_bins, norm_bits=bits, norm_log=log)
    return idx, nq, rmin, rmax


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("norm", [(None, False, None, False),
                                  (8, False, 4, True)])
def test_qattn_kernel_matches_ref(g, d, norm):
    kb, klog, vb, vlog = norm
    b, t, nkv = 2, 160, 2
    n_k, n_v = 128, 64
    kc = _mk_cache(b, t, nkv, d, n_k, kb, klog, seed=5)
    vc = _mk_cache(b, t, nkv, d, n_v, vb, vlog, seed=6)
    q_rot = _rand((b, nkv, g, d), seed=7)
    length = jnp.asarray(130, jnp.int32)
    got = qattn_k.qattn(
        q_rot, *[jnp.asarray(a) for a in kc], *[jnp.asarray(a) for a in vc],
        length, n_bins_k=n_k, n_bins_v=n_v, k_bits=kb, k_log=klog,
        v_bits=vb, v_log=vlog, block_t=64, interpret=True)
    want = qattn_ref.qattn_ref(
        q_rot, *kc, *vc, length, n_bins_k=n_k, n_bins_v=n_v,
        k_norm_bits=kb, k_norm_log=klog, v_norm_bits=vb, v_norm_log=vlog)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_qattn_ops_matches_xla_cache_path():
    """Kernel wrapper == production attend_quant_cache bit-for-bit-ish."""
    from repro.cache import kvcache
    from repro.configs.base import ModelConfig

    cfg = ModelConfig(
        name="t", family="decoder", num_layers=1, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=64, vocab_size=32, head_dim=32)
    qz = KVQuantizer(QuantizerConfig(
        head_dim=32, schedule=mixedkv.uniform(1),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG))
    b, t = 2, 48
    rng = np.random.default_rng(8)
    k = jnp.asarray(rng.normal(size=(b, t, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, 2, 32)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, 4, 32)), jnp.float32)
    kq = qz.encode(k, 128, qz.config.k_norm)
    vq = qz.encode(v, 64, qz.config.v_norm)
    n_valid = jnp.asarray(40, jnp.int32)
    want = kvcache.attend_quant_cache(
        q, kq, vq, jnp.asarray(128), jnp.asarray(64), n_valid, cfg, qz)
    got = qattn_ops.attend_quant_cache_op(
        q, kq, vq, 128, 64, n_valid, cfg, qz, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
