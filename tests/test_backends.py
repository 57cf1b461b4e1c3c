"""Attention-backend layer: parity across implementations, ragged batches,
selection logic, and the batched serving engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import kvcache
from repro.configs import registry
from repro.configs.base import ModelConfig, RunConfig
from repro.core import mixedkv, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.models import transformer
from repro.serving import backends as backends_lib
from repro.serving import decode as decoding
from repro.serving import engine


def _cfg(**kw):
    base = dict(name="t", family="decoder", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                head_dim=32)
    base.update(kw)
    return ModelConfig(**base)


def _qz(cfg, norm, schedule=None):
    return KVQuantizer(QuantizerConfig(
        head_dim=cfg.head_dim,
        schedule=schedule or mixedkv.uniform(cfg.num_layers),
        k_norm=norm, v_norm=norm))


NORMS = [
    pytest.param(rates.NORM_FP32, id="fp32"),
    pytest.param(rates.NormConfig(8, False), id="8bit"),
    pytest.param(rates.NormConfig(4, True), id="4bit-log"),
]


# ------------------------------------------------- pallas/xla parity -------
@pytest.mark.parametrize("norm", NORMS)
def test_backend_parity_pallas_vs_xla_ragged(norm):
    """quant-pallas (interpret) == quant-xla within 1e-3 on a ragged batch,
    for all three norm configurations."""
    cfg = _cfg()
    qz = _qz(cfg, norm)
    # f32 y_dtype matches the kernel's in-VMEM dequant precision; the bf16
    # default trades ~3e-3 of agreement for half the HBM traffic (checked
    # separately below).
    xla = backends_lib.QuantXLABackend(cfg, qz, y_dtype=jnp.float32)
    xla_bf16 = backends_lib.QuantXLABackend(cfg, qz)
    pallas = backends_lib.QuantPallasBackend(cfg, qz, interpret=True)

    b, t = 4, 40
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    layer_cache = (qz.encode(k, 128, qz.config.k_norm),
                   qz.encode(v, 64, qz.config.v_norm))
    n_valid = jnp.asarray([3, 17, 29, 40], jnp.int32)  # ragged

    got = pallas.attend(q, layer_cache, 128, 64, n_valid)
    want = xla.attend(q, layer_cache, 128, 64, n_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    want_bf16 = xla_bf16.attend(q, layer_cache, 128, 64, n_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_bf16),
                               rtol=2e-2, atol=1e-2)


def test_backend_parity_traced_bins():
    """n_bins can be traced per-layer scan values (MixedKV schedules)."""
    cfg = _cfg()
    qz = _qz(cfg, rates.NormConfig(8, False),
             schedule=mixedkv.early_boost(cfg.num_layers, 1, 256, 128))
    xla = backends_lib.QuantXLABackend(cfg, qz, y_dtype=jnp.float32)
    pallas = backends_lib.QuantPallasBackend(cfg, qz, interpret=True)

    b, t = 2, 24
    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    n_valid = jnp.asarray([11, 24], jnp.int32)
    nk, nv = qz.layer_bins()

    def per_layer(nk_l, nv_l):
        cache = (qz.encode(k, nk_l, qz.config.k_norm),
                 qz.encode(v, nv_l, qz.config.v_norm))
        return (pallas.attend(q, cache, nk_l, nv_l, n_valid),
                xla.attend(q, cache, nk_l, nv_l, n_valid))

    got, want = jax.lax.map(lambda ab: per_layer(*ab), (nk, nv))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_raw_backend_matches_direct_kvcache():
    cfg = _cfg()
    be = backends_lib.RawBackend(cfg, dtype=jnp.float32)
    b, t = 2, 16
    rng = np.random.default_rng(2)
    layer_k = jnp.asarray(
        rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)), jnp.float32)
    layer_v = jnp.asarray(
        rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    n_valid = jnp.asarray([5, 16], jnp.int32)
    got = be.attend(q, (layer_k, layer_v), 0, 0, n_valid)
    want = kvcache.attend_raw_cache(q, layer_k, layer_v, n_valid, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# ------------------------------------------------ selection / config -------
def test_backend_selection():
    cfg = _cfg()
    qz = _qz(cfg, rates.NORM_K8)
    run = RunConfig(model=cfg)
    assert backends_lib.from_run(run, qz).name == "quant-xla"
    assert backends_lib.from_run(run, None).name == "raw"
    run_p = dataclasses.replace(
        run, model=dataclasses.replace(cfg, use_pallas=True))
    assert backends_lib.from_run(run_p, qz,
                                 interpret=True).name == "quant-pallas"
    run_exp = dataclasses.replace(run, backend="quant-pallas")
    assert backends_lib.from_run(run_exp, qz,
                                 interpret=True).name == "quant-pallas"
    # quant-pallas never guesses between interpreter and compiled kernels
    with pytest.raises(ValueError, match="interpret"):
        backends_lib.from_run(run_exp, qz)
    if jax.default_backend() != "tpu":  # compiled kernels need a TPU
        with pytest.raises(RuntimeError, match="need a TPU"):
            backends_lib.QuantPallasBackend(cfg, qz, interpret=False)
    with pytest.raises(ValueError):
        backends_lib.from_run(dataclasses.replace(run, backend="quant-xla"),
                              None)
    with pytest.raises(ValueError):
        backends_lib.get_backend("nope", cfg)


def test_pallas_backend_accepts_bitpack_and_matches_xla():
    """quant-pallas reads the packed word stream directly (in-kernel
    unpack); parity with quant-xla at f32 y_dtype within 1e-3."""
    cfg = _cfg()
    qz = KVQuantizer(QuantizerConfig(
        head_dim=cfg.head_dim,
        schedule=mixedkv.uniform(cfg.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage="bitpack"))
    xla = backends_lib.QuantXLABackend(cfg, qz, y_dtype=jnp.float32)
    pallas = backends_lib.QuantPallasBackend(cfg, qz, interpret=True)
    b, t = 2, 24
    rng = np.random.default_rng(12)
    k = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    cache = (qz.encode(k, 128, qz.config.k_norm),
             qz.encode(v, 64, qz.config.v_norm))
    assert cache[0].indices.dtype == jnp.uint32
    n_valid = jnp.asarray([13, 24], jnp.int32)
    got = pallas.attend(q, cache, 128, 64, n_valid)
    want = xla.attend(q, cache, 128, 64, n_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


# ------------------------------------------------- ragged decode ----------
def test_ragged_decode_matches_per_row_reference():
    """A ragged batch through the raw backend must produce the same greedy
    tokens as serving each row alone at its exact prompt length."""
    cfg = _cfg(vocab_size=128)
    params, _ = transformer.init_params(jax.random.PRNGKey(0), cfg)
    be = backends_lib.RawBackend(cfg, dtype=jnp.float32)
    lens = [9, 5]
    gen = 4
    rng = np.random.default_rng(3)
    rows = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)
            for n in lens]

    # reference: one row at a time, no padding anywhere
    ref_tokens = []
    for row in rows:
        res = engine.generate(params, cfg, be, row,
                              max_new_tokens=gen)
        ref_tokens.append(np.asarray(res.tokens)[0])

    # ragged batch: right-padded to a common width
    s_max = max(lens)
    batch = np.zeros((len(lens), s_max), np.int32)
    for i, row in enumerate(rows):
        batch[i, : lens[i]] = np.asarray(row)[0]
    res = engine.generate(params, cfg, be, jnp.asarray(batch),
                          jnp.asarray(lens, jnp.int32), max_new_tokens=gen)
    for i in range(len(lens)):
        np.testing.assert_array_equal(np.asarray(res.tokens)[i],
                                      ref_tokens[i])


def test_sliding_window_crossing_pallas_matches_xla():
    """Decoding past the window boundary: the kernel must clamp n_valid to
    the ring size exactly like _score_mask (regression: unwritten slots
    past the window used to enter the softmax on the pallas path)."""
    cfg = _cfg(sliding_window=8, vocab_size=64)
    qz = _qz(cfg, rates.NormConfig(8, False))
    params, _ = transformer.init_params(jax.random.PRNGKey(5), cfg)
    prompts = jnp.asarray(
        np.random.default_rng(7).integers(0, 64, (2, 6)), jnp.int32)
    outs = {}
    for be in (backends_lib.QuantXLABackend(cfg, qz, y_dtype=jnp.float32),
               backends_lib.QuantPallasBackend(cfg, qz, interpret=True)):
        res = engine.generate(params, cfg, be, prompts, max_new_tokens=8)
        outs[be.name] = np.asarray(res.tokens)
        # ring cache never grows past the window
        assert res.cache.k.indices.shape[2] == 8
    np.testing.assert_array_equal(outs["quant-xla"], outs["quant-pallas"])


def test_ragged_sliding_window_prefill_matches_per_row():
    """Ragged prompts wider than the window: each row must keep ITS OWN
    trailing window in ring order (regression: the batch-uniform trailing
    slice dropped short rows' real tokens)."""
    cfg = _cfg(sliding_window=8, vocab_size=128)
    params, _ = transformer.init_params(jax.random.PRNGKey(6), cfg)
    be = backends_lib.RawBackend(cfg, dtype=jnp.float32)
    lens = [12, 4]
    gen = 4
    rng = np.random.default_rng(8)
    rows = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)
            for n in lens]
    ref = [np.asarray(engine.generate(params, cfg, be, row,
                                      max_new_tokens=gen).tokens)[0]
           for row in rows]
    batch = np.zeros((len(lens), max(lens)), np.int32)
    for i, row in enumerate(rows):
        batch[i, : lens[i]] = np.asarray(row)[0]
    res = engine.generate(params, cfg, be, jnp.asarray(batch),
                          jnp.asarray(lens, jnp.int32), max_new_tokens=gen)
    for i in range(len(lens)):
        np.testing.assert_array_equal(np.asarray(res.tokens)[i], ref[i])


# ------------------------------------------------- engine -----------------
def test_engine_serves_xlstm_family():
    """Cache-less recurrent families generate through the same engine."""
    cfg = registry.get_reduced_config("xlstm-350m")
    params, _ = transformer.init_params(jax.random.PRNGKey(7), cfg)
    be = backends_lib.RawBackend(cfg)
    prompts = jnp.asarray(
        np.random.default_rng(9).integers(0, cfg.vocab_size, (2, 6)),
        jnp.int32)
    res = engine.generate(params, cfg, be, prompts, max_new_tokens=3)
    assert np.asarray(res.tokens).shape == (2, 3)
    assert res.cache is None
    with pytest.raises(ValueError):  # ragged needs the KV-cache mask
        engine.generate(params, cfg, be, prompts,
                        jnp.asarray([6, 3], jnp.int32), max_new_tokens=2)



def test_engine_eos_early_exit_and_padding():
    cfg = _cfg(vocab_size=64)
    params, _ = transformer.init_params(jax.random.PRNGKey(1), cfg)
    be = backends_lib.RawBackend(cfg, dtype=jnp.float32)
    rng = np.random.default_rng(4)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 8)), jnp.int32)

    free = engine.generate(params, cfg, be, prompts, max_new_tokens=6)
    toks = np.asarray(free.tokens)
    assert toks.shape == (3, 6)
    assert np.asarray(free.num_generated).tolist() == [6, 6, 6]

    # force row 0 to terminate immediately: its first greedy token is EOS
    eos = int(toks[0, 0])
    res = engine.generate(params, cfg, be, prompts, max_new_tokens=6,
                          eos_id=eos, pad_id=-1)
    out = np.asarray(res.tokens)
    num = np.asarray(res.num_generated)
    assert num[0] == 1
    assert (out[0, 1:] == -1).all()
    for i in range(3):
        hits = np.nonzero(out[i] == eos)[0]
        if hits.size:
            assert num[i] == hits[0] + 1
            assert (out[i, hits[0] + 1:] == -1).all()
        else:
            assert num[i] == res.steps
    # all rows hitting EOS early must stop the loop before max_new_tokens
    if (num < 6).all():
        assert int(res.steps) < 6


def test_engine_sampling_configs_run():
    cfg = _cfg(vocab_size=64)
    params, _ = transformer.init_params(jax.random.PRNGKey(2), cfg)
    be = backends_lib.RawBackend(cfg, dtype=jnp.float32)
    prompts = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (2, 6)), jnp.int32)
    for sc in (engine.SamplingConfig(temperature=0.8),
               engine.SamplingConfig(temperature=1.0, top_k=5),
               engine.SamplingConfig(temperature=1.0, top_p=0.9),
               engine.SamplingConfig(temperature=0.7, top_k=8, top_p=0.95)):
        res = engine.generate(params, cfg, be, prompts, max_new_tokens=3,
                              sampling=sc, rng=jax.random.PRNGKey(7))
        toks = np.asarray(res.tokens)
        assert toks.shape == (2, 3)
        assert ((toks >= 0) & (toks < 64)).all()


def test_engine_quant_backends_end_to_end():
    """Both quantized backends drive the engine on a ragged batch and report
    a compressed cache."""
    cfg = registry.get_reduced_config("qwen3-0.6b")
    cfg = dataclasses.replace(cfg, num_layers=2)
    qz = _qz(cfg, rates.NormConfig(8, False))
    params, _ = transformer.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(6)
    lens = jnp.asarray([10, 6], jnp.int32)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 10)), jnp.int32)

    outs = {}
    # quant-xla at f32 y_dtype: the kernel dequantizes in f32, so this is
    # the setting at which the two backends compute the same attention
    for be in (backends_lib.QuantXLABackend(cfg, qz, y_dtype=jnp.float32),
               backends_lib.QuantPallasBackend(cfg, qz, interpret=True)):
        res = engine.generate(params, cfg, be, prompts, lens,
                              max_new_tokens=4)
        outs[be.name] = np.asarray(res.tokens)
        raw_ref = jax.eval_shape(
            lambda: kvcache.init_raw_cache(cfg, 2, 14, jnp.bfloat16))
        assert (kvcache.cache_physical_bytes(res.cache)
                < kvcache.cache_physical_bytes(raw_ref))
    # the two quantized backends see identical caches -> identical greedy
    # tokens (parity is asserted numerically above; this is end-to-end)
    np.testing.assert_array_equal(outs["quant-xla"], outs["quant-pallas"])


def test_sample_tokens_top_k_top_p_masking():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]], jnp.float32))
    rng = jax.random.PRNGKey(0)
    # top_k=1 == greedy regardless of rng
    sc = engine.SamplingConfig(temperature=1.0, top_k=1)
    for i in range(5):
        tok = engine.sample_tokens(jax.random.fold_in(rng, i), logits, sc)
        assert int(tok[0]) == 0
    # top_p=0.6 keeps tokens {0, 1} only (0.5 then crossing 0.3)
    sc = engine.SamplingConfig(temperature=1.0, top_p=0.6)
    seen = {int(engine.sample_tokens(jax.random.fold_in(rng, i), logits,
                                     sc)[0]) for i in range(64)}
    assert seen <= {0, 1}
    assert 0 in seen
    # top_p=0 degenerates to the most-likely token, not an all-masked vocab
    sc = engine.SamplingConfig(temperature=1.0, top_p=0.0)
    shifted = jnp.roll(logits, 2, axis=-1)  # most likely token is id 2
    for i in range(5):
        assert int(engine.sample_tokens(
            jax.random.fold_in(rng, i), shifted, sc)[0]) == 2
