"""Work counts of roofline.py against hand counts at both configurations'
published widths."""
import pytest

import arch
import harness
import roofline


def cfg(name):
    return harness._json(harness.HERE / "configs" / f"{name}.json")


def test_qwen3_matmul_params():
    m = cfg("qwen3-0.6b")
    attn = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024  # q, k, v, o
    mlp = 3 * 1024 * 3072
    assert roofline.matmul_params(m) == 28 * (attn + mlp) + 1024 * 151936


def test_deepseek_matmul_params():
    m = cfg("deepseek-7b-l8")
    per_layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert roofline.matmul_params(m) == 8 * per_layer + 4096 * 102400


@pytest.mark.parametrize("name,per_layer_bits", [
    # K: log2(bins)/2 + 8/2 + 64/128; V: log2(bins)/2 + 4/2 + 64/128
    ("qwen3-0.6b", [4 + 4 + .5 + 3.5 + 2 + .5] * 4
     + [3.5 + 4 + .5 + 3 + 2 + .5] * 24),
    ("deepseek-7b-l8", [14.5] * 4 + [13.5] * 4),
])
def test_kv_bits(name, per_layer_bits):
    m = cfg(name)
    q = m["serving"]["kv_quant"]
    layers = arch.of(m).attention_layers(m)
    assert roofline.kv_bits(q, layers[0][2], len(layers)) \
        == pytest.approx(per_layer_bits)


def test_qattn_work_by_hand():
    m = cfg("qwen3-0.6b")
    q = m["serving"]["kv_quant"]
    # one token attending over 1000 positions
    flops, nbytes = roofline.qattn_work(m, q, 1, 1000)
    assert flops == 4 * 16 * 128 * 1000 * 28
    bits = 4 * 14.5 + 24 * 13.5  # summed over layers, per element
    codes = 1000 * 8 * 128 * bits / 8
    assert nbytes == pytest.approx(codes + 28 * 2 * 16 * 128 * 4)


def test_model_flops_by_hand():
    m = cfg("deepseek-7b-l8")
    p = roofline.matmul_params(m)
    head = 4096 * 102400
    attn = 4 * 32 * 128 * 8
    # two decode tokens at contexts 10 and 11, one prefill of 3 tokens
    f = roofline.model_flops(m, 21, 2, [(3, 0)])
    want = 2 * p * 2 + attn * 21 + 2 * (p - head) * 3 + 2 * head \
        + attn * (1 + 2 + 3)
    assert f == pytest.approx(want)


@pytest.mark.parametrize("name,flops,work,step_bytes", [
    ("qwen3-0.6b", 11756922961920.0, (283180040192.0, 64448739584.0),
     1246596451.1338725),
    ("deepseek-7b-l8", 39143719698432.0, (161817165824.0, 73128353792.0),
     4140929461.937107),
])
def test_counts_pinned_at_published_widths(name, flops, work, step_bytes,
                                           monkeypatch):
    """The counts behind `decode_mfu`, `qattn_roofline` and the spans
    tool's `decode_step_bytes`, equal to the last bit to what they read
    before the counts went through the architecture modules."""
    monkeypatch.syspath_prepend(str(harness.HERE / "tools"))
    import spans

    m = cfg(name)
    q = m["serving"]["kv_quant"]
    assert roofline.model_flops(m, 1234567, 8901,
                                [(4200, 4096), (700, 0)]) == flops
    assert roofline.qattn_work(m, q, 8901, 1234567) == work
    assert spans.decode_step_bytes(m, 1113, 8901, 1234567) == step_bytes
