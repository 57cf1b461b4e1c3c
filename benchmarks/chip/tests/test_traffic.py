"""The traffic generator: same requests per seed, the same sizes on every
seed, lengths drawn from the mix's stated distributions."""
import statistics

import numpy as np
import pytest

import harness
import traffic

MIXES = ["doc-decode", "reason-decode", "chat-open"]


def mix(name):
    return harness._json(harness.HERE / "traffic" / f"{name}.json")


def sizes(tr):
    return sorted((len(it.tokens), it.max_new_tokens) for it in tr.window)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 1000, 2**31 + 5, 30)
    b = traffic.generate(mix(name), 1000, 2**31 + 5, 30)
    assert len(a.window) == len(b.window) > 0
    for x, y in zip(a.window, b.window):
        assert np.array_equal(x.tokens, y.tokens)
        assert (x.max_new_tokens, x.arrival, x.shared) == \
            (y.max_new_tokens, y.arrival, y.shared)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_permute_one_set_of_sizes(name):
    a = traffic.generate(mix(name), 1000, 1, 30)
    b = traffic.generate(mix(name), 1000, 2, 30)
    assert sizes(a) == sizes(b)
    assert [len(it.tokens) for it in a.window] != \
        [len(it.tokens) for it in b.window]


def test_uniform_lengths_are_stratified_quantiles():
    q = traffic.quantiles({"dist": "uniform", "lo": 256, "hi": 768}, 16)
    assert q.min() >= 256 and q.max() <= 768
    assert abs(q.mean() - 512) < 2  # the midpoint of 256..768


def test_lognormal_lengths_keep_their_median_and_clip():
    d = {"dist": "lognormal", "median": 192, "sigma": 1.0, "lo": 16,
         "hi": 1024}
    q = traffic.quantiles(d, 64)
    assert q.min() >= 16 and q.max() == 1024  # the tail is clipped
    assert abs(statistics.median(q) - 192) <= 4
    # about 4.8% of a lognormal(sigma 1) lies above 1024/192 x the median
    assert 2 <= int((q == 1024).sum()) <= 5


def test_open_loop_arrivals_keep_the_rate():
    m = mix("chat-open")
    tr = traffic.generate(m, 1000, 7, 30)
    arr = np.array([it.arrival for it in tr.window])
    assert np.all(np.diff(arr) > 0)
    rate = len(arr) / arr[-1]
    assert abs(rate / m["rate_per_s"] - 1) < 0.05


def test_documents_prefix_every_request():
    m = mix("doc-decode")
    tr = traffic.generate(m, 1000, 3, 30)
    assert len(tr.documents) > 1
    uses = np.zeros(len(tr.documents), int)
    for it in tr.window:
        hit = [i for i, d in enumerate(tr.documents) if len(d) == it.shared
               and np.array_equal(it.tokens[:it.shared], d)]
        assert len(hit) == 1
        uses[hit[0]] += 1
    # every document is asked about equally often, in a seeded order
    assert np.all(uses == len(tr.window) // len(tr.documents))


def test_closed_loop_backlog_is_queued_at_zero():
    tr = traffic.generate(mix("reason-decode"), 1000, 3, 30)
    assert {it.arrival for it in tr.window} == {0.0}
