"""The comparison that decides `correct` fails when the timed path is
broken underneath: a token altered where it is produced, and a decode step
that returns its state (the KV pool) unchanged. Each drives the rest of a
run (CPU, tiny size, interpreter) and must read `correct` false. The
control (the reference in three bfloat16 passes in the program's place)
must read a wider gap than the program."""
import harness

SEED = 4242


def correct(out):
    return all(c["value"] <= c["limit"] for c in out.checks.values())


def run(cell, **kw):
    return harness.run_cell(cell, SEED, 2.0, False, interpret=True,
                            log=lambda m: None, **kw)


def test_sound_run_is_correct(tiny_cell):
    assert correct(run(tiny_cell))


def test_altered_token_is_caught(tiny_cell, monkeypatch):
    from repro.serving import engine

    sample = engine.sample_tokens

    def altered(rng, logits, sc):
        return (sample(rng, logits, sc) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_tokens", altered)
    assert not correct(run(tiny_cell))


def test_state_left_unchanged_is_caught(tiny_cell, monkeypatch):
    from repro.serving import backends

    monkeypatch.setattr(backends.QuantPallasBackend, "paged_append",
                        lambda self, layer_cache, *a, **k: layer_cache)
    assert not correct(run(tiny_cell))


def test_control_reads_wider_than_the_program(monkeypatch):
    """The control is the reference in three bfloat16 passes in the
    program's place. Its logits differ from the reference's at `highest`,
    and its gap, read over the same served requests, is the one the
    verdict then holds to the limit. At these widths its rounding (about
    1e-5 of a logit) never reaches the margin between the two best tokens,
    so no argmax moves and it reads 0 like the program; at the cells' own
    sizes on the chip it moves argmaxes through the quantizer's bins and
    reads above every cell's limit (`tools/control.py`, PERF.md)."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    import conftest
    import reference

    cell = conftest.shrink(harness.load_cell("qwen3-0.6b.doc-decode"))
    tiny = cell.arch.model_config
    monkeypatch.setattr(cell.arch, "model_config",
                        lambda c: dataclasses.replace(tiny(c),
                                                      vocab_size=4096))
    out = run(cell, control=True)
    c = out.checks
    assert c["control_gap"]["value"] >= c["logit_gap"]["value"]
    assert harness.verdict(harness.control_checks(c)) == \
        (c["control_gap"]["value"] <= c["control_gap"]["limit"])
    above = dict(c, control_gap=dict(c["control_gap"],
                                     value=2 * c["logit_gap"]["limit"]))
    assert not harness.verdict(harness.control_checks(above))

    params = harness.make_weights(SEED,
                                  cell.arch.model_config(cell.config),
                                  cell.arch.init_leaf)
    toks = np.random.default_rng(0).integers(0, 4096, 64).astype(np.int32)
    kw = dict(model=reference.freeze(cell.config),
              quant=reference.freeze(cell.config["serving"]["kv_quant"]),
              chunk=32, requant=False, rows=8)
    hi, lo = (np.asarray(cell.arch.served_logits(
        params, jnp.asarray(toks), jnp.asarray(40, jnp.int32), mode=m, **kw))
        for m in ("highest", "high"))
    assert 0 < np.abs(hi - lo).max() < 1e-2 * np.abs(hi).max()
