"""The comparison that decides `correct`.

After the window has closed and the program's state is freed, a sample
drawn from the seed of the requests the window finished, with the one that
served the most tokens always in it, is run through the plain reference
(`served_logits` of the cell's `arch/<arch>.py`, on the helpers of
`reference.py`) once over its prompt and served tokens. Every served
token was the program's greedy choice; the number compared is the widest
gap by which a served token's reference logit lies below the reference's
best logit at that position (0 where the two agree).

The control puts the reference itself in the program's place, one
precision step below what the configuration states (three bfloat16
passes for float32 at `highest`), and reads the same gap for the token
that the lower precision puts first.
"""
from __future__ import annotations

import numpy as np

import reference


def sample(results, n: int, seed: int) -> list:
    """`n` completed results: the one with the most served tokens (then
    the longest prompt), and the rest drawn from the seed."""
    done = [r for r in results if r.status == "completed"]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), -r.prompt_len, r.rid))
    rest = done[1:]
    rng = np.random.default_rng([int(seed) % 2**64, 3])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[0]] + [rest[i] for i in sorted(pick)]


def padded_length(cell, items) -> int:
    """One padded length for every request of the cell, so that the
    reference compiles once: the longest prompt plus the longest output,
    rounded up to whole chunks."""
    chunk = cell.mix["prefill_chunk"]
    longest = max(len(it.tokens) for it in items) \
        + int(cell.mix["output"]["hi"])
    return -(-longest // chunk) * chunk


def gaps(params, cell, prompt, served, t_pad: int, modes=("highest",)):
    """Per served token, the gap of the token chosen by each of `modes`
    (the program's own served token for "served")."""
    import jax.numpy as jnp

    m, q = cell.config, cell.config["serving"]["kv_quant"]
    rows = int(cell.mix["output"]["hi"])
    toks = np.zeros((t_pad,), np.int32)
    full = np.concatenate([prompt, served]).astype(np.int32)
    toks[:len(full)] = full
    kw = dict(model=reference.freeze(m), quant=reference.freeze(q),
              chunk=int(cell.mix["prefill_chunk"]),
              requant=cell.mix["prefix_cache"] != "off", rows=rows)
    ref = cell.arch.served_logits(params, jnp.asarray(toks),
                                  jnp.asarray(len(prompt), jnp.int32),
                                  mode="highest", **kw)
    n = len(served)
    chosen = np.zeros((rows,), np.int32)
    chosen[:n] = served
    out = {"served": np.asarray(reference.gap_of(ref, jnp.asarray(chosen)))[:n]}
    for mode in modes:
        if mode == "highest":
            continue
        low = cell.arch.served_logits(params, jnp.asarray(toks),
                                      jnp.asarray(len(prompt), jnp.int32),
                                      mode=mode, **kw)
        out[mode] = np.asarray(reference.gap_of(
            ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))[:n]
    return out


def logit_gap(params, cell, traffic, results, seed: int,
              modes=("highest",)) -> tuple[dict, list]:
    """({"served": widest gap of the served tokens, and one entry per
    lower-precision mode: widest gap of its choice}, sampled rids)."""
    by_rid = {it.rid: it for it in traffic.window}
    picked = sample(results, int(cell.mix["check_requests"]), seed)
    t_pad = padded_length(cell, traffic.window)
    widest = {"served": float("inf")} if not picked else {}
    for r in picked:
        g = gaps(params, cell, by_rid[r.rid].tokens, r.tokens, t_pad, modes)
        for k, v in g.items():
            widest[k] = max(widest.get(k, 0.0), float(np.max(v)))
    return widest, [r.rid for r in picked]
