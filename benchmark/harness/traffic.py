"""The one general traffic generator. A mix is a data file of parameters
(benchmark/traffic/<mix>.json). Sizes and arrival gaps are the quantiles
of the mix's distributions, a fixed set, so that no seed changes the
amount of work. What --seed draws: training batches, token ids (and the
weights), and the order of a closed loop's prompts. An open loop's
schedule is NOT drawn from --seed: every seed sends the one order that the
mix's own ``schedule_seed`` draws (see open_requests)."""
from __future__ import annotations

import math
import statistics

import numpy as np


# ----------------------------------------------------------------- training
def train_batches(mix, seed, batch, vocab):
    """``n_batches`` distinct batches on the device in one jitted call:
    ids [n, B, S] int32 and next-token labels [n, B, S] int64 (the last
    label of a row is drawn too). All rows differ."""
    import jax
    import jax.numpy as jnp

    from .reference import seed_key

    n, seq = mix["n_batches"], mix["seq"]

    @jax.jit
    def make(key):
        toks = jax.random.randint(key, (n, batch, seq + 1), 0, vocab,
                                  jnp.int32)
        return toks[..., :-1], toks[..., 1:]

    ids, labels = make(jax.random.fold_in(seed_key(seed), 0x7ea1))
    return ids, labels


# ------------------------------------------------------------------ serving
def _stratified(n, inv_cdf):
    return [inv_cdf((i + 0.5) / n) for i in range(n)]


def prompt_lengths(mix, n):
    """n lengths: the quantiles of the mix's distribution, unshuffled."""
    p = mix["prompt"]
    if p["dist"] == "fixed":
        return [int(p["length"])] * n
    if p["dist"] == "lognormal":
        nd = statistics.NormalDist(math.log(p["median"]), p["sigma"])
        return [int(min(max(round(math.exp(x)), p["min"]), p["max"]))
                for x in _stratified(n, nd.inv_cdf)]
    raise ValueError(f"unknown prompt dist {p['dist']!r}")


def arrival_gaps(mix, n):
    """n gaps between arrivals with mean 1/rate, unshuffled: the quantiles
    of a Poisson process's exponential gaps, not draws from it."""
    a = mix["arrivals"]
    if a["process"] == "exponential-quantiles":
        return _stratified(n, lambda u: -math.log(1.0 - u) / a["rate"])
    raise ValueError(f"unknown arrival process {a['process']!r}")


def open_requests(mix, seed, seconds, vocab):
    """[(due_s, prompt)] over the ramp [-ramp_s, 0) and the window
    [0, seconds), due times counted from the window's first instant. Each
    span gets its own fixed set of gaps (they sum to the span) and of
    lengths, in an order drawn from the mix's own ``schedule_seed``, not
    from ``seed``: with 32 slots nearly full and some 28 requests a
    window, the order decides who waits for a slot, and the mean time to
    first token moves by 10% from order to order (PERF.md section 2). So
    every seed sends the same frozen schedule, and the seed draws the
    tokens (and the weights) only."""
    order = np.random.default_rng(mix["schedule_seed"])
    rng = np.random.default_rng(int(seed))
    out = []
    for start, span in ((-mix["ramp_s"], mix["ramp_s"]), (0.0, seconds)):
        n = int(round(mix["arrivals"]["rate"] * span))
        if not n:
            continue
        gaps = order.permutation(arrival_gaps(mix, n))
        gaps = gaps * (span / gaps.sum())
        due = start + np.cumsum(gaps) - gaps  # each at its gap's start
        lens = order.permutation(prompt_lengths(mix, n))
        out += [(float(t), rng.integers(1, vocab, int(m)).tolist())
                for t, m in zip(due, lens)]
    return out


def closed_prompts(mix, seed, vocab):
    """An endless per-client stream of prompts for a closed loop."""
    rng = np.random.default_rng(int(seed))
    lens = prompt_lengths(mix, 64)
    while True:
        for m in rng.permutation(lens):
            yield rng.integers(1, vocab, int(m)).tolist()
