"""Serving cells: build the family's model and the ContinuousBatchingEngine
from a configuration file, offer open-loop (timed from when each request
was due) or closed-loop load on a real clock from one thread, and compare a
sample of what was served with the family's plain reference afterwards."""
from __future__ import annotations

import gc
import statistics
import sys
import time

import numpy as np

from . import families, traffic


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build_engine(cfg, seed):
    """The family's model with the seed's weights in it, and the engine at
    the deployment's geometry, warmed on every shape the traffic can use."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    model = families.of(cfg).serving_model(cfg, seed)
    engine = ContinuousBatchingEngine(model, **cfg["deployment"]["engine"])
    # the engine packed its own stacked copy: drop the per-layer one
    for _, p in model.named_parameters():
        p._data = None
    gc.collect()
    engine.warmup()
    warm_first_token_shapes(engine)
    return engine


def warm_first_token_shapes(engine):
    """The engine takes first tokens from as many rows as finished their
    prefill in one tick, an eager program per row count: run each count
    once. k short prompts finish together in one tick, then are cancelled."""
    for k in range(1, engine.max_slots + 1):
        rids = [engine.submit([1 + i, 2, 3]) for i in range(k)]
        engine.step()
        for rid in rids:
            engine.cancel(rid)
        engine.step()
    engine.cancelled.clear()


class Load:
    """One thread drives submit and step and keeps every token's time."""

    def __init__(self, engine, spans):
        self.engine, self.spans = engine, spans
        self.n_new = engine.max_new_tokens
        self.req = {}      # rid -> {"due", "prompt", "times", "tokens"}
        self.done = []     # rids in completion order
        self.late = []     # how late each submit ran against its due time
        self.ticks = []    # (t_start, decode rows, sum of their lengths)

    def _on_token(self, rid, tok):
        r = self.req[rid]
        r["times"].append(time.perf_counter())
        r["tokens"].append(int(tok))

    def submit(self, prompt, due):
        with self.spans.span("bench.submit"):
            rid = self.engine.submit(prompt, on_token=self._on_token)
        self.req[rid] = {"due": due, "prompt": prompt, "times": [],
                         "tokens": []}
        self.late.append(time.perf_counter() - due)
        return rid

    def tick(self):
        live = [r for r in self.req.values()
                if r["tokens"] and len(r["tokens"]) < self.n_new
                and not r.get("gone")]
        self.ticks.append((time.perf_counter(), len(live), sum(
            len(r["prompt"]) + len(r["tokens"]) for r in live)))
        with self.spans.span("bench.tick"):
            finished = self.engine.step()
        for rid in finished:
            self.req[rid]["gone"] = True
            self.done.append(rid)
        return finished

    def active(self):
        return any(not r.get("gone") for r in self.req.values())

    def finished(self, t0, t1):
        """[(prompt, tokens)] of requests whose every token came by t1."""
        return [(r["prompt"], r["tokens"]) for r in
                (self.req[rid] for rid in self.done)
                if len(r["tokens"]) == self.n_new and r["times"][-1] < t1]

    def cancel_rest(self):
        for rid, r in self.req.items():
            if not r.get("gone"):
                self.engine.cancel(rid)
                r["gone"] = True
        self.engine.step()


def run_open(load, mix, args, env, vocab):
    """Open loop: requests are due on a schedule whether or not earlier
    ones have finished; arrivals start ramp_s before the window."""
    reqs = traffic.open_requests(mix, args.seed, args.seconds, vocab)
    plan0 = time.perf_counter() + mix["ramp_s"]  # due times count from here
    i, t0 = 0, None
    while True:
        now = time.perf_counter()
        if t0 is None and now >= plan0:
            env["start_window"]()
            t0 = now = time.perf_counter()
        if t0 is not None and now - t0 >= args.seconds:
            return t0, now
        while i < len(reqs) and plan0 + reqs[i][0] <= now:
            load.submit(reqs[i][1], plan0 + reqs[i][0])
            i += 1
        if load.active():
            load.tick()
        else:
            edge = plan0 if t0 is None else t0 + args.seconds
            nxt = plan0 + reqs[i][0] if i < len(reqs) else edge
            with load.spans.span("bench.wait"):
                time.sleep(max(0.0, min(nxt, edge) - now))


def run_closed(load, mix, args, env, vocab):
    """Closed loop: each client sends its next request when its last one
    completed. ``warm_ticks`` ticks before the window fill every slot."""
    prompts = traffic.closed_prompts(mix, args.seed, vocab)
    for _ in range(mix["clients"]):
        load.submit(next(prompts), time.perf_counter())
    t0 = None
    while True:
        if t0 is None and len(load.ticks) >= mix["warm_ticks"]:
            env["start_window"]()
            t0 = time.perf_counter()
        if t0 is not None and time.perf_counter() - t0 >= args.seconds:
            return t0, time.perf_counter()
        for _ in load.tick():
            load.submit(next(prompts), time.perf_counter())


def window_metrics(load, t0, t1, cfg, counters):
    """End-to-end numbers over every request and token of the window, and
    the work done in it from the harness's own bookkeeping."""
    ttft, gaps, out_tokens, attempted, positions = [], [], 0, 0, []
    for r in load.req.values():
        ts, m = r["times"], len(r["prompt"])
        attempted += t0 <= r["due"] < t1
        if ts and t0 <= ts[0] < t1:
            ttft.append(ts[0] - r["due"])
            positions += range(m)  # its prefill ended in the window
        gaps += [b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1]
        out_tokens += sum(t0 <= t < t1 for t in ts)
        positions += [m + j for j, t in enumerate(ts[1:]) if t0 <= t < t1]
    e2e = {"serve_output_tokens_per_s": out_tokens / (t1 - t0)}
    if ttft:
        e2e["serve_ttft_mean_ms"] = 1e3 * statistics.fmean(ttft)
    if len(gaps) >= 10:
        e2e["serve_itl_p90_ms"] = 1e3 * float(np.quantile(gaps, 0.9))
    log(f"window: {attempted} requests due, {len(ttft)} first tokens, "
        f"{out_tokens} tokens, {len(gaps)} gaps; generator late by mean "
        f"{1e3 * statistics.fmean(load.late):.3f} ms, max "
        f"{1e3 * max(load.late):.3f} ms")
    in_win = [(n, s) for t, n, s in load.ticks if t0 <= t < t1]
    eng = cfg["deployment"]["engine"]
    rows, toks = max(in_win, key=lambda ns: ns[1], default=(0, 0))
    log(f"K/V filled at most: {toks} tokens in {rows} decoding rows, "
        f"{-(-toks // eng['page_size'])}-{toks // eng['page_size'] + rows} "
        f"of the pool's {eng.get('num_pages')} pages")
    family = families.of(cfg)
    counters["model_flops"] = float(family.serve_flops(cfg, positions))
    # the cache rows of every live row's length, in every layer
    counters["paged_kv_bytes"] = float(
        sum(s for _, s in in_win) * family.cache_bytes_per_token(cfg))
    return e2e, int(attempted)


_GAP_FNS = {}


def reference_gaps(forward_logits, weights, prompt, served, cfg, mode,
                   control):
    """For each served token, how far its reference logit lies below the
    reference's best at that position; with ``control`` (a lower-precision
    mode) the token judged is the one that mode puts first. The sequence
    is padded on the right to a multiple of 256 so that few lengths
    compile (causal: padding changes no earlier position)."""
    import jax
    import jax.numpy as jnp

    ids = list(prompt) + list(served[:-1])
    t = len(ids)
    pad = -t % 256
    key = (forward_logits, t + pad, mode, control)
    if key not in _GAP_FNS:
        _GAP_FNS[key] = jax.jit(lambda w, ids: (
            forward_logits(w, ids, cfg, mode),
            forward_logits(w, ids, cfg, control) if control else None))
    ref, low = _GAP_FNS[key](weights,
                             jnp.asarray(ids + [0] * pad, jnp.int32))
    sl = slice(len(prompt) - 1, t)
    toks = (jnp.argmax(low[sl], -1) if control
            else jnp.asarray(served, jnp.int32))
    got = jnp.take_along_axis(ref[sl], toks[:, None], -1)[:, 0]
    return np.asarray(jnp.max(ref[sl], -1) - got)


def check_served(finished, cfg, seed, sample, mode="f32", control=None):
    """The widest and the mean gap over a seeded sample of the requests
    the window finished ([(prompt, served tokens)]), the longest in it."""
    import jax.numpy as jnp

    if not finished:
        return {"served_compared": 0.0}
    order = np.random.default_rng(int(seed)).permutation(len(finished))
    longest = max(range(len(finished)), key=lambda i: len(finished[i][0]))
    pick = [longest] + [i for i in order if i != longest][:sample - 1]
    family = families.of(cfg)
    weights = family.make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    gaps = np.concatenate([reference_gaps(
        family.forward_logits, weights, *finished[i], cfg, mode, control)
        for i in pick])
    return {"served_compared": float(len(gaps)),
            "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}


def run(cell, args, env):
    cfg, mix = cell["config"], cell["mix"]
    engine = build_engine(cfg, args.seed)
    load = Load(engine, env["spans"])
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        loop = run_open if mix["kind"] == "serve-open" else run_closed
        t0, t1 = loop(load, mix, args, env, cfg["vocab_size"])
    finally:
        gc.enable()
        gc.unfreeze()
    env["stop_window"](t0, t1)
    failed = len(engine.cancelled)  # the engine's own, before ours
    finished = load.finished(t0, t1)
    load.cancel_rest()
    counters = {}
    e2e, attempted = window_metrics(load, t0, t1, cfg, counters)
    load.engine = engine = None  # the reference takes the chip
    gc.collect()
    numbers = check_served(finished, cfg, args.seed, mix["check_requests"])
    return {"e2e": e2e, "counters": counters, "numbers": numbers,
            "attempted": attempted, "failed": failed, "t0": t0, "t1": t1}
