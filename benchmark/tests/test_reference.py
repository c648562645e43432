"""The plain reference against the program at a tiny size on the CPU in
float32 (logits, loss, a gradient and three optimizer steps; MHA and GQA,
tied and untied head), and the open loop's clock: latencies count from
when a request was due."""
from __future__ import annotations

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))
sys.path.insert(0, tiny.REPO)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_training_steps_match_the_program(tmp_path, kv_heads):
    """Tied head (the stacked trainer has no other): each loss, the first
    gradient's norms and the change after three steps, factored AdamW."""
    from harness import train

    tiny.make_root(tmp_path, num_key_value_heads=kv_heads)
    import run

    _, cell = run.load_cell(str(tmp_path), "train-seq4096")
    cfg, mix = cell["config"], cell["mix"]
    tr = train.Trainer(cfg, mix, 1)
    tr.load_seed(11)
    prog = tr.first_steps(3)
    ref = train.reference_readings(cfg, mix, 11, tr.batch, 3)
    n = train.compare(prog, ref)
    assert max(n["loss1_gap"], n["loss2_gap"], n["loss3_gap"]) < 1e-6
    assert n["grad_norm_gap"] < 1e-4 and n["change_norm_gap"] < 1e-3
    # leaf by leaf, not only the worst one
    for leaf, want in ref["grad_sumsq"].items():
        assert prog["grad_sumsq"][leaf] == pytest.approx(want, rel=1e-3)


@pytest.mark.parametrize("kv_heads,tied", [(4, False), (2, False),
                                           (2, True)],
                         ids=["mha-untied", "gqa-untied", "gqa-tied"])
def test_logits_match_the_program(tmp_path, kv_heads, tied):
    """The serving decoder's full forward, the seed's weights loaded by
    the family, against the family's forward_logits on the same weights."""
    import jax.numpy as jnp

    from harness import families

    tiny.make_root(tmp_path, num_key_value_heads=kv_heads,
                   tie_word_embeddings=tied)
    import run

    _, cell = run.load_cell(str(tmp_path), "serve-decode-closed")
    cfg = cell["config"]
    family = families.of(cfg)
    model = family.serving_model(cfg, 5)
    w = family.make_weights(cfg, 5, jnp.float32)
    assert ("head" in w) == (not tied)
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], 24)
    got = np.asarray(model(jnp.asarray(ids[None], jnp.int32))._data)[0]
    want = np.asarray(family.forward_logits(
        w, jnp.asarray(ids, jnp.int32), cfg))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


class StalledEngine:
    """Emits each request's tokens over two steps; the first step after
    ``stall_at`` requests sleeps, as a stalled server would."""
    max_new_tokens = 2
    max_slots = 4

    def __init__(self, stall_s):
        self.stall_s, self.q, self.cancelled, self.n = stall_s, [], {}, 0

    def submit(self, prompt, on_token=None):
        self.n += 1
        self.q.append([self.n, on_token, 0])
        return self.n

    def step(self):
        if self.stall_s and self.n >= 2:
            time.sleep(self.stall_s)
            self.stall_s = 0
        done = {}
        for r in list(self.q):
            r[1](r[0], 7)
            r[2] += 1
            if r[2] == 2:
                self.q.remove(r)
                done[r[0]] = []
        return done

    def cancel(self, rid):
        return True


def test_open_loop_latency_counts_from_the_due_time():
    """A stall in the engine lengthens the time to first token of the
    requests that were due while it lasted."""
    from harness import serve, trace

    mix = {"kind": "serve-open", "ramp_s": 0.0, "schedule_seed": 1,
           "arrivals": {"process": "exponential-quantiles", "rate": 40.0},
           "prompt": {"dist": "fixed", "length": 4}}

    class Args:
        seed, seconds = 3, 1.0

    def ttfts(stall):
        load = serve.Load(StalledEngine(stall), trace.Spans())
        env = {"start_window": lambda: None}
        t0, t1 = serve.run_open(load, mix, Args, env, 100)
        return [r["times"][0] - r["due"] for r in load.req.values()
                if r["times"]], max(load.late)

    calm, late_calm = ttfts(0.0)
    stalled, late = ttfts(0.3)
    assert max(calm) < 0.1 and late_calm < 0.1
    # requests due during the stall waited for it: about ten of them
    assert sum(t > 0.1 for t in stalled) >= 5 and max(stalled) > 0.25
    assert late > 0.2  # and the generator says how late it ran
