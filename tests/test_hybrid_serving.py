"""The gated-delta hybrid model behind the serving engine (ISSUE 37), at
tiny widths on the CPU, float32, seeded: a cache of two kinds (K/V by
page, the recurrent state and the convolutions' tails by slot), the
engine's habits repaired for a kind that keeps a state, the state's
step kernel in interpret mode, the delta rule's chunkwise form. The plain
reference is the benchmark's (harness/families/gated_delta_hybrid.py: no
cache, the recurrence a scan over positions)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hybrid_tiny import hybrid_model, tiny_cfg
from harness.families import gated_delta_hybrid as fam
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.gated_delta_hybrid import (GatedDeltaHybridServing,
                                                  chunk_delta_rule)
from paddle_tpu.ops.pallas import gated_delta as gd

# float32 on the CPU: the program and the reference differ by the order of
# float32 sums (the chunkwise form's triangular solve against a scan over
# positions, a paged softmax against a whole one): logits of size ~1 agree
# to a few 1e-6; 2e-4 leaves room and is far under what a state stepped
# twice, a stale tail or a decay left out gives (1e-2 and more)
TOL = 2e-4


def _engine(model, **kw):
    eng = dict(max_slots=3, page_size=8, max_seq_len=64, prefill_chunk=8,
               max_new_tokens=8)
    eng.update(kw)
    return ContinuousBatchingEngine(model, **eng)


def _ref_logits(cfg, seed, ids):
    w = fam.make_weights(cfg, seed, jnp.float32)
    return np.asarray(fam.forward_logits(w, jnp.asarray(ids), cfg))


def _alone(prompt, seed=0, **kw):
    """A request's truth: its generated tokens from a one-slot engine that
    serves nothing else."""
    eng = _engine(hybrid_model(seed), max_slots=1, **kw)
    rid = eng.submit(prompt)
    return eng.run_until_complete()[rid][len(prompt):]


def _streams(eng, prompts):
    seen = {}
    rids = [eng.submit(p, on_token=lambda r, t: seen.setdefault(
        r, []).append(t)) for p in prompts]
    return seen, rids


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, n).tolist() for n in sizes]


class TestAgainstTheReference:
    @pytest.mark.parametrize("chunk,block", [(8, 64), (16, 4)])
    def test_chunked_prefill_then_decode_equals_full_forward_in_logits(
            self, chunk, block, monkeypatch):
        """Two rows of ragged length, neither a multiple of the chunk (or
        of 64): the prompts go through the chunk program (one block a
        chunk, or four blocks chained through S), the state and the
        tails carried from pass to pass in the rows' slots, then every
        further token through the decode program, teacher-forced; the
        logits at each chunk's end and at every decoded position equal
        the reference's full forward."""
        monkeypatch.setattr(GatedDeltaHybridServing, "prefill_block", block)
        cfg = tiny_cfg()
        eng = _engine(hybrid_model(3), prefill_chunk=chunk)
        rng = np.random.default_rng(0)
        seqs = [rng.integers(1, 96, n).tolist() for n in (27, 33)]
        prompts = (13, 21)
        want = [_ref_logits(cfg, 3, s) for s in seqs]
        tables = jnp.asarray([[0, 1, 2, 3, 8, 0, 0, 0],
                              [4, 5, 6, 7, 9, 0, 0, 0]], jnp.int32)
        slots = jnp.asarray([2, 0], jnp.int32)   # not the rows' order
        w = eng._weights
        for start in range(0, max(prompts), chunk):
            nvalid = [max(0, min(chunk, p - start)) for p in prompts]
            ids = np.zeros((2, chunk), np.int32)
            for b in range(2):
                ids[b, :nvalid[b]] = seqs[b][start:start + nvalid[b]]
            row_slots = jnp.where(jnp.asarray(nvalid) > 0, slots,
                                  eng._trash_slot)
            last, stats, eng.cache = eng._prefill_jit(
                w, jnp.asarray(ids), jnp.full((2,), start, jnp.int32),
                jnp.asarray(nvalid, jnp.int32), tables, eng.cache,
                row_slots)
            got = np.asarray(last @ w["head"])
            for b in range(2):
                if nvalid[b]:
                    np.testing.assert_allclose(
                        got[b], want[b][start + nvalid[b] - 1], atol=TOL,
                        rtol=0)
            rows, launched, tokens, prefill = (
                int(v) // 6 for v in np.asarray(stats))
            assert (rows, launched, tokens, prefill) == (
                sum(n > 0 for n in nvalid), 2, sum(nvalid), 1)
        seen = []
        head = eng._head_logits
        eng._head_logits = lambda w, x: seen.append(head(w, x)) or seen[-1]
        lens = np.asarray(prompts, np.int32)
        zeros = jnp.zeros((2,), jnp.float32)
        for step in range(8):
            toks = jnp.asarray([seqs[b][lens[b]] for b in range(2)],
                               jnp.int32)
            out, eng.cache = eng._decode_step(
                w, toks, jnp.asarray(lens), tables, eng.cache, zeros,
                jnp.zeros((2,), jnp.int32), zeros + 1, jax.random.PRNGKey(0),
                eng._no_tick, jnp.full((2,), -1, jnp.int32), False, slots)
            got = np.asarray(seen[-1])
            for b in range(2):
                np.testing.assert_allclose(got[b], want[b][lens[b]],
                                           atol=TOL, rtol=0)
            # the tokens come back padded to max_slots, the counts after
            assert out.shape == (eng.max_slots + 4,)
            assert [int(v) // 6 for v in out[eng.max_slots:]] == [2, 2, 2, 0]
            lens = lens + 1

    def test_served_tokens_are_the_references_argmax(self):
        """Whole requests through step(), joining mid-flight: each served
        token is the reference's first choice at its position (gap 0 in
        float32), whether the host or the device carried it into the
        next tick."""
        cfg = tiny_cfg()
        eng = _engine(hybrid_model(5), max_new_tokens=10)
        prompts = _prompts(1, (5, 13, 19, 9))
        rids = [eng.submit(p) for p in prompts[:2]]
        done = {}
        for tick in range(200):
            if tick == 3:
                rids += [eng.submit(p) for p in prompts[2:]]
            done.update(eng.step())
            if tick > 3 and len(done) == 4:
                break
        assert eng.decode_ticks["ahead"] > 0
        for rid, p in zip(rids, prompts):
            ids = done[rid]
            ref = _ref_logits(cfg, 5, ids[:-1])
            gap = ref[len(p) - 1:].max(-1) - np.take_along_axis(
                ref[len(p) - 1:], np.asarray(ids[len(p):])[:, None], 1)[:, 0]
            assert gap.max() <= TOL, gap


# ------------------------------------------------------ the chunkwise form
def _rule_operands(rng, b, t, h, dk, dv):
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k, v = unit(f(b, t, h, dk)) * dk ** -0.5, unit(f(b, t, h, dk)), \
        f(b, t, h, dv)
    g = -jnp.asarray(rng.uniform(0.01, 3.0, (b, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (b, t, h)), jnp.float32)
    return q, k, v, g, beta, f(b, h, dk, dv)


def _token_scan(q, k, v, g, beta, s0):
    """The recurrence position by position, as the equation reads."""
    def step(s, at):
        qt, kt, vt, gt, bt = at
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    s, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


@pytest.mark.parametrize("block", [4, 8, 16])
def test_the_chunkwise_form_equals_the_token_scan(block):
    """In o and in S, from a non-zero state, one block or several chained
    (decays down to e^-3 a position, steps up to 2: negative
    eigenvalues). 2e-5: float32 sums in another order, values of size
    one."""
    args = _rule_operands(np.random.default_rng(0), 2, 16, 3, 8, 16)
    o, s = chunk_delta_rule(*args, block)
    want_o, want_s = _token_scan(*args)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)


def test_a_position_with_no_decay_and_no_step_leaves_the_state():
    """g = 0, beta = 0 over the tail of a chunk: S comes out as it was
    after the last real position, whatever q, k, v hold there; with
    nothing real at all it comes out as it went in, bitwise."""
    rng = np.random.default_rng(1)
    q, k, v, g, beta, s0 = _rule_operands(rng, 2, 8, 3, 8, 16)
    real = (jnp.arange(8) < 5)[None, :, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    _, s = chunk_delta_rule(q, k, v, g, beta, s0, 8)
    _, want = _token_scan(q[:, :5], k[:, :5], v[:, :5], g[:, :5],
                          beta[:, :5], s0)
    np.testing.assert_allclose(s, want, atol=2e-5, rtol=0)
    other = [jnp.where(real[..., None], a, 7.0 * a + 1.0) for a in (q, k, v)]
    _, again = chunk_delta_rule(*other, g, beta, s0, 8)
    assert np.array_equal(np.asarray(s), np.asarray(again))
    _, same = chunk_delta_rule(q, k, v, 0 * g, 0 * beta, s0, 8)
    assert np.array_equal(np.asarray(same), np.asarray(s0))


def test_positions_past_nvalid_change_neither_state_nor_tail_bitwise():
    """The chunk program on a row whose chunk is 5 real positions of 8:
    what the padding holds changes nothing in the row's slot, bitwise;
    a row with no chunk (nvalid 0, on the trash slot) and every other
    slot keep what they held."""
    stores = []
    for junk in (0, 55):
        eng = _engine(hybrid_model(2))
        held = [np.random.default_rng(9).standard_normal(c.shape).astype(
            c.dtype) for c in eng.cache[2:]]
        eng.cache = eng.cache[:2] + tuple(jnp.asarray(h) for h in held)
        ids = np.full((2, 8), junk, np.int32)
        ids[0, :5] = [7, 8, 9, 10, 11]
        _, _, cache = eng._prefill_jit(
            eng._weights, jnp.asarray(ids), jnp.asarray([8, 0], jnp.int32),
            jnp.asarray([5, 0], jnp.int32),
            jnp.asarray([[0, 1, 2, 0, 0, 0, 0, 0], [24] * 8], jnp.int32),
            eng.cache, jnp.asarray([1, eng._trash_slot], jnp.int32))
        stores.append([np.asarray(c) for c in cache[2:]])
        for new, old in zip(stores[-1], held):
            assert np.array_equal(new[:, [0, 2]], old[:, [0, 2]])
            assert not np.array_equal(new[:, 1], old[:, 1])
    for a, b in zip(*stores):
        assert np.array_equal(a[:, :3], b[:, :3])


# ------------------------------------------------------------- the kernel
@pytest.mark.parametrize("h,dk,dv", [(4, 8, 64), (4, 8, 16), (6, 16, 128)])
def test_gdn_decode_step_in_interpret_mode_equals_the_jnp_path(h, dk, dv):
    """Two heads to a lane row (value 64), one (16: the tile is padded),
    one of a whole tile; rows out of slot order, two of them padding on
    the trash slot: o and the stepped slots equal the jnp path's, and
    every slot no live row names, the trash slot among them, keeps what
    it held, bitwise."""
    g = gd.heads_per_lane_row(h, dv)
    assert g == {64: 2, 16: 1, 128: 1}[dv]
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    b, layers, n = 5, 3, 6
    q, k, v = f(b, h, dk), f(b, h, dk), f(b, h, dv)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    alpha = jnp.asarray(rng.uniform(0.1, 1, (b, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (b, h)), jnp.float32)
    store = f(layers, n + 1, h // g, dk, g * dv)
    slots = jnp.asarray([2, n, 0, n, 5], jnp.int32)
    live = np.asarray(slots) != n
    want_o, want = gd.gdn_decode_step_reference(q, k, v, alpha, beta, store,
                                                slots, 1, g)
    o, got = gd.gdn_decode_step(q, k, v, alpha, beta, store, slots, 1, g=g,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               atol=3e-6, rtol=0)
    got, want, store = (np.asarray(a) for a in (got, want, store))
    np.testing.assert_allclose(got[1, [2, 0, 5]], want[1, [2, 0, 5]],
                               atol=3e-6, rtol=0)
    untouched = np.ones(store.shape[:2], bool)
    untouched[1, [2, 0, 5]] = False
    assert np.array_equal(got[untouched], store[untouched])
    s = f(2, h, dk, dv)
    assert np.array_equal(gd.unpack_state(gd.pack_state(s, g), g), s)


@pytest.mark.parametrize("live", [1, 3, 4])
def test_a_row_does_not_depend_on_the_rows_beside_it(live, monkeypatch):
    """1, 3 and ``max_slots`` live rows, the state stepped by the kernel
    (interpret mode), which steps the rows of a batch one after another:
    a padded row that named a live row's slot, as the engine padded
    before ISSUE 37, would step that state twice. Every request serves
    what it serves alone."""
    monkeypatch.setattr(GatedDeltaHybridServing, "step_kernel", True)
    prompts = _prompts(11, (6, 11, 4, 9))[:live]
    want = [_alone(p, seed=4) for p in prompts]
    eng = _engine(hybrid_model(4), max_slots=4)
    rids = [eng.submit(p) for p in prompts]
    done = eng.run_until_complete()
    for rid, p, w in zip(rids, prompts, want):
        assert done[rid] == p + w


def test_the_old_padding_would_have_stepped_a_state_twice(monkeypatch):
    """The fault the pad row cures, planted: pad rows that name the first
    live row's slot change what that row serves (under the kernel)."""
    monkeypatch.setattr(GatedDeltaHybridServing, "step_kernel", True)
    prompt = _prompts(12, (7,))[0]
    want = _alone(prompt, seed=4)
    vec = ContinuousBatchingEngine._slot_vec

    def copies(self, at, width):
        slots = vec(self, at, width)
        if len(at):
            slots[len(at):] = at[0]
        return slots

    monkeypatch.setattr(ContinuousBatchingEngine, "_slot_vec", copies)
    eng = _engine(hybrid_model(4), max_slots=4)
    eng._decode_rows = (4,)        # a lone row's tick padded to four
    rid = eng.submit(prompt)
    assert eng.run_until_complete()[rid] != prompt + want


# ------------------------------------------------- the engine's other paths
class TestTheEngine:
    def test_a_slot_taken_again_after_a_row_ended_in_flight(self):
        """A row ends by eos, which the host learns one tick late: the
        row rides in the tick launched ahead and steps its slot's state
        once more. The request admitted to that slot next serves what a
        fresh engine serves: its first chunk starts from zero whatever
        the slot holds, in the device's program order."""
        first, other, later = _prompts(21, (6, 9, 12))
        kw = dict(max_new_tokens=10)
        eos = _alone(first, seed=6, **kw)[3]
        kw["eos_token_id"] = eos
        want = {i: _alone(p, seed=6, **kw)
                for i, p in enumerate((first, other, later))}
        assert len(want[0]) == 4 and want[0][-1] == eos
        eng = _engine(hybrid_model(6), max_slots=2, **kw)
        seen, rids = _streams(eng, (first, other, later))
        done = eng.run_until_complete()
        assert eng.discarded_tokens["ended"] >= 1
        for i, (rid, p) in enumerate(zip(rids, (first, other, later))):
            assert seen[rid] == want[i] and done[rid] == p + want[i]

    @pytest.mark.parametrize("policy", ("recompute", "swap"))
    def test_preemption_loses_and_repeats_nothing(self, policy):
        """A pool one page short of what the rows grow into: the victim's
        pages AND its slot's state and tails go (recompute: rebuilt from
        position 0; swap: snapshotted with the pages and restored into
        whatever slot it is given next)."""
        prompts = _prompts(41, (7, 7, 7))
        kw = dict(max_new_tokens=12, page_size=4, max_seq_len=32)
        want = [_alone(p, seed=7, **kw) for p in prompts]
        eng = _engine(hybrid_model(7), num_pages=14, preempt_policy=policy,
                      **kw)
        seen, rids = _streams(eng, prompts)
        done = eng.run_until_complete()
        assert eng.preemptions > 0
        assert (eng.swaps_in > 0) == (policy == "swap")
        for rid, p, w in zip(rids, prompts, want):
            assert seen[rid] == w and done[rid] == p + w
        assert eng.pool.available == eng.pool.num_pages

    def test_a_swap_snapshot_holds_the_slots_state(self):
        eng = _engine(hybrid_model(8), preempt_policy="swap")
        rid = eng.submit(_prompts(5, (11,))[0])
        for _ in range(4):
            eng.step()
        slot = next(i for i, r in enumerate(eng._slots) if r is not None)
        snap = eng._snapshot_to_host(eng._slots[slot])
        assert set(snap) >= {"k", "v", "state", "conv", "n"}
        for name in ("state", "conv"):
            pool = np.asarray(eng._pool(name))
            assert np.array_equal(snap[name], pool[:, slot])
            assert snap[name].shape == pool.shape[:1] + pool.shape[2:]

    @pytest.mark.parametrize("feature,kw", [
        ("int8_kv", dict(int8_kv=True)),
        ("int8_weights", dict(int8_weights=True)),
        ("draft_model", dict(draft_model=object())),
        ("group prefill", dict(prefill_chunk=None)),
        ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ])
    def test_a_refused_feature_raises_by_name(self, feature, kw):
        with pytest.raises(ValueError, match=feature):
            _engine(hybrid_model(0), **kw)

    def test_handoff_raises_naming_the_state_store(self):
        eng = _engine(hybrid_model(0))
        eng.submit([1, 2, 3])
        eng.step()
        with pytest.raises(NotImplementedError, match="state store"):
            eng.extract(0)
        with pytest.raises(NotImplementedError, match="state store"):
            eng.inject(eng._slots[0])
        with pytest.raises(AttributeError, match="latent"):
            eng._pool("latent")

    def test_the_dense_kind_pads_with_the_trash_row_and_no_slot_operand(
            self):
        from test_serving import _tiny_model

        eng = ContinuousBatchingEngine(_tiny_model(), max_slots=2,
                                       page_size=8, max_seq_len=32,
                                       prefill_chunk=8, max_new_tokens=4)
        pad = eng._pad_row
        assert pad.length == 0 and set(pad.pages) == {eng._trash_page}
        assert eng._slot_vec([0], 2) is None
        assert len(eng.cache) == 2 and eng._n_paged == 2

    def test_counts_reach_the_spans_the_counter_and_the_gauges(self):
        import paddle_tpu.telemetry as telemetry
        from paddle_tpu.telemetry import trace

        telemetry.enable()
        trace.enable()
        trace.reset()
        before = sum(telemetry.snapshot()["counters"].get(
            "serving_state_steps_total", {}).values())
        try:
            eng = _engine(hybrid_model(9), max_new_tokens=6)
            eng.warmup()
            prompts = _prompts(3, (13, 5))
            for p in prompts:
                eng.submit(p)
            eng.run_until_complete()
        finally:
            events = trace.events()
            trace.disable()
        spans = lambda name: [e["attrs"] for e in events
                              if e.get("ph") == "X" and e["name"] == name
                              and "state_rows" in (e.get("attrs") or {})]
        decode, prefill = spans("decode_tick"), spans("prefill_tick")
        assert decode and prefill
        # 3 slots, a ladder of (1, 3): two live rows and a padded one a
        # tick, then one alone
        assert {(t["state_rows"], t["state_rows_launched"])
                for t in decode} == {(2, 3), (1, 1)}
        assert {(t["live"], t["rows"]) for t in decode} == {(2, 3), (1, 1)}
        assert sum(t["state_rows"] for t in decode) == 2 * 5
        assert sum(t["state_tokens"] for t in prefill) == 13 + 5
        assert [t["state_rows"] for t in prefill] == [2, 1]
        snap = telemetry.snapshot()
        steps = snap["counters"]["serving_state_steps_total"]
        assert sum(steps.values()) - before >= 13 + 5 + 2 * 5
        assert len(steps) == 2                      # decode and prefill
        kinds = snap["gauges"]["serving_cache_bytes"]
        assert len(kinds) == 4
        cfg = eng._arch.cfg
        store = sum(int(c.nbytes) for c in eng.cache[2:])
        assert store == 4 * 6 * (4 * 8 * 16 * 4 + 3 * 128 * 4)
        assert eng._arch.slot_cache_bytes(4) * 4 == store
        assert cfg.num_linear_layers == 6 and cfg.num_full_layers == 2
        assert set(eng.program_bytes) >= {"decode", "prefill",
                                          "prefill_r1", "prefill_r2"}


# ------------------------------------------------------------- the reader
def test_state_step_roofline_reads_the_ticks_rows():
    """The reader on synthetic ticks and a synthetic trace; without the
    attrs (a model that keeps no state, a parent commit), the ops or the
    family's arithmetic it reads nothing."""
    from harness import recurrent, roofline

    cfg = tiny_cfg()
    per_row = fam.state_bytes_per_row(cfg)
    assert per_row == 6 * 4 * 8 * 16 * 4
    peaks = roofline.peaks("TPU v5 lite")
    ticks = [("decode_tick", {"state_rows": 3, "state_rows_launched": 4}),
             ("decode_tick", {"state_rows": 2, "state_rows_launched": 4}),
             ("prefill_tick", {"state_rows": 2, "state_tokens": 16})]
    events = [{"ph": "X", "name": n, "ts": 1.0 + i, "dur": 0.5, "attrs": a}
              for i, (n, a) in enumerate(ticks)]
    events.append({"ph": "X", "name": "decode_tick", "ts": 9.8, "dur": 0.5,
                   "attrs": {"state_rows": 9}})   # ends past the window
    ctx = {"config": cfg, "peaks": peaks, "t0": 0.0, "t1": 10.0,
           "trace": {"ops": {"gdn_decode_step": [12, 2e-6],
                             "paged_attention": [4, 1.0]}},
           "program": {"events": events, "epoch": 0.0, "beats": []}}
    args = dict(patterns=["^gdn_decode_step"], spans=["decode_tick"])
    want = 100 * 2 * 5 * per_row / peaks["hbm_bytes_per_s"] / 2e-6
    assert recurrent.state_step_roofline(ctx, **args) == pytest.approx(want)
    assert recurrent.state_step_roofline(
        dict(ctx, trace={"ops": {"fusion": [1, 1.0]}}), **args) is None
    assert recurrent.state_step_roofline(dict(ctx, trace=None),
                                         **args) is None
    for e in events:
        e["attrs"] = {"live": 4}
    assert recurrent.state_step_roofline(ctx, **args) is None
    dense = {"family": "dense_gqa"}
    assert recurrent.state_step_roofline(dict(ctx, config=dense),
                                         **args) is None
    from harness import program

    share = dict(span="decode_tick", num="state_rows",
                 den="state_rows_launched")
    for e, (_, a) in zip(events, ticks):
        e["attrs"] = a
    assert program.span_attr_share(ctx, **share) == pytest.approx(
        100 * 5 / 8)
