"""Continuous-batching serving engine vs per-request generate.

The strongest possible check: staggered requests served through the
paged-cache engine must produce EXACTLY the greedy tokens that
LlamaForCausalLM.generate produces one request at a time.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ContinuousBatchingEngine, PagePool
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _tiny_model(seed=0):
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=128,
                      dropout=0.0)
    paddle.seed(seed)
    return LlamaForCausalLM(cfg)


#: the engine's model kinds (docs/SERVING.md): a feature's test runs on
#: both where the feature is not the dense decoder's alone
MODEL_KINDS = ("dense", "latent")


def _model_of(kind, seed=0):
    if kind == "dense":
        return _tiny_model(seed)
    from _latent_tiny import latent_model

    return latent_model(seed)


class TestPagePool:
    def test_alloc_free_cycle(self):
        p = PagePool(4)
        a = p.alloc(3)
        assert p.available == 1
        with pytest.raises(MemoryError):
            p.alloc(2)
        p.free(a)
        assert p.available == 4


class TestContinuousBatching:
    @pytest.mark.slow  # serving soak; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_matches_per_request_generate(self):
        model = _tiny_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 96, (n,)).tolist() for n in (5, 9, 3)]
        new_tokens = 6

        # reference: one request at a time through the dense-cache generate
        want = {}
        for i, pr in enumerate(prompts):
            out = model.generate(paddle.to_tensor(
                np.asarray([pr], np.int32)), max_new_tokens=new_tokens)
            want[i] = np.asarray(out.numpy())[0].tolist()

        eng = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                       max_seq_len=64,
                                       max_new_tokens=new_tokens)
        # staggered submission: two up front, the third mid-flight
        assert eng.submit(prompts[0]) == 0
        assert eng.submit(prompts[1]) == 1
        eng.step()
        eng.step()
        assert eng.submit(prompts[2]) == 2
        done = eng.run_until_complete()
        assert sorted(done) == [0, 1, 2]
        for rid, ids in done.items():
            assert ids == want[rid], (rid, ids, want[rid])

    def test_pages_recycled_across_requests(self):
        model = _tiny_model(1)
        # pool sized so the 3rd request NEEDS pages from a finished one
        eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                       max_seq_len=32, num_pages=2,
                                       max_new_tokens=4)
        rng = np.random.default_rng(1)
        for _ in range(3):
            eng.submit(rng.integers(1, 96, (6,)).tolist())
        done = eng.run_until_complete()
        assert len(done) == 3
        assert eng.pool.available == 2  # everything returned

    def test_eos_stops_early(self):
        """The engine stops at the FIRST eos occurrence in the greedy
        stream — including when eos lands on the prefill-completion
        token (the seed's off-by-one decoded once more past eos /
        past max_new before the retire check; ISSUE 12 fix)."""
        model = _tiny_model(2)
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 96, (4,)).tolist()
        ref = model.generate(paddle.to_tensor(
            np.asarray([prompt], np.int32)), max_new_tokens=8)
        ref_ids = np.asarray(ref.numpy())[0].tolist()
        gen = ref_ids[len(prompt):]
        eos = gen[2]                    # the 3rd generated token...
        first = gen.index(eos)          # ...which may occur earlier
        eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                       max_seq_len=32, max_new_tokens=8,
                                       eos_token_id=int(eos))
        eng.submit(prompt)
        done = eng.run_until_complete()
        out = done[0]
        assert out == prompt + gen[:first + 1]
        assert out[-1] == eos


def test_submit_rejects_oversized_requests():
    model = _tiny_model(3)
    eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                   max_seq_len=32, max_new_tokens=8)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(list(range(1, 30)))  # 29 + 8 > 32


@pytest.mark.slow  # serving soak; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
class TestBatchedPrefillAndSampling:
    """VERDICT r2 item 5: batched admission prefill, sampling, streaming."""

    def test_group_prefill_one_pass_and_faster(self):
        import time

        cfg = LlamaConfig(vocab_size=256, hidden_size=256, num_layers=4,
                          num_heads=8, num_kv_heads=4, max_seq_len=256,
                          dropout=0.0)
        paddle.seed(3)
        model = LlamaForCausalLM(cfg)

        rng = np.random.default_rng(1)

        def four_prompts():
            return [rng.integers(1, 256, (48,)).tolist() for _ in range(4)]

        def serve(eng, prompts):
            for p in prompts:
                eng.submit(p)
            done = {}
            while len(done) < len(prompts):
                done.update(eng.step())
            return done

        eng = ContinuousBatchingEngine(model, max_slots=4, page_size=16,
                                       max_seq_len=128, max_new_tokens=4)
        eng2 = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                        max_seq_len=128, max_new_tokens=4)
        # warm pass: compiles the decode step + eager prefill op cache
        serve(eng, four_prompts())
        serve(eng2, four_prompts())
        assert eng.prefill_batches == 1       # 4-slot: ONE admission group
        assert eng2.prefill_batches == 4      # 1-slot: one group per request

        # steady-state: 4-wide admission (one weight pass + shared decode
        # ticks) beats four sequential requests; best-of-2 guards against
        # scheduler noise on shared CI hosts
        def best_of(engine, n=2):
            best = float("inf")
            for _ in range(n):
                t0 = time.perf_counter()
                serve(engine, four_prompts())
                best = min(best, time.perf_counter() - t0)
            return best

        t_batched = best_of(eng)
        t_seq = best_of(eng2)
        assert t_batched < t_seq, (t_batched, t_seq)

    def test_sampling_distribution_and_greedy_default(self):
        model = _tiny_model(seed=5)
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 96, (6,)).tolist()

        # temperature 0 (default) stays exact-greedy and deterministic
        outs = set()
        for seed in (0, 1, 2):
            eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                           max_seq_len=64, max_new_tokens=8,
                                           seed=seed)
            eng.submit(prompt)
            outs.add(tuple(eng.run_until_complete()[0]))
        assert len(outs) == 1

        # temperature > 0 explores: different seeds give different strings
        outs = set()
        for seed in range(4):
            eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                           max_seq_len=64, max_new_tokens=8,
                                           seed=seed)
            eng.submit(prompt, temperature=1.0, top_k=50)
            outs.add(tuple(eng.run_until_complete()[0]))
        assert len(outs) > 1

        # top_k=1 degenerates to greedy regardless of temperature
        eng_g = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                         max_seq_len=64, max_new_tokens=8)
        eng_g.submit(prompt)
        want = eng_g.run_until_complete()[0]
        eng_k1 = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                          max_seq_len=64, max_new_tokens=8,
                                          seed=9)
        eng_k1.submit(prompt, temperature=1.0, top_k=1)
        assert eng_k1.run_until_complete()[0] == want

    def test_streaming_callback_order(self):
        model = _tiny_model(seed=7)
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, 96, (5,)).tolist()
        seen = []
        eng = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                       max_seq_len=64, max_new_tokens=5)
        rid = eng.submit(prompt, on_token=lambda r, t: seen.append((r, t)))
        done = eng.run_until_complete()
        gen = done[rid][len(prompt):]
        assert [t for _, t in seen] == gen
        assert all(r == rid for r, _ in seen)

    def test_reload_weights_takes_effect(self):
        model = _tiny_model(seed=11)
        rng = np.random.default_rng(4)
        prompt = rng.integers(1, 96, (5,)).tolist()
        eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                       max_seq_len=64, max_new_tokens=4)
        eng.submit(prompt)
        before = eng.run_until_complete()[0]

        # zero the lm path -> logits change -> different generation
        with paddle.no_grad():
            w = model.model.embed_tokens.weight
            w.set_value(paddle.to_tensor(
                rng.standard_normal(w.shape).astype(np.float32) * 0.5))
        eng.reload_weights()
        eng.submit(prompt)
        after = eng.run_until_complete()[1]
        assert before != after


def test_top_p_truncates_distribution():
    """top_p must actually filter: with a tiny nucleus the sampler may only
    ever emit the highest-probability tokens (code-review r3 finding)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.inference.serving import _sample_rows

    rng = np.random.RandomState(0)
    logits = jnp.asarray([[5.0, 4.9] + [0.0] * 62], jnp.float32)
    allowed = {0, 1}
    for seed in range(24):
        got = _sample_rows(jax, jnp, logits,
                           jnp.asarray([1.0], jnp.float32),
                           jnp.asarray([0], jnp.int32),
                           jnp.asarray([0.6], jnp.float32),
                           jax.random.PRNGKey(seed))
        assert int(got[0]) in allowed, int(got[0])
    # and with top_p=1.0 the tail is reachable (sanity that filtering off
    # actually widens the support)
    seen = set()
    for seed in range(64):
        got = _sample_rows(jax, jnp, logits,
                           jnp.asarray([3.0], jnp.float32),
                           jnp.asarray([0], jnp.int32),
                           jnp.asarray([1.0], jnp.float32),
                           jax.random.PRNGKey(seed))
        seen.add(int(got[0]))
    assert len(seen - allowed) > 0, seen


class TestChunkedPrefill:
    """Chunked prefill interleaved with decode (vLLM-style; reference
    slot: the serving stack's mixed prefill/decode scheduling over
    block_multihead_attention)."""

    @pytest.mark.slow  # serving soak; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_matches_unchunked_exactly(self):
        model = _tiny_model(seed=13)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 96, (n,)).tolist() for n in (19, 7, 26)]

        def serve(chunk):
            eng = ContinuousBatchingEngine(
                model, max_slots=2, page_size=16, max_seq_len=64,
                max_new_tokens=5, prefill_chunk=chunk)
            for p in prompts:
                eng.submit(p)
            return eng.run_until_complete()

        want = serve(None)           # whole-prompt admission prefill
        got = serve(8)               # 8-token chunks
        assert got == want

    def test_decode_continues_during_long_prefill(self):
        model = _tiny_model(seed=17)
        rng = np.random.default_rng(6)
        short = rng.integers(1, 96, (4,)).tolist()
        long = rng.integers(1, 96, (40,)).tolist()
        eng = ContinuousBatchingEngine(
            model, max_slots=2, page_size=16, max_seq_len=64,
            max_new_tokens=12, prefill_chunk=8)
        r_short = eng.submit(short)
        eng.step()                   # short fully prefilled (one chunk)
        assert len(eng._slots[0].generated) >= 1
        r_long = eng.submit(long)
        # while the 40-token prompt fills at 8 tokens/tick (5 ticks), the
        # short request must KEEP DECODING every tick
        grew = []
        for _ in range(5):
            before = len(eng._slots[0].generated)
            eng.step()
            grew.append(len(eng._slots[0].generated) - before)
        assert all(g == 1 for g in grew), grew
        long_req = eng._slots[1]
        assert long_req.rid == r_long
        assert long_req.prefill_pos == 40 and long_req.generated
        done = eng.run_until_complete()
        assert sorted(done) == [r_short, r_long]


def test_engine_rejects_bad_inputs():
    model = _tiny_model(19)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ContinuousBatchingEngine(model, prefill_chunk=0)
    eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                   max_seq_len=32, max_new_tokens=4)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([])


class TestDeadlinesAndCancel:
    """ISSUE 12 satellite: a stuck client must not hold pages forever."""

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_deadline_cancels_queued_and_running(self, kind):
        import paddle_tpu.telemetry as telemetry

        telemetry.enable()
        model = _model_of(kind)
        rng = np.random.default_rng(8)
        eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                       max_seq_len=64, max_new_tokens=8,
                                       prefill_chunk=4)
        # r0 fills the only slot; r1 waits queued with an expired
        # deadline; r0's own deadline expires once it is mid-stream
        r0 = eng.submit(rng.integers(1, 96, (6,)).tolist(),
                        deadline_seconds=0.05)
        r1 = eng.submit(rng.integers(1, 96, (6,)).tolist(),
                        deadline_seconds=0.0)
        eng.step()
        assert eng.cancelled.get(r1) == "deadline"
        import time as _t

        _t.sleep(0.06)
        eng.step()
        assert eng.cancelled.get(r0) == "deadline"
        # everything released: no slots, no pages, queue empty
        assert all(s is None for s in eng._slots)
        assert eng.pool.available == eng.pool.num_pages
        assert not eng._waiting
        snap = telemetry.snapshot()
        series = snap["counters"].get("serving_cancellations_total", {})
        assert any("deadline" in k for k in series), series

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_cancel_running_request_frees_pages(self, kind):
        model = _model_of(kind)
        rng = np.random.default_rng(9)
        # the latent model prefills by chunks only
        eng = ContinuousBatchingEngine(
            model, max_slots=2, page_size=16, max_seq_len=64,
            max_new_tokens=8, prefill_chunk=None if kind == "dense" else 8)
        keep = eng.submit(rng.integers(1, 96, (5,)).tolist())
        drop = eng.submit(rng.integers(1, 96, (7,)).tolist())
        eng.step()
        assert eng.cancel(drop)
        assert not eng.cancel(drop)            # already gone
        done = eng.run_until_complete()
        assert keep in done and drop not in done
        assert eng.cancelled == {drop: "user"}
        assert eng.pool.available == eng.pool.num_pages

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_deadline_on_finished_request_still_completes(self, kind):
        """A request whose FINAL token was already delivered must
        retire as a completion even if its deadline expires in the
        tick gap before the retire loop runs (code-review round 2: the
        sweep ran first and reported a fully-served request as
        cancelled)."""
        import time as _t

        model = _model_of(kind)
        rng = np.random.default_rng(12)
        eng = ContinuousBatchingEngine(model, max_slots=1, page_size=16,
                                       max_seq_len=64, max_new_tokens=1,
                                       prefill_chunk=8)
        rid = eng.submit(rng.integers(1, 96, (5,)).tolist(),
                         deadline_seconds=0.05)
        eng.step()                       # prefill completes: all tokens out
        _t.sleep(0.06)                   # deadline expires post-delivery
        done = eng.step()
        assert rid in done and rid not in eng.cancelled

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_cancelled_prefix_pages_still_register(self, kind):
        """A cancelled request's COMPLETED prefix pages hold valid KV —
        they register into the prefix cache and a follow-up request
        reuses them."""
        model = _model_of(kind)
        system = list(range(1, 13))            # 3 full pages @4
        eng = ContinuousBatchingEngine(model, max_slots=1, page_size=4,
                                       max_seq_len=48, max_new_tokens=6,
                                       prefill_chunk=4,
                                       enable_prefix_cache=True)
        rid = eng.submit(system + [20, 21])
        for _ in range(3):                     # part-way through prefill
            eng.step()
        eng.cancel(rid)
        eng.submit(system + [30, 31])
        eng.run_until_complete()
        assert eng.prefix_cache_hits > 0


def _ahead_engine(kind, seed=0, **kw):
    """A small engine of either model kind (the latent kind prefills by
    chunks only, so both do here unless a test says otherwise)."""
    args = dict(max_slots=3, page_size=8, max_seq_len=64, prefill_chunk=8,
                max_new_tokens=6)
    args.update(kw)
    return ContinuousBatchingEngine(_model_of(kind, seed), **args)


def _alone(kind, prompt, seed=0, **kw):
    """What the tests here take as a request's truth: its generated
    tokens from a one-slot engine that serves nothing else."""
    eng = _ahead_engine(kind, seed, max_slots=1, **kw)
    rid = eng.submit(prompt)
    return eng.run_until_complete()[rid][len(prompt):]


def _streams(eng, prompts, **kw):
    """Submit ``prompts`` with a recording ``on_token``: ({rid: [tokens]},
    [rids])."""
    seen = {}
    rids = [eng.submit(p, on_token=lambda r, t: seen.setdefault(
        r, []).append(t), **kw) for p in prompts]
    return seen, rids


def _step_until_in_flight(eng, rids, max_steps=50):
    """Step until a decode tick that carries every one of ``rids`` has
    been launched and not fetched."""
    for _ in range(max_steps):
        eng.step()
        t = eng._in_flight
        if t is not None and {r.rid for _, r in t.live} >= set(rids):
            return
    raise AssertionError("no tick in flight carried " + repr(rids))


class TestLaunchAhead:
    """ISSUE 36: tick N+1 is launched from the device's own next tokens
    before tick N's are fetched (docs/SERVING.md "The step's order").
    Every request still gets the tokens it gets when served alone."""

    def _prompts(self, seed=36, sizes=(5, 11, 3, 9)):
        rng = np.random.default_rng(seed)
        return [rng.integers(1, 96, (n,)).tolist() for n in sizes]

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_staggered_arrivals_join_a_running_batch(self, kind):
        prompts = self._prompts()
        eng = _ahead_engine(kind)
        seen, rids = _streams(eng, prompts[:2])
        eng.step()
        eng.step()
        eng.step()
        more, rid2 = _streams(eng, prompts[2:3])   # joins mid-flight
        rids += rid2
        eng.step()
        last, rid3 = _streams(eng, prompts[3:])    # waits for a slot
        rids += rid3
        done = eng.run_until_complete()
        seen.update(more)
        seen.update(last)
        for rid, p in zip(rids, prompts):
            want = _alone(kind, p)
            assert done[rid] == p + want, (rid, done[rid], want)
            assert seen[rid] == want          # streamed once, in order
        assert eng.decode_ticks["ahead"] > eng.decode_ticks["settled"] >= 1
        assert eng.discarded_tokens == {"ended": 0, "withdrawn": 0}

    def test_dense_tokens_are_generates(self):
        """The same staggered batch against the model's own greedy
        ``generate``, under group prefill."""
        model = _tiny_model()
        prompts = self._prompts(sizes=(5, 9, 3))
        eng = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                       max_seq_len=64, max_new_tokens=6)
        rids = [eng.submit(p) for p in prompts[:2]]
        eng.step()
        eng.step()
        rids.append(eng.submit(prompts[2]))
        done = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            out = model.generate(paddle.to_tensor(
                np.asarray([p], np.int32)), max_new_tokens=6)
            assert done[rid] == np.asarray(out.numpy())[0].tolist()
        assert eng.decode_ticks["ahead"] > 0

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_max_new_reached_in_flight_emits_exactly_max_new(self, kind):
        """A row's last tick is known by count when it is launched: the
        row is left out of the next launch, not cut after the fact."""
        prompts = self._prompts(seed=37, sizes=(4, 13, 7))
        eng = _ahead_engine(kind, max_new_tokens=5)
        seen, rids = _streams(eng, prompts[:1])
        eng.step()
        eng.step()
        more, rids2 = _streams(eng, prompts[1:])   # these end later
        done = eng.run_until_complete()
        seen.update(more)
        for rid, p in zip(rids + rids2, prompts):
            assert len(seen[rid]) == 5 and done[rid] == p + seen[rid]
            assert seen[rid] == _alone(kind, p, max_new_tokens=5)
        assert eng.discarded_tokens == {"ended": 0, "withdrawn": 0}
        assert eng.pool.available == eng.pool.num_pages

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_eos_overshoot_is_discarded_and_pages_release_once(self, kind):
        """Whether a row ended at tick N-1 is not known when N is
        launched: the row rides in N, N's token for it is discarded,
        and it retires a step later. With the prefix cache on, a second
        release of a page would raise (refcount underflow)."""
        prompts = self._prompts(seed=38, sizes=(6, 10))
        full = _alone(kind, prompts[0], max_new_tokens=8)
        eos = full[3]
        first = full.index(eos)
        eng = _ahead_engine(kind, max_new_tokens=8, eos_token_id=int(eos),
                            enable_prefix_cache=True)
        seen, rids = _streams(eng, prompts)
        done = eng.run_until_complete()
        assert seen[rids[0]] == full[:first + 1]       # nothing past eos
        assert done[rids[0]] == prompts[0] + full[:first + 1]
        other = _alone(kind, prompts[1], max_new_tokens=8,
                       eos_token_id=int(eos))
        assert seen[rids[1]] == other
        if first > 0:                    # eos came out of a decode tick
            assert eng.discarded_tokens["ended"] >= 1
        assert eng.discarded_tokens["withdrawn"] == 0
        assert all(s is None for s in eng._slots)
        assert all(v == 0 for v in eng._page_ref.values())
        cached = len(eng._cached_pages)
        assert eng.pool.available == eng.pool.num_pages - cached

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_cancel_with_a_tick_in_flight(self, kind):
        """The cancelled row's token of the tick in flight is dropped at
        the fetch (nothing is waited for); its neighbour loses nothing;
        the same rid, replayed at once as a fleet does, starts clean."""
        prompts = self._prompts(seed=39, sizes=(7, 5))
        eng = _ahead_engine(kind, max_new_tokens=8)
        seen, (keep, drop) = _streams(eng, prompts)
        _step_until_in_flight(eng, [keep, drop])
        had = list(seen[drop])
        assert eng.cancel(drop)
        assert eng._in_flight is not None             # nothing settled
        replay, _ = _streams(eng, prompts[1:], rid=drop)
        done = eng.run_until_complete()
        want = _alone(kind, prompts[1], max_new_tokens=8)
        # the token in flight at the cancel was never emitted ...
        assert seen[drop] == had == want[:len(had)]
        # ... and the replay owes nothing to the old request's row
        assert replay[drop] == want
        assert done[drop] == prompts[1] + want
        assert seen[keep] == _alone(kind, prompts[0], max_new_tokens=8)
        assert eng.discarded_tokens == {"ended": 0, "withdrawn": 1}
        assert eng.pool.available == eng.pool.num_pages

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_extract_inject_with_a_tick_in_flight(self, kind):
        """``extract`` settles the tick in flight first: the request
        leaves with every token the device made for it, and the engine
        it is injected into goes on from there."""
        prompts = self._prompts(seed=40, sizes=(6, 9))
        src = _ahead_engine(kind, max_new_tokens=8)
        dst = _ahead_engine(kind, max_new_tokens=8)
        seen, (moved, stays) = _streams(src, prompts)
        _step_until_in_flight(src, [moved, stays])
        before = len(seen[moved])
        slot = next(i for i, r in enumerate(src._slots)
                    if r is not None and r.rid == moved)
        req = src.extract(slot)
        assert src._in_flight is None
        assert len(seen[moved]) == before + 1          # settled, emitted
        assert req.length == len(prompts[0]) + len(req.generated) - 1
        dst.inject(req)
        done = {**src.run_until_complete(), **dst.run_until_complete()}
        for rid, p in ((moved, prompts[0]), (stays, prompts[1])):
            want = _alone(kind, p, max_new_tokens=8)
            assert seen[rid] == want and done[rid] == p + want
        assert src.discarded_tokens == {"ended": 0, "withdrawn": 0}

    @pytest.mark.parametrize("policy", ("recompute", "swap"))
    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_forced_preemption_with_a_tick_in_flight(self, kind, policy):
        """A pool one page short of what the rows grow into: a victim is
        chosen while a tick is in flight, which is settled first, so the
        victim's token of that tick is neither lost nor served twice."""
        prompts = self._prompts(seed=41, sizes=(7, 7, 7))
        kw = dict(max_new_tokens=12, page_size=4, max_seq_len=32)
        want = [_alone(kind, p, **kw) for p in prompts]
        # each row ends with 7 + 11 tokens cached, on 5 pages: 15 in all
        eng = _ahead_engine(kind, num_pages=14, preempt_policy=policy, **kw)
        in_flight, grow = [], eng._grow_pages

        def watched(newly):
            was, n = eng._in_flight is not None, eng.preemptions
            grow(newly)
            if eng.preemptions > n:
                in_flight.append(was)
                assert eng._in_flight is None

        eng._grow_pages = watched
        seen, rids = _streams(eng, prompts)
        done = eng.run_until_complete()
        assert eng.preemptions > 0 and any(in_flight)
        for rid, p, w in zip(rids, prompts, want):
            assert seen[rid] == w and done[rid] == p + w
        assert eng.pool.available == eng.pool.num_pages

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_sampled_engine_equals_a_settled_every_tick_run(self, kind):
        """A sampled tick is launched ahead like a greedy one: its key
        is split on the host, in launch order, and depends on no
        token."""
        prompts = self._prompts(seed=42, sizes=(5, 8, 4))
        outs = []
        for settle in (False, True):
            eng = _ahead_engine(kind, seed=3, max_new_tokens=7)
            seen, rids = _streams(eng, prompts[:2], temperature=0.9,
                                  top_k=20)
            for tick in range(200):
                if tick == 2:
                    more, rid2 = _streams(eng, prompts[2:],
                                          temperature=0.7, top_p=0.9)
                eng.step()
                if settle:
                    eng._settle()
                if tick > 2 and not eng._waiting and all(
                        s is None for s in eng._slots):
                    break
            seen.update(more)
            assert eng.decode_ticks["ahead"] == 0 if settle else \
                eng.decode_ticks["ahead"] > 0
            outs.append([seen[r] for r in rids + rid2])
        assert outs[0] == outs[1]
        assert all(len(t) == 7 for t in outs[0])

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_an_idle_engine_holds_no_token_back(self, kind):
        """The step that finds nothing to launch fetches what is in
        flight, emits it and retires what it finished, all in that one
        call."""
        prompts = self._prompts(seed=43, sizes=(6, 6))
        eng = _ahead_engine(kind, max_new_tokens=4)
        seen, rids = _streams(eng, prompts)
        done = {}
        while not done:
            assert sum(map(len, seen.values())) < 8 or eng._in_flight
            done = eng.step()
        assert sorted(done) == sorted(rids)            # both, in one call
        assert [len(seen[r]) for r in rids] == [4, 4]
        assert eng._in_flight is None
        assert all(s is None for s in eng._slots)
        assert eng.step() == {}                        # and stays idle

    def test_counter_reads_ahead_for_every_tick_but_the_first(self):
        import paddle_tpu.telemetry as telemetry
        from paddle_tpu.telemetry import trace

        telemetry.enable()
        trace.enable()
        trace.reset()
        try:
            eng = _ahead_engine("dense", max_new_tokens=7)
            c0 = dict(telemetry.snapshot()["counters"].get(
                "serving_decode_ticks_total", {}))
            for p in self._prompts(seed=44, sizes=(6, 6)):
                eng.submit(p)
            eng.run_until_complete()
            events = trace.events()
        finally:
            trace.disable()
        # 7 tokens a row: one from the prefill pass, six ticks
        assert eng.decode_ticks == {"settled": 1, "ahead": 5}
        c1 = telemetry.snapshot()["counters"]["serving_decode_ticks_total"]
        delta = {k: v - c0.get(k, 0) for k, v in c1.items()}
        assert sorted(delta.values()) == [1, 5], delta
        assert {k for k, v in delta.items() if v == 5} == {
            k for k in delta if "ahead" in k}
        ticks = [e["attrs"] for e in events
                 if e.get("ph") == "X" and e["name"] == "decode_tick"]
        assert [t["ahead"] for t in ticks] == [0, 1, 1, 1, 1, 1]
        assert all(t["ticks"] == 1 and t["live"] == 2
                   and t["discarded"] == 0 for t in ticks)

    def test_a_draft_engine_and_a_prefill_only_one_stay_settled(self):
        """What stays synchronous is read off the engine's own state: a
        plain tick under a draft model is settled at once (the next
        speculative window is built from the host's tokens), and a
        ``prefill_only`` engine never decodes."""
        model = _tiny_model()
        prompts = self._prompts(seed=45, sizes=(5, 7))
        spec = ContinuousBatchingEngine(
            model, max_slots=2, page_size=8, max_seq_len=64,
            max_new_tokens=6, prefill_chunk=8, draft_model=model,
            spec_tokens=2, seed=1)
        seen, _ = _streams(spec, prompts, temperature=0.8)  # fallback ticks
        for _ in range(40):
            spec.step()
            assert spec._in_flight is None
        assert all(len(t) == 6 for t in seen.values())
        assert spec.decode_ticks["ahead"] == 0
        assert spec.decode_ticks["settled"] > 0
        half = ContinuousBatchingEngine(
            model, max_slots=2, page_size=8, max_seq_len=64,
            max_new_tokens=6, prefill_chunk=8, prefill_only=True)
        for p in prompts:
            half.submit(p)
        for _ in range(6):
            half.step()
        assert half._in_flight is None
        assert half.decode_ticks == {"ahead": 0, "settled": 0}


class TestScanDecode:
    """ISSUE 12 satellite: the serving forward compiles through the
    scan-over-layers body (depth-flat replica cold start); the
    unrolled escape hatch is bitwise."""

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_scan_vs_unrolled_bitwise(self, monkeypatch, kind):
        model = _model_of(kind)
        rng = np.random.default_rng(21)
        prompts = [rng.integers(1, 96, (n,)).tolist() for n in (5, 9)]

        def serve(scan):
            monkeypatch.setenv("PTPU_SCAN_LAYERS", scan)
            eng = ContinuousBatchingEngine(
                model, max_slots=2, page_size=16, max_seq_len=64,
                max_new_tokens=6, prefill_chunk=8)
            assert eng._scan_layers == (scan == "1")
            for p in prompts:
                eng.submit(p)
            return eng.run_until_complete()

        assert serve("1") == serve("0")

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_warmup_records_build_seconds(self, kind):
        model = _model_of(kind)
        eng = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                       max_seq_len=64, max_new_tokens=4,
                                       prefill_chunk=8)
        assert eng.build_seconds is None
        dt = eng.warmup()
        assert dt > 0 and eng.build_seconds == dt
        # warmup wrote only into the scratch page: a real request after
        # warmup behaves exactly like one on a fresh engine
        rng = np.random.default_rng(22)
        prompt = rng.integers(1, 96, (6,)).tolist()
        eng.submit(prompt)
        warm = eng.run_until_complete()[0]
        fresh = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                         max_seq_len=64, max_new_tokens=4,
                                         prefill_chunk=8)
        fresh.submit(prompt)
        assert warm == fresh.run_until_complete()[0]


class TestKvWriteRun:
    """`_kv_write_run`, the one writer of K/V rows: each slot's run of
    consecutive positions lands in the pages its table names, row for
    row what a plain per-row loop writes, and nothing else moves."""

    L, HKV, PAGES, PAGE, D, B, PPS = 3, 2, 9, 8, 4, 3, 3

    def _case(self, c, seed):
        rng = np.random.default_rng(seed)
        pool = rng.standard_normal(
            (self.L, self.HKV, self.PAGES + 1, self.PAGE, self.D)
        ).astype(np.float32)
        # distinct pages a slot: pages are exclusively owned
        tables = rng.permutation(self.PAGES).reshape(
            self.B, self.PPS).astype(np.int32)
        vals = rng.standard_normal(
            (self.B, c, self.HKV, self.D)).astype(np.float32)
        return pool, tables, vals

    @staticmethod
    def _reference(pool, li, tables, pos0, nvalid, vals, page):
        want = pool.copy()
        for b in range(len(pos0)):
            for r in range(int(nvalid[b])):
                pos = int(pos0[b]) + r
                if pos // page < tables.shape[1]:
                    want[li, :, tables[b, pos // page], pos % page] = \
                        vals[b, r]
        return want

    @pytest.mark.parametrize("traced", [False, True],
                             ids=["int-layer", "traced-layer"])
    @pytest.mark.parametrize("c,pos0,nvalid", [
        (1, [0, 7, 17], [1, 1, 1]),        # a decode tick: one row a slot
        (3, [6, 15, 21], [3, 3, 3]),       # a window across a page edge
        (8, [0, 8, 16], [8, 5, 0]),        # aligned chunk, a short and an
                                           # idle slot
        (8, [3, 13, 5], [8, 8, 2]),        # unaligned: three pages a run
        (12, [0, 2, 9], [12, 10, 7]),      # c over a page
        (8, [20, 23, 16], [8, 8, 8]),      # positions past the table's
                                           # end are dropped
    ])
    def test_matches_row_loop(self, c, pos0, nvalid, traced):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.inference.serving import _kv_write_run

        pool, tables, vals = self._case(c, seed=c + sum(pos0))
        li = 1
        want = self._reference(pool, li, tables, pos0, nvalid, vals,
                               self.PAGE)
        args = (jnp.asarray(tables), jnp.asarray(pos0, jnp.int32),
                jnp.asarray(nvalid, jnp.int32), jnp.asarray(vals))
        if traced:
            got = jax.jit(lambda p, l: _kv_write_run(p, l, *args))(
                jnp.asarray(pool), jnp.int32(li))
        else:
            got = _kv_write_run(jnp.asarray(pool), li, *args)
        got = np.asarray(got)
        # the scratch page (the pool's last) may hold anything
        assert got[:, :, :-1].tobytes() == want[:, :, :-1].tobytes()

    def test_int8_pool_quantizes_each_row(self):
        import jax.numpy as jnp

        from paddle_tpu.inference.serving import _kv_write_run
        from paddle_tpu.memory import quantize_rows_int8

        pool, tables, vals = self._case(8, seed=5)
        pos0, nvalid = [3, 13, 5], [8, 6, 0]
        codes, scales = quantize_rows_int8(jnp.asarray(pool))
        qv, sv = quantize_rows_int8(jnp.asarray(vals))
        want_q = self._reference(np.asarray(codes), 2, tables, pos0, nvalid,
                                 np.asarray(qv), self.PAGE)
        want_s = self._reference(np.asarray(scales), 2, tables, pos0,
                                 nvalid, np.asarray(sv), self.PAGE)
        got_q, got_s = _kv_write_run(
            (codes, scales), 2, jnp.asarray(tables),
            jnp.asarray(pos0, jnp.int32), jnp.asarray(nvalid, jnp.int32),
            jnp.asarray(vals))
        assert got_q.dtype == jnp.int8 and got_s.dtype == jnp.float32
        assert np.asarray(got_q)[:, :, :-1].tobytes() == \
            want_q[:, :, :-1].tobytes()
        assert np.asarray(got_s)[:, :, :-1].tobytes() == \
            want_s[:, :, :-1].tobytes()


_POOL_PROGRAMS = ("decode", "prefill", "prefill_r1", "verify",
                  "draft_window_c2")


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (scan and pjit bodies, branches), except a Pallas kernel's own body:
    what a kernel does to one VMEM block is not the program's traffic."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub)


class TestPoolStaysInPlace:
    """ISSUE 28: a serving program takes the stacked K/V pool, writes
    rows into it in place and returns the same buffer. Structural: the
    CPU's buffer assignment keeps a pool-sized temporary in either
    form, so bytes are the chip's to prove (``program_bytes``); what a
    CPU can prove is that the jaxpr gives XLA nothing to copy — the
    pools ride the layer scan's carry, and no equation makes a layer's
    slab or a second pool."""

    @pytest.fixture(scope="class", params=[
        (scan, kv) for scan in ("scan", "unrolled")
        for kv in ("exact", "int8")], ids="-".join)
    def programs(self, request):
        """One warmed engine with a draft per (walk, pool kind): its
        programs' jaxprs as ``warmup()`` compiled them, taken at
        ``_warm``, the one seam every compiled program passes."""
        import paddle_tpu.telemetry as telemetry
        from paddle_tpu.telemetry import trace

        scan, kv = request.param
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("PTPU_SCAN_LAYERS", "1" if scan == "scan" else "0")
            model = _tiny_model()
            eng = ContinuousBatchingEngine(
                model, max_slots=2, page_size=16, max_seq_len=64,
                max_new_tokens=4, prefill_chunk=8, draft_model=model,
                spec_tokens=2, int8_kv=kv == "int8")
            assert eng._scan_layers == (scan == "scan")
            assert eng.int8_kv == (kv == "int8")
            jaxprs, warm = {}, eng._warm

            def spy(name, jitted, *operands):
                jaxprs[name] = jitted.trace(*operands).jaxpr.jaxpr
                return warm(name, jitted, *operands)

            eng._warm = spy
            telemetry.enable()
            trace.enable()
            try:
                eng.warmup()
                gauges = telemetry.snapshot()["gauges"]
                instants = [e for e in trace.events()
                            if e["name"] == "program_memory"]
            finally:
                trace.disable()
                trace.reset()
                telemetry.disable()
                telemetry.reset()
        return eng, jaxprs, gauges, instants

    @pytest.mark.parametrize("program", _POOL_PROGRAMS)
    def test_pool_rides_the_carry_and_no_slab_is_made(self, programs,
                                                      program):
        eng, jaxprs, _, _ = programs
        # the draft's pool has the target's shape here (self-drafting)
        pool = tuple(np.shape(eng._draft.kc))
        slab = pool[1:]
        eqns = list(_walk_eqns(jaxprs[program]))

        def shapes(vs):
            return [tuple(getattr(v.aval, "shape", ())) for v in vs]

        for eqn in eqns:
            for shp in shapes(eqn.outvars):
                assert shp != slab and shp != (1,) + slab, (
                    f"{program}: {eqn.primitive.name} makes a layer's "
                    f"slab {shp}")
        makers = {eqn.primitive.name for eqn in eqns
                  if pool in shapes(eqn.outvars)}
        # pages scattered back in place, and the layer scan hands the
        # buffer on: nothing else has a pool for a result
        assert "scatter" in makers
        assert makers <= {"scatter", "scan"}, makers
        scans = [e for e in eqns if e.primitive.name == "scan"
                 and e.params["length"] == eng.cfg.num_layers]
        if not eng._scan_layers:
            assert not scans
            return
        (scan,) = scans
        nc, nk = scan.params["num_consts"], scan.params["num_carry"]
        ins = shapes(scan.invars)
        assert ins[nc:nc + nk].count(pool) == 2       # K and V, carried
        assert pool not in ins[:nc] + ins[nc + nk:]   # not consts, not xs
        outs = shapes(scan.outvars)
        assert outs[:nk].count(pool) == 2
        assert not outs[nk:]                          # the scan has no ys

    def test_warmup_fills_program_bytes(self, programs):
        """Each compiled program's memory_analysis(), once, at warmup:
        on the engine, in the gauge, and as an instant in the trace."""
        eng, jaxprs, gauges, instants = programs
        names = set(_POOL_PROGRAMS) | {"draft_window_c1"}
        assert set(eng.program_bytes) == set(jaxprs) == names
        pool_bytes = 2 * sum(
            x.nbytes for x in (eng.kc if eng.int8_kv else (eng.kc,)))
        for name, nb in eng.program_bytes.items():
            assert set(nb) == {"temp", "alias"}
            assert nb["temp"] >= 0
            # both pools are donated and come back in the same buffers
            want = (2 * eng._draft.kc.nbytes if name.startswith("draft")
                    else pool_bytes)
            assert nb["alias"] >= want, (name, nb, want)
        g = gauges["serving_program_temp_bytes"]
        assert g == {f"program={n}": float(nb["temp"])
                     for n, nb in eng.program_bytes.items()}
        assert {e["attrs"]["program"]: {k: e["attrs"][k]
                                        for k in ("temp", "alias")}
                for e in instants} == eng.program_bytes


def test_batched_prefill_single_compile_and_throughput():
    """VERDICT r3 item 7: chunked prefill is one BATCHED jitted pass over
    all prefilling slots (fixed shapes -> compiles once), and the engine
    records a continuous-batching throughput number so regressions are
    visible."""
    import time

    model = _tiny_model(seed=3)
    eng = ContinuousBatchingEngine(model, max_slots=4, page_size=16,
                                   max_new_tokens=8, prefill_chunk=4)
    rng = np.random.RandomState(0)
    n_requests = 8
    for _ in range(n_requests):
        eng.submit(list(rng.randint(1, 90, rng.randint(6, 20))))
    t0 = time.perf_counter()
    done = eng.run_until_complete()
    dt = time.perf_counter() - t0
    assert len(done) == n_requests
    toks = sum(len(v) for v in done.values())
    print(f"\nserving throughput ({n_requests} concurrent, chunked prefill):"
          f" {toks / dt:.1f} tok/s over {toks} tokens")
    # every prefilling slot advances per tick through ONE jitted pass
    assert eng.prefill_chunk_steps > 0
    # the pass's shapes are the row ladder's: one compilation of the
    # chunk step a step of it, however the arrivals fall
    sizes = eng._prefill_jit._cache_size()
    assert 1 <= sizes <= len(eng._pass_rows) == 3, sizes


@pytest.mark.parametrize("kind", MODEL_KINDS)
def test_batched_prefill_advances_all_slots_together(kind):
    """Two long prompts admitted together finish prefill on the same tick
    count a single request would need (they share the batched pass), not
    2x (the r3 one-request-per-tick behavior)."""
    model = _model_of(kind, seed=4)
    eng = ContinuousBatchingEngine(model, max_slots=4, page_size=16,
                                   max_new_tokens=2, prefill_chunk=4)
    prompt = list(range(1, 17))          # 16 tokens -> 4 chunks of 4
    eng.submit(prompt)
    eng.submit(prompt)
    ticks = 0
    while eng.prefills_completed < 2:
        eng.step()
        ticks += 1
        assert ticks < 50
    # both prompts prefilled in ~4 chunk passes, not ~8
    assert eng.prefill_chunk_steps <= 5, eng.prefill_chunk_steps


class TestServingSoak:
    @staticmethod
    def _check_invariants(eng):
        """Page-accounting invariants that must hold after EVERY tick:
        no leaks, no double-ownership, refcounts consistent."""
        live_pages = []
        for r in eng._slots:
            if r is not None:
                assert len(set(r.pages)) == len(r.pages), (
                    "request holds a duplicate page", r.rid, r.pages)
                live_pages.extend(r.pages)
        cached = set(eng._prefix_cache.values())
        assert cached == eng._cached_pages
        from collections import Counter

        holders = Counter(live_pages)
        # a page held by >1 request must be cache-shared; refcounts match
        for pg, n in holders.items():
            if n > 1:
                assert pg in cached, (pg, n)
            assert eng._page_ref.get(pg, 0) == n, (
                pg, n, eng._page_ref.get(pg, 0))
        # cache-held pages with no live holder carry ref 0
        for pg in cached - set(holders):
            assert eng._page_ref.get(pg, 0) == 0, pg
        # conservation: allocated == live ∪ cached (no leak, no alias)
        allocated = eng.pool.num_pages - eng.pool.available
        assert allocated == len(set(live_pages) | cached), (
            allocated, len(set(live_pages) | cached))

    @pytest.mark.slow
    def test_randomized_soak_accounting(self):
        """40 requests with random lengths/arrival times/sampling modes,
        half sharing a system prompt, through a starved pool with prefix
        caching on — the full feature interaction surface (growth,
        preemption-recompute, cache register/hit/evict, mixed
        greedy/sampled ticks). Invariants checked after every tick;
        everything must drain."""
        model = _tiny_model()
        rng = np.random.default_rng(17)
        system = list(range(1, 13))  # 3 full pages @4
        eng = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                       max_seq_len=64, num_pages=17,
                                       max_new_tokens=6, prefill_chunk=5,
                                       enable_prefix_cache=True)
        pending = []
        for i in range(40):
            if rng.random() < 0.5:
                prompt = system + rng.integers(1, 96, (
                    int(rng.integers(1, 8)),)).tolist()
            else:
                prompt = rng.integers(1, 96, (
                    int(rng.integers(4, 20)),)).tolist()
            temp = 0.0 if rng.random() < 0.5 else 0.7
            pending.append((int(rng.integers(0, 120)), prompt, temp))
        pending.sort(key=lambda t: t[0])

        done = {}
        for tick in range(4000):
            while pending and pending[0][0] <= tick:
                _, prompt, temp = pending.pop(0)
                eng.submit(prompt, temperature=temp, top_k=8, top_p=0.95)
            done.update(eng.step())
            self._check_invariants(eng)
            if (not pending and not eng._waiting
                    and all(s is None for s in eng._slots)):
                break
        else:
            raise AssertionError("soak did not drain")
        assert len(done) == 40
        assert all(len(v) > 0 for v in done.values())
        # steady state: every refcount at zero, pool fully accounted
        assert all(v == 0 for v in eng._page_ref.values())
        assert (eng.pool.available + len(eng._cached_pages)
                == eng.pool.num_pages)
        # the workload exercised the interesting paths
        assert eng.prefix_cache_hits > 0
        assert eng.preemptions > 0 or eng.prefix_cache_evictions > 0


    @pytest.mark.slow
    def test_randomized_soak_swap_policy(self):
        """Same soak shape under preempt_policy='swap' (prefix cache
        off — the policies are exclusive): swapped-out requests hold no
        pages while their snapshots wait, restores rebuild exactly, and
        the pool conserves."""
        model = _tiny_model()
        rng = np.random.default_rng(23)
        eng = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                       max_seq_len=64, num_pages=13,
                                       max_new_tokens=6, prefill_chunk=5,
                                       preempt_policy="swap")
        pending = []
        for i in range(30):
            prompt = rng.integers(1, 96, (
                int(rng.integers(4, 18)),)).tolist()
            pending.append((int(rng.integers(0, 90)), prompt))
        pending.sort(key=lambda t: t[0])

        done = {}
        for tick in range(4000):
            while pending and pending[0][0] <= tick:
                eng.submit(pending.pop(0)[1])
            done.update(eng.step())
            live = [r for r in eng._slots if r is not None]
            held = [pg for r in live for pg in r.pages]
            assert len(set(held)) == len(held), "double ownership"
            assert (eng.pool.num_pages - eng.pool.available
                    == len(held)), "pool leak"
            for r in eng._waiting:
                assert not r.pages, "waiting request holds pages"
            if (not pending and not eng._waiting
                    and all(s is None for s in eng._slots)):
                break
        else:
            raise AssertionError("swap soak did not drain")
        assert len(done) == 30
        assert eng.swaps_in == eng.swaps_out
        assert eng.pool.available == eng.pool.num_pages


@pytest.mark.slow  # serving soak; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
class TestGPTPipeServing:
    def test_gpt_pipe_model_serves_identically(self):
        """The flagship stacked/pipelined GPT family serves through the
        SAME engine: with identical weights, GPTForCausalLMPipe and
        LlamaForCausalLM produce bitwise-identical greedy streams
        (the _decode_params contract, llama.py:66 / gpt.py)."""
        import jax.numpy as jnp

        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

        dims = dict(vocab_size=96, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, max_seq_len=128,
                    dropout=0.0)
        paddle.seed(0)
        llama = LlamaForCausalLM(LlamaConfig(tie_embeddings=True, **dims))
        pipe = GPTForCausalLMPipe(GPTConfig(**dims))

        layers = llama.model.layers
        stack = lambda get: jnp.stack([get(l)._data for l in layers])
        pipe.embed_tokens.weight._data = llama.model.embed_tokens.weight._data
        pipe.final_norm.weight._data = llama.model.final_norm.weight._data
        d = pipe.decoder
        d.ln1._data = stack(lambda l: l.input_norm.weight)
        d.wq._data = stack(lambda l: l.attn.q_proj.weight)
        d.wk._data = stack(lambda l: l.attn.k_proj.weight)
        d.wv._data = stack(lambda l: l.attn.v_proj.weight)
        d.wo._data = stack(lambda l: l.attn.o_proj.weight)
        d.ln2._data = stack(lambda l: l.post_attn_norm.weight)
        d.wg._data = stack(lambda l: l.mlp.gate_proj.weight)
        d.wu._data = stack(lambda l: l.mlp.up_proj.weight)
        d.wd._data = stack(lambda l: l.mlp.down_proj.weight)

        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, 96, (n,)).tolist() for n in (11, 7, 9)]

        def serve(model):
            eng = ContinuousBatchingEngine(model, max_slots=2, page_size=8,
                                           max_seq_len=64,
                                           max_new_tokens=10,
                                           prefill_chunk=6)
            for p in prompts:
                eng.submit(p)
            return eng.run_until_complete()

        a, b = serve(llama), serve(pipe)
        assert sorted(a) == sorted(b) == [0, 1, 2]
        for rid in a:
            assert a[rid] == b[rid], (rid, a[rid], b[rid])


@pytest.mark.slow  # serving soak; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
class TestPageEconomics:
    """VERDICT r4 item 3: incremental page growth + preemption under
    pressure (block-table growth semantics of the reference's
    block_multi_head_attention serving path)."""

    def test_admission_reserves_prompt_not_worst_case(self):
        model = _tiny_model()
        eng = ContinuousBatchingEngine(model, max_slots=2, page_size=8,
                                       max_seq_len=64, max_new_tokens=40)
        eng.submit(list(range(1, 9)))  # 8 tokens = exactly one page
        eng.step()
        r = next(r for r in eng._slots if r is not None)
        # worst-case would be ceil((8+40)/8)=6 pages; prompt needs 1
        assert len(r.pages) <= 2, r.pages  # prompt page (+1 growth)

    def test_preemption_under_pressure_completes_all(self):
        model = _tiny_model()
        new_tokens = 12
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 96, (n,)).tolist()
                   for n in (10, 9, 11, 8)]

        # roomy reference run (greedy): the ground truth outputs
        roomy = ContinuousBatchingEngine(model, max_slots=4, page_size=4,
                                         max_seq_len=48,
                                         max_new_tokens=new_tokens)
        for pr in prompts:
            roomy.submit(pr)
        want = roomy.run_until_complete()
        assert roomy.preemptions == 0

        # starved pool: enough for each request alone ((11+12)/4 -> 6
        # pages) but NOT for four growing concurrently
        eng = ContinuousBatchingEngine(model, max_slots=4, page_size=4,
                                       max_seq_len=48, num_pages=13,
                                       max_new_tokens=new_tokens)
        for pr in prompts:
            eng.submit(pr)
        done = eng.run_until_complete()
        assert sorted(done) == [0, 1, 2, 3]
        assert eng.preemptions > 0, "pool pressure must trigger preemption"
        # preemption is recompute: greedy outputs stay BITWISE identical
        for rid in done:
            assert done[rid] == want[rid], (
                rid, eng.preemptions, done[rid], want[rid])

    def test_preemption_with_chunked_prefill(self):
        model = _tiny_model()
        new_tokens = 10
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 96, (n,)).tolist() for n in (12, 10, 9)]
        roomy = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                         max_seq_len=48,
                                         max_new_tokens=new_tokens,
                                         prefill_chunk=5)
        for pr in prompts:
            roomy.submit(pr)
        want = roomy.run_until_complete()

        eng = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                       max_seq_len=48, num_pages=11,
                                       max_new_tokens=new_tokens,
                                       prefill_chunk=5)
        for pr in prompts:
            eng.submit(pr)
        done = eng.run_until_complete()
        assert sorted(done) == [0, 1, 2]
        assert eng.preemptions > 0
        for rid in done:
            assert done[rid] == want[rid], (rid, done[rid], want[rid])

    def test_swap_policy_bitwise_and_no_recompute(self):
        """preempt_policy="swap": victims' KV pages round-trip through
        host memory instead of being recomputed — greedy outputs stay
        bitwise identical to a roomy pool AND each request prefills
        exactly once (no FLOPs re-paid)."""
        model = _tiny_model()
        new_tokens = 12
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 96, (n,)).tolist()
                   for n in (10, 9, 11, 8)]

        roomy = ContinuousBatchingEngine(model, max_slots=4, page_size=4,
                                         max_seq_len=48,
                                         max_new_tokens=new_tokens)
        for pr in prompts:
            roomy.submit(pr)
        want = roomy.run_until_complete()

        eng = ContinuousBatchingEngine(model, max_slots=4, page_size=4,
                                       max_seq_len=48, num_pages=13,
                                       max_new_tokens=new_tokens,
                                       preempt_policy="swap")
        for pr in prompts:
            eng.submit(pr)
        done = eng.run_until_complete()
        assert sorted(done) == [0, 1, 2, 3]
        assert eng.preemptions > 0, "pool pressure must trigger preemption"
        assert eng.swaps_out > 0 and eng.swaps_in == eng.swaps_out
        # the swap path restores KV instead of re-prefilling
        assert eng.prefills_completed == len(prompts), (
            eng.prefills_completed, eng.preemptions)
        for rid in done:
            assert done[rid] == want[rid], (
                rid, eng.preemptions, done[rid], want[rid])

    def test_swap_policy_with_chunked_prefill(self):
        model = _tiny_model()
        new_tokens = 10
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 96, (n,)).tolist() for n in (12, 10, 9)]
        roomy = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                         max_seq_len=48,
                                         max_new_tokens=new_tokens,
                                         prefill_chunk=5)
        for pr in prompts:
            roomy.submit(pr)
        want = roomy.run_until_complete()

        eng = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                       max_seq_len=48, num_pages=11,
                                       max_new_tokens=new_tokens,
                                       prefill_chunk=5,
                                       preempt_policy="swap")
        for pr in prompts:
            eng.submit(pr)
        done = eng.run_until_complete()
        assert sorted(done) == [0, 1, 2]
        assert eng.preemptions > 0
        assert eng.swaps_in == eng.swaps_out > 0
        assert eng.prefills_completed == len(prompts)
        for rid in done:
            assert done[rid] == want[rid], (rid, done[rid], want[rid])

    def test_swap_policy_rejects_bad_value(self):
        model = _tiny_model()
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(model, preempt_policy="drop")

    def test_prefix_cache_reuses_pages_bitwise(self):
        """Automatic prefix caching (vLLM APC / radix-cache shape): a
        second request sharing a full-page prompt prefix reuses the
        cached KV pages and prefills ONLY the tail; greedy outputs stay
        bitwise identical to the cache-off engine."""
        model = _tiny_model()
        system = list(range(1, 13))        # 12 tokens = 3 full pages @4
        prompts = [system + [20, 21, 22],  # shared prefix, distinct tails
                   system + [30, 31],
                   system + [20, 21, 22]]  # exact repeat of prompt 0

        def run(**kw):
            eng = ContinuousBatchingEngine(
                model, max_slots=2, page_size=4, max_seq_len=48,
                max_new_tokens=8, prefill_chunk=4, **kw)
            for p in prompts:
                eng.submit(p)
            return eng, eng.run_until_complete()

        _, want = run()
        eng, got = run(enable_prefix_cache=True)
        assert sorted(got) == [0, 1, 2]
        for rid in got:
            assert got[rid] == want[rid], (rid, got[rid], want[rid])
        # request 0 prefills everything and registers; 1 and 2 reuse the
        # 3 system pages each (2 slots: 0 and 1 admit together, so 1
        # only hits pages after 0 releases... assert at least one full
        # reuse and the skip counter)
        assert eng.prefix_cache_hits >= 3, eng.prefix_cache_hits
        assert eng.prefix_tokens_skipped >= 12
        # no page leaks: after drain, live refs are zero and cached +
        # free pages account for the whole pool
        assert all(v == 0 for v in eng._page_ref.values())
        cached = set(eng._prefix_cache.values())
        assert eng.pool.available + len(cached) == eng.pool.num_pages

    def test_prefix_cache_eviction_under_pressure(self):
        """Free-but-cached pages are reclaimed (FIFO) when the pool runs
        short; the engine completes all work without deadlock."""
        model = _tiny_model()
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 96, (9,)).tolist() for _ in range(4)]

        def run(**kw):
            eng = ContinuousBatchingEngine(
                model, max_slots=2, page_size=4, max_seq_len=48,
                num_pages=9, max_new_tokens=8, prefill_chunk=4, **kw)
            for p in prompts:
                eng.submit(p)
            return eng, eng.run_until_complete()

        _, want = run()
        eng, got = run(enable_prefix_cache=True)
        assert sorted(got) == [0, 1, 2, 3]
        assert eng.prefix_cache_evictions > 0, (
            "tiny pool must force cache eviction")
        for rid in got:
            assert got[rid] == want[rid], (rid, got[rid], want[rid])

    def test_prefix_cache_requires_chunked_recompute(self):
        model = _tiny_model()
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(model, enable_prefix_cache=True)
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(model, enable_prefix_cache=True,
                                     prefill_chunk=4,
                                     preempt_policy="swap")

    def test_prefix_cache_matched_pages_survive_eviction(self):
        """Admission must PIN matched prefix pages before evicting for
        the tail allocation — the regression was FIFO eviction
        reclaiming the just-matched (ref-0, oldest) prefix page and
        re-issuing it as the same request's tail page: one physical
        page aliased into prefix-read and tail-write roles."""
        model = _tiny_model()
        system = list(range(1, 9))          # 8 tokens = 2 pages @4
        a = system + [90]                   # seeds p0,p1 (oldest FIFO)
        c = [70, 71, 72, 73, 74, 75, 76, 77, 78]  # seeds younger entries
        b = system + [40, 41, 42, 43, 44, 45]     # matches p0,p1; needs
                                                  # 2 own pages, 1 free

        def run(**kw):
            eng = ContinuousBatchingEngine(model, max_slots=1, page_size=4,
                                           max_seq_len=48, num_pages=5,
                                           max_new_tokens=2,
                                           prefill_chunk=4, **kw)
            outs = []
            for p in (a, c, b):
                eng.submit(p)
                outs.append(eng.run_until_complete())
            return eng, outs

        _, want = run()
        eng, got = run(enable_prefix_cache=True)
        assert eng.prefix_cache_hits >= 2      # b reused the system pages
        assert eng.prefix_cache_evictions >= 1  # tail alloc forced eviction
        for w, g in zip(want, got):
            assert w == g, (w, g)
        # matched pages stayed coherent: no page appears twice in any
        # accounting (a duplicate would mean the aliasing regression)
        assert len(eng._cached_pages) == len(
            set(eng._prefix_cache.values()))

    def test_prefix_cache_with_sampling_completes(self):
        """Prefix reuse is orthogonal to the sampling mode: sampled
        (temperature>0) requests sharing a prefix complete, reuse
        pages, and drain refcounts — outputs are stochastic so only
        liveness + accounting are asserted."""
        model = _tiny_model()
        system = list(range(1, 13))
        eng = ContinuousBatchingEngine(model, max_slots=2, page_size=4,
                                       max_seq_len=48, max_new_tokens=6,
                                       prefill_chunk=4,
                                       enable_prefix_cache=True)
        for tail in ([20, 21], [30], [40, 41, 42]):
            eng.submit(system + tail, temperature=0.8, top_k=10,
                       top_p=0.9)
        done = eng.run_until_complete()
        assert sorted(done) == [0, 1, 2]
        assert all(len(v) > len(system) for v in done.values())
        assert eng.prefix_cache_hits > 0
        assert all(v == 0 for v in eng._page_ref.values())

    def test_prefix_cache_fully_aligned_prompt_still_decodes(self):
        """A prompt whose pages are ALL cached must still compute its
        first token: matching is capped one token short, so the last
        token always prefills."""
        model = _tiny_model()
        base = list(range(1, 9))  # 8 tokens = 2 full pages @4

        def run(**kw):
            eng = ContinuousBatchingEngine(
                model, max_slots=1, page_size=4, max_seq_len=48,
                max_new_tokens=6, prefill_chunk=4, **kw)
            eng.submit(base)
            first = eng.run_until_complete()
            eng.submit(base)  # identical prompt, page-aligned
            second = eng.run_until_complete()
            return eng, first, second

        _, f0, s0 = run()
        eng, f1, s1 = run(enable_prefix_cache=True)
        assert f1[0] == f0[0] and s1[1] == s0[1]
        assert eng.prefix_cache_hits >= 1
        # the identical prompt reused at most len-1 tokens
        assert eng.prefix_tokens_skipped < 2 * len(base)

    def test_swap_group_prefill_no_thrash(self):
        """A decode-phase victim under GROUP (non-chunked) prefill must
        restore with its growth page reserved — the regression was
        prefill_pos lagging length after _prefill_group, misclassifying
        the snapshot as mid-prefill and looping restore->starve->swap
        (one full host KV round-trip per tick, zero progress)."""
        model = _tiny_model()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 96, (6,)).tolist() for _ in range(2)]

        roomy = ContinuousBatchingEngine(model, max_slots=2, page_size=4,
                                         max_seq_len=48,
                                         max_new_tokens=14)
        for p in prompts:
            roomy.submit(p)
        want = roomy.run_until_complete()

        eng = ContinuousBatchingEngine(model, max_slots=2, page_size=4,
                                       max_seq_len=48, num_pages=7,
                                       max_new_tokens=14,
                                       preempt_policy="swap")
        for p in prompts:
            eng.submit(p)
        done = eng.run_until_complete()
        assert sorted(done) == [0, 1]
        assert eng.swaps_out <= 2, (
            f"swap thrash: {eng.swaps_out} round-trips")
        for rid in done:
            assert done[rid] == want[rid], (rid, done[rid], want[rid])


# ------------------------------------------ the width of a prefill pass
class TestPrefillPassWidth:
    """ISSUE 33: a chunked prefill pass is as wide as the smallest step
    of the engine's row ladder that holds the rows with a chunk. One
    jitted function, one compiled program a step, all of them made by
    ``warmup()``; a row's result does not depend on the width."""

    @pytest.mark.parametrize("slots,want", [
        (1, (1,)), (4, (1, 2, 4)), (6, (1, 2, 4, 6)),
        (32, (1, 2, 4, 8, 16, 32)), (64, (1, 2, 4, 8, 16, 32, 64))])
    def test_ladder(self, slots, want):
        from paddle_tpu.inference.serving import _pass_row_ladder

        steps = _pass_row_ladder(slots)
        assert steps == want
        assert steps[-1] == slots and len(set(steps)) == len(steps)
        assert list(steps) == sorted(steps)
        # any count of rows has a step, under twice as wide
        for rows in range(1, slots + 1):
            step = next(n for n in steps if n >= rows)
            assert rows <= step < 2 * rows or step == slots

    @pytest.fixture(scope="class", params=[
        ("mha", 4, {}), ("gqa", 2, {}), ("int8-pool", 2, {"int8_kv": True})],
        ids=lambda p: p[0])
    def dense(self, request):
        """(model, its subject row through the full-width pass alone,
        engine arguments) of one dense kind."""
        import _prefill_width as pw

        _, kv_heads, engine_kw = request.param
        cfg = LlamaConfig(vocab_size=96, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=kv_heads,
                          max_seq_len=128, dropout=0.0)
        paddle.seed(11)
        model = LlamaForCausalLM(cfg)
        base = pw.serve_beside(model, 0, True, **engine_kw)
        if engine_kw:
            assert base[1][0].dtype == np.int8
        return model, base, engine_kw

    @pytest.mark.parametrize("neighbours", [0, 1, 3, 7])
    def test_a_row_does_not_depend_on_the_rows_beside_it(self, dense,
                                                         neighbours):
        import _prefill_width as pw

        model, base, engine_kw = dense
        pw.assert_same_row(model, neighbours, base, **engine_kw)

    @staticmethod
    def _staggered(eng):
        """Arrivals that put 1, 2, 4 and 8 rows into a pass, in turn:
        each prompt is long enough to be prefilling when the next come."""
        rng = np.random.default_rng(3)
        for more in (1, 1, 2, 4):
            for _ in range(more):
                eng.submit(rng.integers(1, 96, 40).tolist())
            eng.step()
        return eng.run_until_complete()

    @pytest.mark.parametrize("kind", MODEL_KINDS)
    def test_no_compile_after_warmup_on_any_step(self, kind):
        import paddle_tpu.telemetry as telemetry
        from paddle_tpu.telemetry import trace

        eng = ContinuousBatchingEngine(
            _model_of(kind, seed=5), max_slots=8, page_size=8,
            max_seq_len=64, max_new_tokens=3, prefill_chunk=4)
        eng.warmup()
        assert set(eng.program_bytes) == {
            "decode", "prefill", "prefill_r1", "prefill_r2", "prefill_r4"}
        jits = (eng._prefill_jit, eng._first_token_jit, eng._decode_jit)
        before = [j._cache_size() for j in jits]
        assert before == [4, 1, 1]
        telemetry.enable()
        trace.enable()
        trace.reset()
        try:
            done = self._staggered(eng)
            # brownout L3 narrows a pass's valid tokens, not its shapes
            eng.prefill_chunk_cap = 2
            done.update(self._staggered(eng))
            events = trace.events()
            passes = telemetry.snapshot()["counters"][
                "serving_prefill_passes_total"]
        finally:
            trace.disable()
            trace.reset()
            telemetry.disable()
            telemetry.reset()
        assert len(done) == 16
        assert [j._cache_size() for j in jits] == before
        assert not [e for e in events if e["name"] == "xla_compile"]
        launched = [e["attrs"] for e in events
                    if e["name"] == "prefill_tick" and e["attrs"]]
        assert {a["pass_rows"] for a in launched} == {1, 2, 4, 8}
        for a in launched:
            assert a["computed_tokens"] == a["pass_rows"] * 4
            assert a["rows"] <= a["pass_rows"] < 2 * a["rows"] or (
                a["pass_rows"] == 8)
            assert a["valid_tokens"] <= a["rows"] * 4
        # the counter names each pass by the width it was compiled at
        assert passes == {
            f"rows={n}": float(sum(a["pass_rows"] == n for a in launched))
            for n in (1, 2, 4, 8)}
        assert sum(passes.values()) == eng.prefill_chunk_steps
