"""The latent-attention + routed-experts model behind the serving engine
(ISSUE 30), at tiny widths on the CPU, float32, seeded. The plain
reference is the benchmark's (harness/families/mla_moe.py: not absorbed,
no cache, every held expert on every token under a mask)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _latent_tiny import latent_model, tiny_cfg
from harness.families import mla_moe
from paddle_tpu.incubate.distributed.models.moe.grouped import (
    grouped_sigmoid_route, held_expert_ffn)
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.ops.pallas.decode_attention import (_mla_gather,
                                                    mla_paged_attention)

# float32 on the CPU: the program and the reference differ by the order of
# float32 sums (absorbed against up-projected attention, a running softmax
# against a whole one, sorted experts against masked ones): logits of size
# ~1 agree to a few 1e-6; 1e-4 leaves room and is far under what a dropped
# term gives (1e-2 and more)
TOL = 1e-4


def _engine(model, **kw):
    eng = dict(max_slots=2, page_size=8, max_seq_len=64, prefill_chunk=8,
               max_new_tokens=8)
    eng.update(kw)
    return ContinuousBatchingEngine(model, **eng)


def _ref_logits(cfg, seed, ids):
    w = mla_moe.make_weights(cfg, seed, jnp.float32)
    return np.asarray(mla_moe.forward_logits(w, jnp.asarray(ids), cfg))


class TestAgainstTheReference:
    def test_chunked_prefill_then_decode_equals_full_forward_in_logits(self):
        """Two rows of ragged length: the prompts go through the chunk
        program (8 positions a pass, the second row's last chunk partial
        and its history crossing a page edge), then every further token
        through the decode program, teacher-forced; the logits at each
        chunk's end and at every decoded position equal the reference's
        full forward."""
        cfg = tiny_cfg()
        eng = _engine(latent_model(3))
        rng = np.random.default_rng(0)
        seqs = [rng.integers(1, 96, n).tolist() for n in (21, 27)]
        prompts = (8, 13)
        want = [_ref_logits(cfg, 3, s) for s in seqs]
        tables = jnp.asarray([[0, 1, 2, 3, 0, 0, 0, 0],
                              [4, 5, 6, 7, 0, 0, 0, 0]], jnp.int32)
        w = eng._weights
        for start in (0, 8):
            nvalid = [max(0, min(8, p - start)) for p in prompts]
            ids = np.zeros((2, 8), np.int32)
            for b in range(2):
                ids[b, :nvalid[b]] = seqs[b][start:start + nvalid[b]]
            last, _, eng.cache = eng._prefill_jit(
                w, jnp.asarray(ids), jnp.full((2,), start, jnp.int32),
                jnp.asarray(nvalid, jnp.int32), tables, eng.cache)
            got = np.asarray(last @ w["head"])
            for b in range(2):
                if nvalid[b]:
                    np.testing.assert_allclose(
                        got[b], want[b][start + nvalid[b] - 1], atol=TOL,
                        rtol=0)
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
                eng._no_tick, jnp.full((2,), -1, jnp.int32))
            got = np.asarray(seen[-1])
            for b in range(2):
                np.testing.assert_allclose(got[b], want[b][lens[b]],
                                           atol=TOL, rtol=0)
            assert out.shape == (2 + 4,) and int(out[-1]) == 0  # no drop
            lens = lens + 1

    def test_prefill_through_the_kernel_equals_full_forward_in_logits(
            self, monkeypatch):
        """The chunk program with its attention through the
        ``mla_paged_prefill`` kernel (interpret mode here; heads 4 and
        chunk 8, so a mix-up of the two axes cannot pass): the logits at
        each chunk's end equal the reference's."""
        from paddle_tpu.models.latent_moe import LatentMoEServing

        monkeypatch.setattr(LatentMoEServing, "prefill_kernel", True)
        over = dict(kv_lora_rank=128)        # the kernel slices lanes
        cfg = tiny_cfg(**over)
        eng = _engine(latent_model(4, **over))
        rng = np.random.default_rng(3)
        seqs = [rng.integers(1, 96, n).tolist() for n in (16, 11)]
        want = [_ref_logits(cfg, 4, s) for s in seqs]
        tables = jnp.asarray([[0, 1, 2, 3, 0, 0, 0, 0],
                              [4, 5, 6, 7, 0, 0, 0, 0]], jnp.int32)
        w = eng._weights
        for start in (0, 8):
            nvalid = [max(0, min(8, len(s) - start)) for s in seqs]
            ids = np.zeros((2, 8), np.int32)
            for b in range(2):
                ids[b, :nvalid[b]] = seqs[b][start:start + nvalid[b]]
            last, _, eng.cache = eng._prefill_jit(
                w, jnp.asarray(ids), jnp.full((2,), start, jnp.int32),
                jnp.asarray(nvalid, jnp.int32), tables, eng.cache)
            got = np.asarray(last @ w["head"])
            for b in range(2):
                np.testing.assert_allclose(
                    got[b], want[b][start + nvalid[b] - 1], atol=TOL, rtol=0)

    def test_served_tokens_are_the_references_argmax(self):
        """Whole requests through step(): each served token is the
        reference's first choice at its position (gap 0 in float32)."""
        cfg = tiny_cfg()
        eng = _engine(latent_model(5), max_slots=3)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 96, n).tolist() for n in (5, 12, 19)]
        rids = [eng.submit(p) for p in prompts]
        done = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            ids = done[rid]
            ref = _ref_logits(cfg, 5, ids[:-1])
            gap = ref[len(p) - 1:].max(-1) - np.take_along_axis(
                ref[len(p) - 1:], np.asarray(ids[len(p):])[:, None], 1)[:, 0]
            assert gap.max() <= TOL, gap


def test_absorbed_attention_equals_up_projected():
    """Scores against the latent row with the query multiplied into the
    latent space equal scores against up-projected keys; the weighted sum
    of latent rows through W_vb equals the weighted sum of values."""
    rng = np.random.default_rng(2)
    nh, dn, dr, dv, r, t = 4, 16, 8, 16, 32, 11
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q_nope, q_r, ckv, k_r = f(nh, dn), f(nh, dr), f(t, r), f(t, dr)
    wkb, wvb = f(nh, dn, r), f(nh, r, dv)
    k = jnp.concatenate([jnp.einsum("tr,hdr->thd", ckv, wkb),
                         jnp.broadcast_to(k_r[:, None], (t, nh, dr))], -1)
    v = jnp.einsum("tr,hrv->thv", ckv, wvb)
    p = jax.nn.softmax(jnp.einsum(
        "hd,thd->ht", jnp.concatenate([q_nope, q_r], -1), k) * 0.2, -1)
    want = jnp.einsum("ht,thv->hv", p, v)
    width = 128
    row = jnp.concatenate([ckv, k_r, jnp.zeros((t, width - r - dr))], -1)
    pool = jnp.zeros((1, 1, 3, 8, width)).at[0, 0, :2].set(
        jnp.pad(row, ((0, 16 - t), (0, 0))).reshape(2, 8, width))
    qf = jnp.concatenate([jnp.einsum("hd,hdr->hr", q_nope, wkb), q_r,
                          jnp.zeros((nh, width - r - dr))], -1)
    o_lat = _mla_gather(qf[None], pool, jnp.asarray([[0, 1]]),
                        jnp.asarray([t]), 0, r, 0.2)
    got = jnp.einsum("hr,hrv->hv", o_lat[0], wvb)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("group", [1, 2, 4])
def test_mla_kernel_in_interpret_mode_equals_the_gather_path(group):
    """Ragged lengths, one of them ending across a page edge, one of one
    token; the last table entry is garbage past every length."""
    rng = np.random.default_rng(0)
    L, P, page, w, rank, H, B = 2, 12, 8, 256, 128, 4, 3
    pool = jnp.asarray(rng.standard_normal((L, 1, P + 1, page, w)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, w)), jnp.float32)
    tables = jnp.concatenate(
        [jnp.asarray(rng.permutation(P).reshape(B, 4), jnp.int32),
         jnp.full((B, 1), 999, jnp.int32)], 1)
    lens = jnp.asarray([1, 9, 29], jnp.int32)
    got = mla_paged_attention(q, pool, tables, lens, layer=1, rank=rank,
                              scale=0.1, group=group, interpret=True)
    want = _mla_gather(q, pool, jnp.clip(tables, 0, P), lens, 1, rank, 0.1)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


class TestRouterAndExperts:
    def test_router_equals_the_references(self):
        """Groups, bias, normalisation and the factor 2.5: the same
        experts (as sets, in the same order here) and the same weights."""
        cfg = tiny_cfg(router_experts=32, n_group=8, topk_group=4,
                       num_experts_per_tok=8)
        rng = np.random.default_rng(4)
        logits = jnp.asarray(rng.standard_normal((50, 32)), jnp.float32)
        bias = jnp.asarray(0.3 * rng.standard_normal(32), jnp.float32)
        idx, w = grouped_sigmoid_route(
            logits, bias, top_k=8, n_group=8, topk_group=4, scale=2.5)
        ridx, rw = mla_moe.route(logits, bias, cfg)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_allclose(w, rw, atol=1e-6, rtol=0)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
        # the bias moved the choice for some token, and never the weight
        plain, _ = grouped_sigmoid_route(
            logits, 0 * bias, top_k=8, n_group=8, topk_group=4, scale=2.5)
        assert (np.asarray(plain) != np.asarray(idx)).any()
        # only experts of the four kept groups are chosen
        assert all(len({int(e) // 4 for e in row}) <= 4
                   for row in np.asarray(idx))

    def test_sixteen_shares_and_the_shared_expert_once_add_up(self):
        """Each of 16 ranks holds one expert of 16 and computes its part
        of the routed sum for the tokens sent to it; the parts plus the
        shared expert, counted once, are the uncut layer of the
        reference (all 16 held)."""
        cfg = tiny_cfg(n_routed_experts=16, experts_held=[0, 16])
        w = mla_moe.make_weights(cfg, 7, jnp.float32)
        p = jax.tree_util.tree_map(lambda a: a[0], w["moe"])
        rng = np.random.default_rng(5)
        h2 = jnp.asarray(rng.standard_normal((37, 64)), jnp.float32)
        want = mla_moe.experts_part(p, h2, cfg, "f32")
        logits = jnp.matmul(h2, p["router"],
                            precision=jax.lax.Precision.HIGHEST)
        idx, wt = grouped_sigmoid_route(
            logits, p["bias"], top_k=4, n_group=4, topk_group=2, scale=2.5)
        got = mla_moe.swiglu(h2, p["sg"], p["su"], p["sd"], "f32")
        pairs = 0
        for e in range(16):
            y, st = held_expert_ffn(h2, idx, wt, p["eg"][e:e + 1],
                                    p["eu"][e:e + 1], p["ed"][e:e + 1],
                                    (e, e + 1))
            got = got + y
            pairs += int(st[0])
            assert int(st[3]) == 0
        assert pairs == 37 * 4          # every pair computed exactly once
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)

    def test_blocks_of_pairs_loop_until_none_is_left(self):
        """A block smaller than the pairs there are: the loop takes as
        many blocks as it needs and the result does not change."""
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
        f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s),
                                   jnp.float32)
        eg, eu, ed = f(4, 16, 8), f(4, 16, 8), f(4, 8, 16)
        idx = jnp.asarray(rng.integers(0, 8, (40, 3)), jnp.int32)
        wt = jnp.asarray(rng.random((40, 3)), jnp.float32)
        whole, s1 = held_expert_ffn(x, idx, wt, eg, eu, ed, (2, 6))
        parts, s2 = held_expert_ffn(x, idx, wt, eg, eu, ed, (2, 6),
                                    block_rows=8)
        np.testing.assert_allclose(parts, whole, atol=1e-5, rtol=0)
        np.testing.assert_array_equal(s1, s2)
        local = (np.asarray(idx) >= 2) & (np.asarray(idx) < 6)
        assert int(s1[0]) == local.sum() > 8 and int(s1[3]) == 0

    def test_a_pair_the_gemm_is_not_handed_counts_as_dropped(
            self, monkeypatch):
        """The fourth count is what the grouped GEMM was handed against
        the pairs there are, not a constant: a loop one block short
        leaves the last block's pairs counted as dropped, and the model
        kind raises on such a reading."""
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
        f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s),
                                   jnp.float32)
        eg, eu, ed = f(4, 16, 8), f(4, 16, 8), f(4, 8, 16)
        idx = jnp.asarray(rng.integers(0, 8, (40, 3)), jnp.int32)
        wt = jnp.asarray(rng.random((40, 3)), jnp.float32)
        loop = jax.lax.fori_loop
        monkeypatch.setattr(jax.lax, "fori_loop",
                            lambda lo, hi, body, init: loop(lo, hi - 1,
                                                            body, init))
        _, st = held_expert_ffn(x, idx, wt, eg, eu, ed, (2, 6),
                                block_rows=8)
        monkeypatch.undo()
        n_local = int(((np.asarray(idx) >= 2) & (np.asarray(idx) < 6)).sum())
        assert int(st[0]) == n_local
        assert int(st[3]) == n_local - 8 * (-(-n_local // 8) - 1) > 0
        arch = latent_model(2).serving_arch()
        with pytest.raises(RuntimeError, match="routing drops nothing"):
            arch.note_stats(np.asarray(st))
        attrs = arch.note_stats(np.asarray([12, 5, 4, 0]))
        assert attrs["dropped_tokens"] == 0 and attrs["local_pairs"] == 12
        assert attrs["expert_load_mean"] == 12 / 8    # eight experts held


class TestEngineFeaturesOnTheLatentModel:
    def _prompts(self):
        rng = np.random.default_rng(11)
        return [rng.integers(1, 96, n).tolist() for n in (9, 14, 6, 17)]

    def _serve(self, **kw):
        eng = _engine(latent_model(2), **kw)
        rids = [eng.submit(p) for p in self._prompts()]
        done = eng.run_until_complete()
        return eng, [done[r] for r in rids]

    def test_cache_is_one_latent_pool_and_names_no_k_or_v(self):
        from paddle_tpu.models.latent_moe import LatentMoEForCausalLM

        # the model drawn from its own seed (no harness weights)
        eng = _engine(LatentMoEForCausalLM(mla_moe.model_config(tiny_cfg()),
                                           seed=1))
        rid = eng.submit([5, 6, 7])
        assert len(eng.run_until_complete()[rid]) == 3 + 8
        assert eng.cache_names == ("latent",)
        (pool,) = eng.cache
        assert pool.shape == (3, 1, eng.pool.num_pages + 1, 8, 128)
        with pytest.raises(AttributeError, match="no 'k' pool"):
            eng.kc

    @pytest.mark.parametrize("feature, kw", [
        ("int8_kv", dict(int8_kv=True)),
        ("int8_weights", dict(int8_weights=True)),
        ("draft_model", dict(draft_model=object())),
        ("group prefill", dict(prefill_chunk=None)),
    ])
    def test_refuses_by_name(self, feature, kw):
        with pytest.raises(ValueError, match=feature):
            _engine(latent_model(2), **kw)

    def test_preemption_recompute_and_swap_are_bitwise(self):
        _, want = self._serve(max_slots=4)
        for policy in ("recompute", "swap"):
            eng, got = self._serve(max_slots=4, num_pages=6,
                                   preempt_policy=policy)
            assert eng.preemptions > 0
            if policy == "swap":
                assert eng.swaps_in == eng.swaps_out > 0
            assert got == want
            assert eng.pool.available == eng.pool.num_pages

    def test_prefix_cache_reuses_latent_pages_bitwise(self):
        system = list(range(1, 17))             # two full pages
        eng = _engine(latent_model(2), enable_prefix_cache=True)
        plain = _engine(latent_model(2))
        outs = []
        for e in (eng, plain):
            got = []
            for tail in ([20, 21], [30, 31, 32]):
                rid = e.submit(system + tail)
                got.append(e.run_until_complete()[rid])
            outs.append(got)
        assert eng.prefix_cache_hits >= 2 and outs[0] == outs[1]

    def test_extract_inject_hands_the_latent_rows_over(self):
        from paddle_tpu.inference.fleet.wire import (request_from_wire,
                                                     request_to_wire)

        _, want = self._serve()
        src = _engine(latent_model(2), prefill_only=True)
        dst = _engine(latent_model(2))
        prompts = self._prompts()[:2]
        for p in prompts:
            src.submit(p)
        for _ in range(4):
            src.step()
        for i in range(2):
            req = src.extract(i)
            assert set(req.swapped) == {"latent", "n", "prefill_pos",
                                        "length"}
            dst.inject(request_from_wire(request_to_wire(req)))
        done = dst.run_until_complete()
        assert [done[r] for r in sorted(done)] == want[:2]

    def test_prefix_pages_export_and_import(self):
        system = list(range(1, 17))
        a = _engine(latent_model(2), enable_prefix_cache=True)
        a.submit(system + [40])
        a.run_until_complete()
        b = _engine(latent_model(2), enable_prefix_cache=True)
        # 16 + 1 + 8 served tokens: three full pages were registered
        assert b.import_prefix_pages(a.export_prefix_pages()) == 3
        assert b.prefix_match_pages(system + [41]) == 2

    def test_warmup_fills_program_bytes_and_the_pool_stays_in_place(self):
        """Each program donates the one latent pool and gets it back in
        the same buffer (alias = the pool's bytes); no temporary is as
        large as the pool."""
        eng = _engine(latent_model(2), max_slots=4)
        eng.warmup()
        pool = eng.cache[0].nbytes
        assert set(eng.program_bytes) == {"decode", "decode_r1", "prefill",
                                          "prefill_r1", "prefill_r2"}
        for name, nb in eng.program_bytes.items():
            assert nb["alias"] >= pool, (name, nb, pool)

    def test_prefill_kernel_in_interpret_mode_equals_the_gather_path(self):
        """Ragged starts (0, a page edge, mid-page), a partial chunk and
        a row with nothing to prefill (skipped: zeros)."""
        from paddle_tpu.ops.pallas.decode_attention import (
            _mla_prefill_gather, mla_paged_prefill)

        rng = np.random.default_rng(0)
        L, P, page, w, rank, H, B, c = 2, 14, 8, 256, 128, 4, 4, 8
        pool = jnp.asarray(rng.standard_normal((L, 1, P + 1, page, w)),
                           jnp.float32)
        q = jnp.asarray(rng.standard_normal((B, H * c, w)), jnp.float32)
        tables = jnp.concatenate(
            [jnp.asarray(rng.permutation(P)[:12].reshape(3, 4), jnp.int32),
             jnp.zeros((3, 1), jnp.int32)], 1)
        tables = jnp.concatenate([tables, tables[:1]], 0)
        pos0 = jnp.asarray([0, 8, 19, 0], jnp.int32)
        nv = jnp.asarray([8, 8, 5, 0], jnp.int32)
        want = _mla_prefill_gather(q, pool, tables, pos0, 1, rank, 0.1, c)
        for hb, group in ((1, 1), (2, 2), (4, 4)):
            got = mla_paged_prefill(q, pool, tables, pos0, nv, layer=1,
                                    rank=rank, scale=0.1, chunk=c,
                                    heads_block=hb, group=group,
                                    interpret=True)
            np.testing.assert_allclose(got[:3], want[:3], atol=3e-6,
                                       rtol=0)
            assert float(jnp.abs(got[3]).max()) == 0.0

    def test_expert_counts_reach_the_spans_and_counters(self):
        import paddle_tpu.telemetry as telemetry
        from paddle_tpu.telemetry import trace

        telemetry.enable()
        trace.enable()
        trace.reset()
        try:
            eng, _ = self._serve()
        finally:
            events = trace.events()
            trace.disable()
        ticks = [e["attrs"] for e in events if e.get("ph") == "X"
                 and e["name"] in ("decode_tick", "prefill_tick")
                 and "local_pairs" in (e.get("attrs") or {})]
        assert {e["name"] for e in events if e.get("ph") == "X"} >= {
            "decode_tick", "prefill_tick"}
        assert ticks and all(t["dropped_tokens"] == 0 for t in ticks)
        assert sum(t["local_pairs"] for t in ticks) > 0
        for t in ticks:
            assert 0 <= t["expert_load_mean"] <= t["expert_load_max"]
            assert t["experts_hit"] <= 2 * 8    # two expert layers of 8
        snap = telemetry.snapshot()
        pairs = snap["counters"]["serving_moe_local_pairs_total"]
        assert sum(pairs.values()) >= sum(t["local_pairs"] for t in ticks)
        assert sum(snap["counters"].get("serving_moe_dropped_total",
                                        {"": 0}).values()) == 0
        kinds = snap["gauges"]["serving_cache_bytes"]
        assert len(kinds) == 2
        assert eng.program_bytes == {}          # no warmup here


class TestLaunchAhead:
    """ISSUE 36 on the latent kind: a tick's tokens go from program to
    program on the device, and its expert counts still ride behind them
    to the host, one tick later."""

    def test_tokens_carried_on_the_device_are_the_references_argmax(self):
        """Requests that join a running batch mid-flight: each served
        token is the float32 reference's first choice at its position,
        whether the host or the device carried it into the next tick."""
        cfg = tiny_cfg()
        eng = _engine(latent_model(5), max_slots=3)
        rng = np.random.default_rng(36)
        prompts = [rng.integers(1, 96, n).tolist() for n in (6, 13, 4, 10)]
        rids = [eng.submit(p) for p in prompts[:2]]
        for _ in range(3):
            eng.step()
        rids.append(eng.submit(prompts[2]))        # joins mid-flight
        eng.step()
        rids.append(eng.submit(prompts[3]))        # waits for a slot
        done = eng.run_until_complete()
        assert eng.decode_ticks["ahead"] > eng.decode_ticks["settled"]
        for rid, p in zip(rids, prompts):
            ids = done[rid]
            assert len(ids) == len(p) + 8
            ref = _ref_logits(cfg, 5, ids[:-1])
            gap = ref[len(p) - 1:].max(-1) - np.take_along_axis(
                ref[len(p) - 1:], np.asarray(ids[len(p):])[:, None], 1)[:, 0]
            assert gap.max() <= TOL, gap

    def test_a_ticks_expert_counts_land_on_the_span_that_launched_it(self):
        """The counts come to the host with the tokens, a step after the
        tick's span closed: every ``decode_tick`` span still gets its
        own, the last one at the drain."""
        from paddle_tpu.telemetry import trace

        trace.enable()
        trace.reset()
        try:
            eng = _engine(latent_model(2))
            for p in ([3, 4, 5, 6, 7], [9, 8, 7]):
                eng.submit(p)
            eng.run_until_complete()
            events = trace.events()
        finally:
            trace.disable()
        ticks = [e["attrs"] for e in events
                 if e.get("ph") == "X" and e["name"] == "decode_tick"]
        assert len(ticks) == sum(eng.decode_ticks.values()) == 7
        assert [t["ahead"] for t in ticks] == [0] + [1] * 6
        # two rows x four pairs a token x two expert layers, less what
        # went to experts this chip does not hold
        assert all(0 < t["local_pairs"] <= 16 and t["dropped_tokens"] == 0
                   for t in ticks)

    @pytest.mark.parametrize("policy", ("recompute", "swap"))
    def test_preemption_streams_lose_and_repeat_nothing(self, policy):
        """The latent pool under pressure with ticks in flight: every
        request's stream is its roomy run's, token for token."""
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 96, n).tolist() for n in (9, 14, 6, 17)]

        def serve(**kw):
            eng = _engine(latent_model(2), max_slots=4, **kw)
            seen = {}
            rids = [eng.submit(p, on_token=lambda r, t: seen.setdefault(
                r, []).append(t)) for p in prompts]
            done = eng.run_until_complete()
            assert all(done[r] == p + seen[r]
                       for r, p in zip(rids, prompts))
            return eng, [seen[r] for r in rids]

        _, want = serve()
        eng, got = serve(num_pages=6, preempt_policy=policy)
        assert eng.preemptions > 0 and got == want
        assert eng.discarded_tokens == {"ended": 0, "withdrawn": 0}


class TestPrefillPassWidth:
    """ISSUE 33, the latent kind: its chunk attention takes rows in
    groups and every token of a pass goes through the router, so a
    narrower pass hands the expert layers fewer pad tokens; a real row
    comes to the same."""

    @pytest.fixture(scope="class")
    def served(self):
        """(model, its subject row through the full-width pass alone)."""
        import _prefill_width as pw

        model = latent_model(6)
        return model, pw.serve_beside(model, 0, True)

    @pytest.mark.parametrize("neighbours", [0, 1, 3, 7])
    def test_a_row_does_not_depend_on_the_rows_beside_it(self, served,
                                                         neighbours):
        import _prefill_width as pw

        pw.assert_same_row(served[0], neighbours, served[1])

    def test_a_narrow_pass_routes_fewer_pad_tokens(self):
        """The counts a pass reports are over the rows it computed: one
        prompt of one chunk through a one-row pass routes a chunk's
        tokens, through the full-width pass eight rows of them."""
        import _prefill_width as pw

        pairs = {}
        for full in (False, True):
            eng = _engine(latent_model(6), max_slots=pw.SLOTS,
                          prefill_only=True)       # no decode tick's counts
            if full:
                eng._pass_rows = (pw.SLOTS,)
            seen = []
            note = eng._arch.note_stats
            eng._arch.note_stats = lambda st: seen.append(
                [int(v) for v in st]) or note(st)
            eng.submit(list(range(1, 9)))
            eng.step()
            eng._drain_stats()
            (stats,) = seen
            pairs[full] = stats[0]
            assert stats[3] == 0                      # nothing dropped
        assert 0 < pairs[False] < pairs[True]
