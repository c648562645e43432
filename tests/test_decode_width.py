"""A decode tick is as wide as the smallest step of the engine's decode
ladder (1, 8, ``max_slots``) that holds its live rows, and every model
kind pads it with the trash row: length 0, every page the trash page, the
trash slot (docs/SERVING.md "How wide a decode tick is"). A row serves
what it serves alone whatever the width; a padded row writes nothing but
the trash page and slot; every width is compiled by ``warmup()``; and the
full-width program is the one the engine had before the ladder."""
import numpy as np
import pytest

import _decode_width as dw

KINDS = ("dense", "latent", "hybrid")


def _model(kind, seed=5):
    if kind == "dense":
        from test_serving import _tiny_model

        return _tiny_model(seed)
    if kind == "latent":
        from _latent_tiny import latent_model

        return latent_model(seed)
    from _hybrid_tiny import hybrid_model

    return hybrid_model(seed)


@pytest.mark.parametrize("slots,want", [
    (1, (1,)), (6, (1, 6)), (8, (1, 8)), (9, (1, 8, 9)), (32, (1, 8, 32)),
    (64, (1, 8, 64))])
def test_ladder(slots, want):
    from paddle_tpu.inference.serving import _decode_row_ladder

    assert _decode_row_ladder(slots) == want


class TestEveryKind:
    @pytest.fixture(scope="class", params=KINDS)
    def alone(self, request):
        """(model, prompts, each prompt's ids served alone) of a kind."""
        model = _model(request.param)
        ps = dw.prompts(dw.SLOTS)
        return model, ps, dw.served_alone(model, ps)

    @pytest.mark.parametrize("n", dw.LIVE)
    def test_rows_serve_what_they_serve_alone(self, alone, n):
        """n rows in lockstep: every tick at the smallest step that holds
        n, launched ahead after the first, each writing only its live
        rows' positions and the trash page and slot; the ids equal."""
        model, ps, want = alone
        got, seen = dw.served_together(model, ps[:n])
        assert got == want[:n]
        width = next(b for b in (1, 8, dw.SLOTS) if b >= n)
        assert {(b, live) for b, live, _ in seen} == {(width, n)}
        assert [ahead for *_, ahead in seen] == [False] + [True] * (
            len(seen) - 1)

    def test_launch_ahead_across_a_change_of_width(self, alone):
        """8 -> 1, 1 -> 8 and 32 -> 8, each tick launched from the tick
        before still on the device: the rows that stay serve what they
        serve alone."""
        model = alone[0]
        ps, got, seen = dw.width_changes(model)
        assert got == dw.served_alone(model, ps, new=8)
        steps = {(a[0], b[0]) for a, b in zip(seen, seen[1:]) if b[2]}
        assert {(8, 1), (1, 8), (32, 8)} <= steps


@pytest.mark.parametrize("kind", KINDS)
def test_a_padded_tick_equals_the_tick_without_its_pads(kind):
    """Two live rows at the 8-row step against the same two rows alone
    in a 2-row program, from the same pools: the padded rows' logits are
    finite, the live rows' logits, tokens and every page and slot but the
    trash ones agree; a latent model's padded rows drop no routed pair."""
    eng = dw.engine(_model(kind))
    (lg, nxt, pools), (lg0, nxt0, pools0) = (
        dw.tick_with_and_without_padding(eng, 2, 8))
    assert lg.shape[0] == 8 and np.isfinite(lg).all()
    np.testing.assert_allclose(lg[:2], lg0, atol=1e-5, rtol=1e-5)
    assert (nxt[:2] == nxt0[:2]).all()
    paged = len(pools) - (len(eng.cache) - eng._n_paged)
    for k, (a, b) in enumerate(zip(pools, pools0)):
        assert np.isfinite(a).all()
        if k < paged:                   # [L, H, pages, page, width]
            a, b = a[:, :, :eng._trash_page], b[:, :, :eng._trash_page]
        else:                           # [L, slots + 1, ...]
            a, b = a[:, :eng._trash_slot], b[:, :eng._trash_slot]
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    if kind == "latent":
        pairs, _, _, dropped = (int(v) for v in nxt[eng.max_slots:])
        pairs0, _, _, dropped0 = (int(v) for v in nxt0[eng.max_slots:])
        assert dropped == dropped0 == 0
        # the padded rows' pairs are routed beside the live rows' pairs
        assert pairs >= pairs0 > 0


def test_the_latent_kernel_on_a_padded_row():
    """``mla_paged_attention`` (interpret mode) with a padded row beside
    live ones: one position, every table entry the trash page. Its
    output is finite and the gather path's, and the live rows' outputs
    are what they are without it."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.decode_attention import (_mla_gather,
                                                        mla_paged_attention)

    rng = np.random.default_rng(39)
    L, P, page, w, rank, H = 2, 12, 8, 256, 128, 4
    pool = jnp.asarray(rng.standard_normal((L, 1, P + 1, page, w)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, H, w)), jnp.float32)
    live = jnp.asarray(rng.permutation(P)[:8].reshape(2, 4), jnp.int32)
    tables = jnp.concatenate([live, jnp.full((1, 4), P, jnp.int32)])
    lens = jnp.asarray([9, 29, 1], jnp.int32)
    got = mla_paged_attention(q, pool, tables, lens, layer=1, rank=rank,
                              scale=0.1, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    want = _mla_gather(q, pool, tables, lens, 1, rank, 0.1)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    alone = mla_paged_attention(q[:2], pool, live, lens[:2], layer=1,
                                rank=rank, scale=0.1, interpret=True)
    np.testing.assert_allclose(got[:2], alone, atol=2e-6, rtol=0)


def test_every_width_is_compiled_before_service():
    """After ``warmup()`` the decode programs are the ladder's three; a
    seeded run of bursts, lulls and cancellations uses all three and
    compiles nothing more. Each ``decode_tick`` span names its width
    beside its live rows, and the counter counts ticks by width."""
    import paddle_tpu.telemetry as telemetry
    from paddle_tpu.telemetry import trace

    eng = dw.engine(_model("dense"))
    eng.warmup()
    assert {n for n in eng.program_bytes if n.startswith("decode")} == {
        "decode", "decode_r1", "decode_r8"}
    jits = (eng._decode_jit, eng._prefill_jit, eng._first_token_jit)
    before = [j._cache_size() for j in jits]
    assert before == [3, 6, 1]
    rng = np.random.default_rng(39)
    telemetry.enable()
    trace.enable()
    trace.reset()
    try:
        rids = []
        for tick in range(90):
            rate = 4.0 if tick < 12 else 0.3
            for _ in range(rng.poisson(rate)):
                n = int(rng.integers(3, 30))
                rids.append(eng.submit(rng.integers(1, 96, n).tolist()))
            if rids and rng.random() < 0.15:
                eng.cancel(rids[int(rng.integers(len(rids)))])
            eng.step()
        eng.run_until_complete()
        events = trace.events()
        counted = telemetry.snapshot()["counters"][
            "serving_decode_rows_total"]
    finally:
        trace.disable()
        trace.reset()
        telemetry.disable()
        telemetry.reset()
    assert [j._cache_size() for j in jits] == before
    assert not [e for e in events if e["name"] == "xla_compile"]
    ticks = [e["attrs"] for e in events
             if e["name"] == "decode_tick" and e.get("ph") == "X"]
    assert {t["rows"] for t in ticks} == {1, 8, dw.SLOTS}
    for t in ticks:
        assert t["rows"] == next(b for b in (1, 8, dw.SLOTS)
                                 if b >= t["live"])
    assert counted == {f"rows={b}": float(sum(t["rows"] == b
                                                for t in ticks))
                       for b in (1, 8, dw.SLOTS)}


#: sha256 of ``decode_program_text()`` (the greedy decode tick at
#: ``max_slots`` rows, lowered on the CPU) of each serving configuration at
#: depth 2 and vocabulary 1,024, everything else as the benchmark runs it:
#: the digests of the engine before its decode ladder
FULL_WIDTH_DIGESTS = {
    "internlm2-1.8b-serve":
        "4d967b14dcb4fd43c0257032508e8984d61c1905a8d80628629ce617b8db9af8",
    "dots-vlm1-ep16-serve":
        "b42081c5cedddf4ce290fdab7fbe73e467010265a5eeb572e06eb941de049174",
}


@pytest.mark.parametrize("name", sorted(FULL_WIDTH_DIGESTS))
def test_the_full_width_program_is_unchanged(name):
    cfg = dw.config(name, num_hidden_layers=2, vocab_size=1024)
    text = dw.cell_engine(cfg).decode_program_text()
    assert dw.digest(text) == FULL_WIDTH_DIGESTS[name]


@pytest.mark.parametrize("family", ["dense_gqa", "mla_moe"])
def test_weights_never_made_give_the_program_real_ones_give(family):
    """The digest above is of an engine on weights that exist only as
    shapes: at a tiny size, its program is the one real weights give."""
    from harness import families

    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    if family == "mla_moe":
        from _latent_tiny import tiny_cfg

        cfg = tiny_cfg(torch_dtype="bfloat16")
    else:
        cfg = dw.config(
            "internlm2-1.8b-serve", hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=96,
            deployment={"engine": dict(max_slots=4, page_size=8,
                                       max_seq_len=64, prefill_chunk=8,
                                       max_new_tokens=4)})
    real = ContinuousBatchingEngine(families.of(cfg).serving_model(cfg, 0),
                                    **cfg["deployment"]["engine"])
    assert (dw.cell_engine(cfg).decode_program_text()
            == real.decode_program_text())
