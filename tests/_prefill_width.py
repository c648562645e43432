"""A prompt through the chunked prefill beside a chosen number of
neighbours, on a pass as wide as the engine's row ladder makes it or
held at the full ``max_slots`` (ISSUE 33): what the subject's row came
to, for tests/test_serving.py and tests/test_latent_moe.py."""
import numpy as np

from paddle_tpu.inference.serving import ContinuousBatchingEngine

SLOTS, CHUNK, PAGE = 8, 8, 8
SUBJECT = 21            # the subject prompt's length: three passes
#: neighbours' prompt lengths, in arrival order: the first outlasts the
#: subject, so its passes are never one row wide by accident
NEIGHBOURS = (30, 9, 17, 5, 12, 26, 3)


def serve_beside(model, neighbours, full_width, **engine_kw):
    """(the subject's served ids, its rows of every pool leaf right after
    its prefill as [L, H, tokens, width] arrays, the width of each pass
    it rode)."""
    eng = ContinuousBatchingEngine(
        model, max_slots=SLOTS, page_size=PAGE, max_seq_len=64,
        max_new_tokens=4, prefill_chunk=CHUNK, **engine_kw)
    if full_width:
        eng._pass_rows = (SLOTS,)
    widths, jit = [], eng._prefill_jit
    eng._prefill_jit = lambda w, ids, *rest: (
        widths.append(ids.shape[0]) or jit(w, ids, *rest))
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 96, SUBJECT).tolist()
    rid = eng.submit(prompt)
    (req,) = eng._waiting
    for n in NEIGHBOURS[:neighbours]:
        eng.submit(rng.integers(1, 96, n).tolist())
    while req.prefill_pos < SUBJECT:
        eng.step()
    rode = list(widths)
    leaves = [leaf for pool in eng.cache
              for leaf in (pool if isinstance(pool, tuple) else (pool,))]
    rows = [np.asarray(leaf)[:, :, req.pages].reshape(
        leaf.shape[:2] + (-1, leaf.shape[-1]))[:, :, :SUBJECT]
        for leaf in leaves]
    return eng.run_until_complete()[rid], rows, rode


def assert_same_row(model, neighbours, base, **engine_kw):
    """The subject beside ``neighbours`` others on the ladder's passes
    against ``base``, its full-width pass alone. The passes are other
    compiled programs than the full-width one: XLA's CPU dot sums a
    float32 row in another order at another row count (2e-6 here), so
    pool rows are compared to 1e-5 and an int8 code to one step."""
    from paddle_tpu.inference.serving import _pass_row_ladder

    ids, rows, rode = serve_beside(model, neighbours, False, **engine_kw)
    want_ids, want_rows, full = base
    assert set(full) == {SLOTS}
    steps = _pass_row_ladder(SLOTS)
    assert rode[0] == next(n for n in steps if n >= neighbours + 1)
    assert set(rode) <= set(steps) and max(rode) <= rode[0]
    assert ids == want_ids
    for got, want in zip(rows, want_rows):
        assert got.shape == want.shape and got.dtype == want.dtype
        if got.dtype == np.int8:
            off = np.abs(got.astype(np.int32) - want.astype(np.int32))
            assert off.max() <= 1 and (off > 0).mean() < 0.01
        else:
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
