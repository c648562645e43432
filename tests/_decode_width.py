"""Decode ticks of every width of the engine's decode ladder, for
tests/test_decode_width.py: requests served together against the same
requests served alone, what one tick may write, a tick with and without
its padded rows, and the text of the full-width decode program of a
benchmark configuration, built on weights that are never made."""
import hashlib
import json
import math
import os
import sys
from types import SimpleNamespace
from unittest import mock

import numpy as np

from paddle_tpu.inference.serving import ContinuousBatchingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

SLOTS, PAGE, CHUNK, NEW = 32, 8, 8, 5
#: live rows a test serves together: a step of the ladder (1, 8, 32),
#: one above a step, and one below
LIVE = (1, 2, 8, 9, 32)


def engine(model, new=NEW, **kw):
    return ContinuousBatchingEngine(
        model, max_slots=SLOTS, page_size=PAGE, max_seq_len=64,
        prefill_chunk=CHUNK, max_new_tokens=new, **kw)


def prompts(n, seed=0):
    """``n`` prompts of two chunks each (9-16 tokens): rows admitted
    together finish their prefill on one tick and decode in lockstep."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, int(rng.integers(9, 17))).tolist()
            for _ in range(n)]


def served_alone(model, ps, **kw):
    """Each prompt's served ids, one request in the engine at a time."""
    eng = engine(model, **kw)
    out = []
    for p in ps:
        rid = eng.submit(p)
        out.append(eng.run_until_complete()[rid])
    return out


def watch_ticks(eng):
    """Record (width, live rows, launched ahead) of every decode launch,
    and check, tick by tick, that the program wrote nothing but each
    live row's one position and the trash page (the trash slot for a
    leaf addressed by slot)."""
    seen, jit = [], eng._decode_jit

    def launch(w, tokens, lens, tables, cache, *rest):
        before = [np.array(leaf, copy=True) for leaf in _leaves(cache)]
        nxt, cache = jit(w, tokens, lens, tables, cache, *rest)
        lens_np, tables_np = np.asarray(lens), np.asarray(tables)
        live = tables_np[:, 0] != eng._trash_page
        slots = rest[-1]
        assert_writes_only(eng, before, cache, lens_np[live],
                           tables_np[live],
                           None if slots is None else np.asarray(slots)[live])
        src = np.asarray(rest[5])
        seen.append((int(tokens.shape[0]), int(live.sum()),
                     bool((src >= 0).any())))
        return nxt, cache

    eng._decode_jit = launch
    return seen


def _leaves(cache):
    return [leaf for pool in cache
            for leaf in (pool if isinstance(pool, tuple) else (pool,))]


def assert_writes_only(eng, before, cache, lens, tables, slots):
    """Every leaf of ``cache`` equals ``before`` but at each live row's
    position ``lens`` (its page ``tables[:, lens // page]``) and on the
    trash page; a leaf addressed by slot, but at the live ``slots`` and
    the trash slot."""
    n_paged = eng._n_paged
    paged = sum(len(p) if isinstance(p, tuple) else 1
                for p in cache[:n_paged])
    ok_page = np.zeros((eng._trash_page + 1, eng.page), bool)
    ok_page[eng._trash_page] = True
    ok_page[tables[np.arange(len(lens)), lens // eng.page],
            lens % eng.page] = True
    for k, (old, new) in enumerate(zip(before, _leaves(cache))):
        new = np.asarray(new)
        if k < paged:        # [L, H, pages, page, width]
            changed = (old != new).any(axis=(0, 1, 4))
            assert not (changed & ~ok_page).any(), (
                k, np.argwhere(changed & ~ok_page))
        else:                # [L, slots + 1, ...]
            changed = (old != new).reshape(
                old.shape[0], old.shape[1], -1).any(axis=(0, 2))
            ok = np.zeros(old.shape[1], bool)
            ok[eng._trash_slot] = True
            ok[slots] = True
            assert not (changed & ~ok).any(), np.flatnonzero(changed & ~ok)


def served_together(model, ps, **kw):
    """Each prompt's served ids with every prompt submitted at once, and
    (width, live, ahead) of each decode launch; every tick checked by
    :func:`watch_ticks`."""
    eng = engine(model, **kw)
    seen = watch_ticks(eng)
    rids = [eng.submit(p) for p in ps]
    done = eng.run_until_complete()
    return [done[r] for r in rids], seen


def width_changes(model, **kw):
    """Launch-ahead across a change of width: 8 rows, then the one at
    row 5 alone (8 -> 1), then seven newcomers beside it (1 -> 8); and
    32 rows, 24 of them cancelled (32 -> 8). Returns (the survivors'
    prompts, their served ids, (width, live, ahead) of each launch)."""
    eng = engine(model, new=8, **kw)
    seen = watch_ticks(eng)
    first = prompts(8, seed=1)
    rids = [eng.submit(p) for p in first]
    for _ in range(4):                  # two prefill ticks, two decodes
        eng.step()
    for r in rids[:5] + rids[6:]:
        eng.cancel(r)
    eng.step()                          # the one at row 5, alone
    late = [p[:7] for p in prompts(7, seed=2)]   # one chunk: in at once
    rids = [rids[5]] + [eng.submit(p) for p in late]
    keep = {r: p for r, p in zip(rids, [first[5]] + late)}
    done = eng.run_until_complete()
    many = prompts(32, seed=3)
    rids = [eng.submit(p) for p in many]
    for _ in range(4):
        eng.step()
    for r in rids[8:]:
        eng.cancel(r)
    keep.update(zip(rids[:8], many[:8]))
    done.update(eng.run_until_complete())
    eng.cancelled.clear()
    return list(keep.values()), [done[r] for r in keep], seen


def tick_with_and_without_padding(eng, n_live, width):
    """Bring ``n_live`` requests to decoding, then run their next tick
    eagerly twice from the same pools: at ``width`` rows
    (padded with ``_pad_row``) and at ``n_live`` rows (no padding).
    Returns ((logits, next, pools) padded, (logits, next, pools) not)."""
    import jax.numpy as jnp

    for p in prompts(n_live, seed=4):
        eng.submit(p)
    while not all(r is not None and r.generated
                  for r in eng._slots[:n_live]):
        eng.step()
    eng._settle()
    live = [(i, r) for i, r in enumerate(eng._slots) if r is not None]
    assert len(live) == n_live
    seen, head = [], eng._head_logits
    eng._head_logits = lambda w, x: seen.append(head(w, x)) or seen[-1]
    out = []
    for b in (width, n_live):
        rows = [r for _, r in live] + [eng._pad_row] * (b - n_live)
        operands = (
            jnp.asarray([r.generated[-1] for r in rows], jnp.int32),
            jnp.asarray([r.length for r in rows], jnp.int32),
            jnp.asarray(eng._table_rows(rows)), eng.cache,
            *eng._greedy_operands(b), eng._no_tick,
            jnp.full((b,), -1, jnp.int32), False,
            eng._slot_vec([i for i, _ in live], b))
        nxt, cache = eng._decode_step(eng._weights, *operands)
        out.append((np.asarray(seen[-1]), np.asarray(nxt),
                    [np.asarray(leaf) for leaf in _leaves(cache)]))
    eng._head_logits = head
    return out


# ------------------------------------------- the full-width program's text
class _Abstract:
    """A weight that is never made: its shape and dtype (what ``lower``
    reads) and the bytes it would take (what the engine counts)."""

    def __init__(self, sds):
        self.shape, self.dtype = sds.shape, sds.dtype

    @property
    def nbytes(self):
        return math.prod(self.shape) * self.dtype.itemsize


def _abstract(tree):
    import jax

    return jax.tree.map(_Abstract, jax.eval_shape(tree))


def config(name, **over):
    """A benchmark configuration by name, ``over`` its top-level keys."""
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        return dict(json.load(f), **over)


def cell_engine(cfg):
    """The engine of a benchmark configuration ``cfg`` at its widths and
    engine geometry, on weights that are never made: their shapes come
    from ``jax.eval_shape`` of the family's own builders."""
    import jax.numpy as jnp

    from harness import families

    fam = families.of(cfg)
    geometry = cfg["deployment"]["engine"]
    if cfg["family"] == "dense_gqa":
        from paddle_tpu.inference.serving import (DenseDecoderServing,
                                                  _pack_weights_stacked)

        box = {}

        def build():
            model = fam.serving_model(cfg, 0)
            box["config"] = model.config
            return _pack_weights_stacked(model)

        packed = _abstract(build)
        with mock.patch.object(DenseDecoderServing, "pack",
                               lambda self, int8_weights=False: packed):
            return ContinuousBatchingEngine(
                SimpleNamespace(config=box["config"]), **geometry)
    from paddle_tpu.models.latent_moe import LatentMoEForCausalLM

    weights = _abstract(lambda: fam.make_weights(
        cfg, 0, jnp.dtype(cfg["torch_dtype"])))
    model = LatentMoEForCausalLM(fam.model_config(cfg), weights=weights)
    return ContinuousBatchingEngine(model, **geometry)


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()
