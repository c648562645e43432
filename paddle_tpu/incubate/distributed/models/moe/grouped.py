"""Routed experts that drop nothing, for a layer that is told which
experts it holds.

The dense dispatch of ``MoELayer`` (this package's ``__init__``) sizes an
``[E, C, M]`` buffer by a capacity factor and drops the tokens past it.
Here nothing is dropped and nothing is sized by a factor:

- :func:`grouped_sigmoid_route`: the router of the DeepSeek-V3 family
  (``noaux_tc``): sigmoid scores over ALL experts in float32, selection on
  ``score + bias`` limited to the best ``topk_group`` of ``n_group`` groups
  (a group's score is the sum of its two best), weights from the unbiased
  scores of the chosen, normalised and scaled.
- :func:`held_expert_ffn`: the part of ``sum_i w_i E_i(x)`` that the
  experts ``[lo, hi)`` held HERE give (what expert parallelism asks of one
  rank): the pairs routed to held experts are sorted by expert and run
  through a grouped GEMM (Mosaic ``megablox.gmm`` on a TPU,
  ``jax.lax.ragged_dot`` elsewhere), ``block_rows`` sorted pairs a pass
  under a ``while_loop`` whose trip count is the pairs there are, so any
  skew fits and the memory is one block's. Pairs routed elsewhere add
  nothing: no stand-in for absent ranks.

Pure functions on arrays: the serving model (models/latent_moe.py) calls
them inside the engine's programs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def grouped_sigmoid_route(logits, bias, *, top_k, n_group, topk_group,
                          scale, normalize=True):
    """``logits`` [T, E] float32, ``bias`` [E] float32 -> (idx [T, top_k]
    int32, weights [T, top_k] float32). The bias moves the choice only;
    the weights are the chosen experts' own sigmoid scores."""
    t, e = logits.shape
    score = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = score + bias.astype(jnp.float32)
    grouped = choice.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)  # [T, n_group]
    _, keep = jax.lax.top_k(group_score, topk_group)
    kept = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], keep].set(True)
    choice = jnp.where(jnp.repeat(kept, e // n_group, 1), choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(score, idx, 1)
    if normalize:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def _grouped_matmul(x, w, sizes, tiling):
    """Rows of ``x`` [R, k], sorted by group, times their group's matrix
    of ``w`` [G, k, n] -> [R, n] at x's dtype; rows past ``sum(sizes)``
    come back undefined (the caller masks them)."""
    from .....ops.pallas import on_tpu_device

    if on_tpu_device():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        k, n = w.shape[1:]
        tm, tk, tn = tiling
        with jax.enable_x64(False):
            return gmm(x, w, sizes, preferred_element_type=x.dtype,
                       tiling=(min(tm, x.shape[0]), min(tk, k), min(tn, n)))
    prec = HI if x.dtype == jnp.float32 else None
    return jax.lax.ragged_dot(x, w, sizes, precision=prec,
                              preferred_element_type=x.dtype)


def held_expert_ffn(x, idx, weights, wg, wu, wd, held, *, layer=None,
                    block_rows=None, tiling=None):
    """The held experts' share of the routed sum.

    ``x`` [T, h]; ``idx``/``weights`` [T, k] from the router over ALL
    experts; ``wg``/``wu`` [n_held, h, m] and ``wd`` [n_held, m, h] are
    the swiglu experts ``held = (lo, hi)``. With ``layer`` (an int or a
    traced scalar) the three are the stacks of every layer's experts,
    [L, n_held, ...], read where they lie: the grouped GEMM is handed the
    whole stack as L * n_held groups of which only this layer's have
    rows, so no layer's 1.4 GB of experts is sliced out (a copy a tick
    when the slab comes as a scan's ``xs``: a custom call takes no fused
    slice). Returns (y [T, h] float32, stats int32[4] = local pairs,
    experts hit, the fullest expert's pairs, and the local pairs the
    grouped GEMM was NOT handed: the pairs less the group sizes it was
    given, summed block by block (0 while the blocks cover the sorted
    pairs; a short loop or a wrong size shows here))."""
    lo, hi = held
    n_held = hi - lo
    if layer is not None:
        n_all = wg.shape[0] * n_held
        wg, wu, wd = (w.reshape((n_all,) + w.shape[2:])
                      for w in (wg, wu, wd))
        first = jnp.asarray(layer, jnp.int32) * n_held
    t, k = idx.shape
    pairs = t * k
    rows = min(pairs, block_rows or max(512, t))
    rows += -rows % 8
    # a visit of an expert's matrices computes a whole tile of rows: a
    # decode tick's few pairs an expert take the small tile
    tiling = tiling or ((512 if rows >= 2048 else 64), 1024, 1024)
    flat = idx.reshape(-1)
    local = (flat >= lo) & (flat < hi)
    key = jnp.where(local, flat - lo, n_held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n_local = ends[-1]
    pad = jnp.concatenate([order, jnp.zeros((rows,), jnp.int32)])
    wflat = weights.reshape(-1).astype(jnp.float32)

    def block(b, carry):
        y, handed = carry
        r0 = b * rows
        pair = jax.lax.dynamic_slice(pad, (r0,), (rows,))
        valid = r0 + jnp.arange(rows, dtype=jnp.int32) < n_local
        tok = pair // k
        held_sizes = sizes = (jnp.clip(ends, r0, r0 + rows)
                              - jnp.clip(starts, r0, r0 + rows)
                              ).astype(jnp.int32)
        if layer is not None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n_all,), jnp.int32), sizes, (first,))
        xs = x[tok]
        mid = (jax.nn.silu(_grouped_matmul(xs, wg, sizes, tiling))
               * _grouped_matmul(xs, wu, sizes, tiling))
        out = _grouped_matmul(mid, wd, sizes, tiling).astype(jnp.float32)
        out = jnp.where(valid[:, None], out * wflat[pair][:, None], 0.0)
        y = y.at[jnp.where(valid, tok, t)].add(out, mode="drop")
        return y, handed + jnp.sum(held_sizes, dtype=jnp.int32)

    carry = (jnp.zeros((t, x.shape[1]), jnp.float32), jnp.int32(0))
    if rows >= pairs:                    # one block holds every pair
        y, handed = block(0, carry)
    else:
        y, handed = jax.lax.fori_loop(0, (n_local + rows - 1) // rows,
                                      block, carry)
    stats = jnp.stack([n_local, jnp.sum(counts > 0), jnp.max(counts),
                       n_local - handed]).astype(jnp.int32)
    return y, stats
