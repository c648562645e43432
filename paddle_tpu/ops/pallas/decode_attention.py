"""Decode-serving attention as Pallas TPU kernels.

Capability parity: the reference's serving attention fusion kernels —
`phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu` (single-token
decode over a dense [B, H, MaxLen, D] cache) and
`phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu` (paged KV
cache addressed through block tables). TPU redesign: one online-softmax
kernel per cache layout, KV streamed through VMEM in blocks/pages, q heads
grouped by their shared kv head (GQA never materialises repeated KV), and
per-batch valid lengths arriving via scalar prefetch so block tables can
drive the BlockSpec index maps (the pages a sequence doesn't own are never
even fetched from HBM).

Decode is HBM-bandwidth-bound (the whole KV cache is read once per token),
so the kernels optimise for streaming: f32 accumulation scratch, last grid
dim sequential over KV, page/block granularity aligned to Mosaic tiling.

Layouts:
  decode_attention:  q [B, Hq, D], cache [B, Hkv, S, D], lengths [B]
  paged_attention:   q [B, Hq, D], pool [L, Hkv, NumPages, PageSize, D]
                     addressed by (layer, page) — or one layer's
                     [Hkv, NumPages, PageSize, D] —
                     block_tables [B, PagesPerSeq], lengths [B]
`lengths[b]` counts the VALID kv positions (including the current token's
freshly-written slot).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32-typed constants: the package runs with x64 on, where a weak python
# float would trace as f64 next to the kernel's f32 operands
NEG_INF = np.float32(-1e30)
ONE_F32 = np.float32(1.0)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr,
                   *, scale, bk, nk):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    length = len_ref[b]

    @pl.when(j * bk < length)          # skip fully-invalid kv blocks
    def _():
        q = q_ref[0, 0]                # [rep, d]
        k = k_ref[0, 0]                # [bk, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                      # [rep, bk]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bk
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = alpha * l_scr[:, 0:1] + jnp.sum(p, -1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0:1] = m_new

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:, 0:1]
        o_ref[0, 0] = (acc[:] / jnp.where(l == 0.0, ONE_F32, l)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, scale=None,
                     block_k=512, interpret=None):
    """Single-token decode attention over a dense KV cache.

    q [B, Hq, D] -> out [B, Hq, D]; cache [B, Hkv, S, D]; lengths [B].
    """
    from . import use_interpret

    if interpret is None:
        interpret = use_interpret()
    b, hq, d = q.shape
    _, hkv, s, _ = k_cache.shape
    rep = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bk = min(block_k, s)
    while s % bk:
        bk //= 2
    nk = s // bk

    qg = q.reshape(b, hkv, rep, d)
    kern = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern,
            name="decode_attention",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, hkv, nk),
                in_specs=[
                    pl.BlockSpec((1, 1, rep, d), lambda bi, h, j, L: (bi, h, 0, 0)),
                    pl.BlockSpec((1, 1, bk, d), lambda bi, h, j, L: (bi, h, j, 0)),
                    pl.BlockSpec((1, 1, bk, d), lambda bi, h, j, L: (bi, h, j, 0)),
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, rep, d), lambda bi, h, j, L: (bi, h, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((rep, d), jnp.float32),
                    pltpu.VMEM((rep, 128), jnp.float32),
                    pltpu.VMEM((rep, 128), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=4 * b * hq * s * d,
                bytes_accessed=(b * hq * d + 2 * b * hkv * s * d)
                * q.dtype.itemsize,
                transcendentals=b * hq * s,
            ),
        )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, hq, d)


# ------------------------------------------------------------------ paged

def _stacked(pools, layer):
    """The pools as ``[L, Hkv, NumPages, PageSize, *]`` and the layer as
    the i32[1] scalar-prefetch operand. One layer's four-dimensional
    pages get a leading axis of 1 (a reshape: free) and layer 0."""
    if pools[0].ndim == 4:
        if layer is not None:
            raise ValueError("layer= addresses a stacked [L, Hkv, P, page, "
                             "D] pool; four-dimensional pages have none")
        pools, layer = tuple(p[None] for p in pools), 0
    elif layer is None:
        raise ValueError("a stacked [L, Hkv, P, page, D] pool needs layer=")
    return pools, jnp.asarray(layer, jnp.int32).reshape(1)


def _table_page(tables, bi, j, num_pages):
    """Sequence ``bi``'s ``j``-th page id, clamped so garbage table
    entries past `lengths` stay in-bounds (i32 bounds: python-int
    literals weak-type to i64 under x64)."""
    return jnp.clip(tables[bi, j], jnp.int32(0), jnp.int32(num_pages - 1))


# The dense walk. A grid step is one row x ``group`` pages x EVERY kv head:
# a page's block is all heads' rows of it ([Hkv, 1, page, D] of the stacked
# pool, 128 KB at 8 heads x 64 x 128 x bf16), ``group`` such blocks a step
# (each an operand of its own, as the latent kernel passes its pool), and
# the step's ``group * page`` tokens go through one pair of head-batched
# matmuls. A step's fixed cost and a page's DMA are paid once for all
# heads, and a step past the row's length is skipped whole.

# what a step's double-buffered K and V blocks may take of scoped VMEM (a
# quarter of a v5e's 16 MiB; the step's tiles and scores take as much again)
_STEP_VMEM = 4 * 1024 * 1024


def _pages_per_step(hkv, page, d, itemsize, pages_per_seq):
    """How many pages a grid step takes: the most whose K and V blocks,
    double-buffered (``4 * group * hkv * page * d * itemsize`` bytes), fit
    :data:`_STEP_VMEM`; at most the table's columns, at least one. 8 at 8
    heads x 64 x 128 x bf16, 2 at 32 heads."""
    return int(max(1, min(pages_per_seq,
                          _STEP_VMEM // (4 * hkv * page * d * itemsize))))


def _fetch_table(tables, lengths, page, group, num_pages):
    """The block table as the grid fetches it, ``[B, steps * group]``:
    column ``j * group + g`` is the page operand ``g`` names in row b's
    step ``j``. A column the row's length reaches names its page (clamped
    into the pool: entries past the length may be garbage). A column past
    the length names what operand ``g`` named in the grid step before, and
    so on back across rows, so the pipeline sees an unchanged block index
    and fetches nothing: not the pages past a row's length, not the
    ``group - n`` operands of a row with ``n`` live pages, nothing at all
    for an empty slot."""
    b, pps = tables.shape
    steps = -(-pps // group)
    i32 = jnp.int32
    t = jnp.clip(tables, i32(0), i32(num_pages - 1))
    t = jnp.pad(t, ((0, 0), (0, steps * group - pps)))
    live = (jnp.arange(steps * group, dtype=i32)[None] * i32(page)
            < lengths[:, None])
    # operand g's blocks in grid order are column g of [B * steps, group]:
    # fill each dead entry forward from the last live one above it
    t, live = (a.reshape(b * steps, group) for a in (t, live))
    at = jnp.where(live, jnp.arange(b * steps, dtype=i32)[:, None], i32(0))
    at = jax.lax.cummax(at, axis=0)
    return jnp.take_along_axis(t, at, axis=0).reshape(b, steps * group)


def _exact_page(ref):
    """A page as it lies in the pool: ``[Hkv, page, D]``."""
    return ref[:, 0]


def _int8_page(codes, scales):
    """A page of ``memory.quantize_rows_int8`` codes times its lane-dense
    scales (``[Hkv, 1, 8, page]``, see :func:`paged_attention_int8`),
    float32: the codes * scales product of the gather path, so only a
    quarter of the exact cache's bytes cross HBM -> VMEM."""
    return (codes[:, 0].astype(jnp.float32)
            * scales[:, 0, 0][:, :, None])


def _paged_kernel(fetch_ref, len_ref, layer_ref, q_ref, *refs, scale, page,
                  steps, group, load, width):
    """One row's walk over its pages, ``group`` a step, for all kv heads.
    ``load`` makes a page's ``[Hkv, page, D]`` of its ``width`` operands
    (the exact rows; int8 codes and their scales); the K pages' operands
    come first, then the V pages', then the output and the running
    softmax state ``[Hkv, rep, *]``."""
    n = group * width
    o_ref, acc, m_scr, l_scr = refs[2 * n:]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    length = len_ref[b]
    start = j * group * page

    def tile(pool):
        # the step's pages as one [Hkv, group * page, D] tile (an operand
        # past the length holds some stale page, masked by its positions)
        pages = [load(*pool[g * width:(g + 1) * width])
                 for g in range(group)]
        return pages[0] if group == 1 else jnp.concatenate(pages, 1)

    @pl.when(start < length)           # a step past the length: nothing
    def _():
        k, v = tile(refs[:n]), tile(refs[n:2 * n])
        q = q_ref[0]                   # [Hkv, rep, D]
        if width > 1:                  # dequantized pages are float32
            q = q.astype(k.dtype)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [Hkv, rep, T]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + start
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, :, 0:1] = (alpha * l_scr[:, :, 0:1]
                            + jnp.sum(p, -1, keepdims=True))
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [Hkv, rep, D]
        m_scr[:, :, 0:1] = m_new

    @pl.when(j == steps - 1)
    def _():
        l = l_scr[:, :, 0:1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, ONE_F32, l)).astype(
            o_ref.dtype)


def _page_spec(hkv, rows, lanes, g, group, sliced=False):
    """Operand ``g``'s block of a pool ``[L, Hkv, P, rows, lanes]``: every
    kv head's rows of one page of one layer, read where it lies (the layer
    axis squeezed: the kernel sees ``[Hkv, 1, rows, lanes]``); the fetch
    table picks the page. ``sliced``: the layer was sliced out already,
    the pool is a stack of one."""

    def index(bi, j, fetch, lens, layer):
        return (0 if sliced else layer[0], 0, fetch[bi, j * group + g], 0, 0)

    return pl.BlockSpec((None, hkv, 1, rows, lanes), index)


# jitted so that a step's operands, the same pool ``group`` times over, are
# one argument of one program also when the call is made eagerly (op by
# op each would be a parameter of its own, counted against the device's
# memory ``group`` times); under a caller's jit this is inlined
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_call(q, pools, block_tables, lengths, layer, *, scale, interpret):
    """The dense walk over layer ``layer`` (i32[1]) of stacked ``pools``:
    ``(K, V)`` exact pages, or ``(K codes, K scales, V codes, V scales)``
    int8 ones."""
    width = len(pools) // 2            # operands a page a pool
    int8 = width == 2
    b, hq, d = q.shape
    _, hkv, num_pages, page, _ = pools[0].shape
    rep = hq // hkv
    pps = block_tables.shape[1]
    # an int8 page is float32 once dequantized: that is what a step holds
    group = _pages_per_step(hkv, page, d,
                            4 if int8 else pools[0].dtype.itemsize, pps)
    steps = -(-pps // group)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    lengths = lengths.astype(jnp.int32)
    if int8:
        # the codes are read where they lie; the scales cannot be. Held
        # as [.., page, 1] columns, Mosaic would want each padded to 128
        # lanes, a relayout of the whole pool of them. So the layer's
        # scales (1/32 of its codes' bytes at D=128) are sliced out and
        # laid [1, Hkv, P, 8, page]: page along the lanes, sublane-padded
        # (the lse8 pattern: Mosaic blocks need >= 8 sublanes)
        def lane_dense(scales):
            s = jax.lax.dynamic_index_in_dim(scales, layer[0], 0,
                                             keepdims=False)
            return jnp.broadcast_to(s.reshape(1, hkv, num_pages, 1, page),
                                    (1, hkv, num_pages, 8, page))

        pools = (pools[0], lane_dense(pools[1]),
                 pools[2], lane_dense(pools[3]))

    def specs(g):
        codes = _page_spec(hkv, page, d, g, group)
        return ([codes, _page_spec(hkv, 8, page, g, group, sliced=True)]
                if int8 else [codes])

    def row(bi, j, fetch, lens, li):
        return (bi, 0, 0, 0)

    kern = functools.partial(
        _paged_kernel, scale=np.float32(scale), page=page, steps=steps,
        group=group, load=_int8_page if int8 else _exact_page, width=width)
    with jax.enable_x64(False):
        fetch = _fetch_table(block_tables.astype(jnp.int32), lengths, page,
                             group, num_pages)
        out = pl.pallas_call(
            kern,
            # the trace's name: kernel.paged_attention_roofline.decode
            # reads the operations that start with it
            name="paged_attention_int8" if int8 else "paged_attention",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b, steps),
                in_specs=[pl.BlockSpec((1, hkv, rep, d), row)]
                + 2 * [s for g in range(group) for s in specs(g)],
                out_specs=pl.BlockSpec((1, hkv, rep, d), row),
                scratch_shapes=[
                    pltpu.VMEM((hkv, rep, d), jnp.float32),
                    pltpu.VMEM((hkv, rep, 128), jnp.float32),
                    pltpu.VMEM((hkv, rep, 128), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=4 * b * hq * pps * page * d,
                bytes_accessed=2 * b * hq * d * q.dtype.itemsize
                + 2 * b * hkv * pps * page * (d + 4 if int8 else
                                              d * pools[0].dtype.itemsize),
                transcendentals=b * hq * pps * page,
            ),
        )(fetch, lengths, layer, q.reshape(b, hkv, rep, d),
          *(p for pool in (pools[:width], pools[width:])
            for g in range(group) for p in pool))
    return out.reshape(b, hq, d)


def _paged_walk(q, pools, block_tables, lengths, layer, scale, interpret):
    """What both entries do before the walk: the pools stacked, the layer
    an operand, ``interpret`` settled."""
    from . import use_interpret

    pools, layer = _stacked(pools, layer)
    return _paged_call(
        q, pools, block_tables, lengths, layer, scale=scale,
        interpret=use_interpret() if interpret is None else bool(interpret))


def paged_attention_int8(q, k_codes, k_scales, v_codes, v_scales,
                         block_tables, lengths, *, layer=None, scale=None,
                         interpret=None):
    """Paged-KV decode attention over int8 pages (the serving
    ``int8_kv=True`` storage: ``memory.quantize_rows_int8`` codes
    ``[L, Hkv, NumPages, PageSize, D]`` int8 + scales
    ``[L, Hkv, NumPages, PageSize, 1]`` f32, read at ``layer``; or one
    layer's four-dimensional pages with ``layer`` left out). The walk is
    :func:`paged_attention`'s, over pages dequantized in VMEM as they
    arrive: numerically identical to gathering the owned pages and
    dequantizing in HBM (same codes * scales product, float32
    throughout), without ever materializing the dequantized cache. The
    codes are addressed in place by (layer, page); only the layer's
    scales are sliced out, to be laid along the lanes.
    """
    return _paged_walk(q, (k_codes, k_scales, v_codes, v_scales),
                       block_tables, lengths, layer, scale, interpret)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    layer=None, scale=None, interpret=None):
    """Paged-KV decode attention (block_multi_head_attention slot).

    q [B, Hq, D]; the stacked pool [L, Hkv, NumPages, PageSize, D] read
    at ``layer`` (an int or a traced scalar: the serving programs hand
    over the whole pool and never slice a layer out of it), or one
    layer's pages [Hkv, NumPages, PageSize, D] with ``layer`` left out;
    block_tables [B, PagesPerSeq] (page ids per sequence, row-major);
    lengths [B] valid kv length. A grid step is one row, every kv head
    and as many pages as :func:`_pages_per_step` allows, put through one
    pair of head-batched matmuls; the index maps read the layer and the
    block table via scalar prefetch, so only the pages a row's length
    reaches are fetched from HBM, and steps past it are skipped.
    """
    return _paged_walk(q, (k_pages, v_pages), block_tables, lengths, layer,
                       scale, interpret)


# ----------------------------------------------------------------- latent
# Latent (MLA) decode in absorbed form. A cached token is ONE row a layer,
# [c_kv (rank) | k_rope | zero pad] of ``width`` lanes (a multiple of 128),
# shared by every head: the queries come already multiplied into the
# latent space ([B, H, width], the rope part beside the latent part, zeros
# over the pad), the scores are taken against the row itself and the
# values ARE its first ``rank`` lanes. One page is fetched once for all H
# heads; ``group`` pages a grid step (each its own block of the same
# pool) go through ONE pair of matmuls, because a step's fixed cost and a
# 64-token matmul's shape are worth more than one page's work (a call of
# 64 rows x 2,200 tokens on a v5e: 1.70 ms page by page whatever the
# group, 0.73 ms at 4 pages a matmul, 0.53 at 12, 0.50 at 18).

def _mla_gather(q, pool, tables, lengths, layer, rank, scale):
    """The gather path: the owned pages gathered out of the pool, masked
    softmax in float32. The CPU's path and the kernel's test reference."""
    b, h, w = q.shape
    rows = pool[layer, 0][tables].reshape(b, -1, w).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    mask = jnp.arange(rows.shape[1])[None, None] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), -1)
    return jnp.einsum("bhs,bsr->bhr", p, rows[..., :rank]).astype(q.dtype)


def _mla_online_softmax(s, kv, rank, acc, m_scr, l_scr):
    """One running-softmax step of masked scores ``s`` [M, N] over the
    latent rows ``kv`` [N, width]: the values are its first ``rank``
    lanes."""
    m_prev = m_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:, 0:1] = alpha * l_scr[:, 0:1] + jnp.sum(p, -1, keepdims=True)
    acc[:] = acc[:] * alpha + jax.lax.dot_general(
        p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:, 0:1] = m_new


def _mla_paged_kernel(tables_ref, len_ref, layer_ref, q_ref, *refs, scale,
                      page, steps, group, rank):
    pages, (o_ref, acc, m_scr, l_scr) = refs[:group], refs[group:]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    length = len_ref[b]
    start = j * group * page

    @pl.when(start < length)
    def _():
        # the step's pages as one [group * page, width] tile: one matmul
        # of every head against all of them (a page past the length is
        # the row's last page again, masked by its positions)
        kv = (pages[0][0, 0] if group == 1 else
              jnp.concatenate([r[0, 0] for r in pages], 0))
        s = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, group*page]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + start
        _mla_online_softmax(jnp.where(pos < length, s, NEG_INF), kv, rank,
                            acc, m_scr, l_scr)

    @pl.when(j == steps - 1)
    def _():
        l = l_scr[:, 0:1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, ONE_F32, l)).astype(
            o_ref.dtype)


def mla_paged_attention(q, pool, block_tables, lengths, *, layer, rank,
                        scale, group=12, interpret=None):
    """Absorbed latent decode attention over a paged latent pool.

    q [B, H, width] (queries in the latent space: ``q_nope W_kvb,K^T``,
    then the roped part, then zeros over the pad); the stacked pool
    [L, 1, NumPages, PageSize, width] read at ``layer`` (an int or a
    traced scalar) by (layer, page); block_tables [B, PagesPerSeq];
    lengths [B] valid positions. Returns the attention-weighted latent
    rows [B, H, rank] (the caller applies ``W_kvb,V`` and ``W_o``). A
    page past a row's length is not fetched: its block index stays on the
    row's last valid page, which is already there. Off the TPU, with
    ``interpret`` unset, the gather path runs instead of the interpreter.
    """
    from . import on_tpu_device

    b, h, w = q.shape
    _, one, num_pages, page, pw = pool.shape
    if one != 1 or pw != w or rank > w:
        raise ValueError(f"mla_paged_attention: pool {pool.shape} against q "
                         f"{q.shape}, rank {rank}: one shared row a token, "
                         "as wide as the queries")
    block_tables = block_tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    if interpret is None:
        if not on_tpu_device():
            return _mla_gather(q, pool, block_tables, lengths, layer, rank,
                               scale)
        interpret = False
    if w % 128 or rank % 128:
        raise ValueError(f"mla_paged_attention: the kernel slices lanes: "
                         f"width {w} and rank {rank} must be multiples of "
                         "128")
    pps = block_tables.shape[1]
    group = min(group, pps)
    steps = -(-pps // group)

    def page_spec(g):
        def index(bi, j, tables, lens, li):
            last = jnp.maximum(lens[bi] - 1, 0) // page
            jj = jnp.minimum(j * group + g, jnp.minimum(last, pps - 1))
            return (li[0], 0, _table_page(tables, bi, jj, num_pages), 0, 0)

        return pl.BlockSpec((None, 1, 1, page, w), index)

    kern = functools.partial(_mla_paged_kernel, scale=np.float32(scale),
                             page=page, steps=steps, group=group, rank=rank)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kern,
            name="mla_paged_attention",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b, steps),
                in_specs=[pl.BlockSpec((1, h, w),
                                       lambda bi, j, T, L, li: (bi, 0, 0))]
                + [page_spec(g) for g in range(group)],
                out_specs=pl.BlockSpec((1, h, rank),
                                       lambda bi, j, T, L, li: (bi, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((h, rank), jnp.float32),
                    pltpu.VMEM((h, 128), jnp.float32),
                    pltpu.VMEM((h, 128), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=2 * b * h * pps * page * (w + rank),
                bytes_accessed=(b * h * (w + rank) + b * pps * page * w)
                * q.dtype.itemsize,
                transcendentals=b * h * pps * page,
            ),
        )(block_tables, lengths, jnp.asarray(layer, jnp.int32).reshape(1),
          q, *([pool] * group))


# A prefill chunk over the same pool, absorbed too: ``heads_block`` heads
# x the chunk's positions are the rows of one tile ([B, H * c, width],
# head-major, so a block of heads is contiguous), the history's pages
# stream past it ``group`` a step, and a row of the tile sees the
# positions up to its own (causal over history and chunk alike: the
# chunk's rows are in the pool already).

def _mla_prefill_gather(q, pool, tables, pos0, layer, rank, scale, chunk):
    """The gather path of :func:`mla_paged_prefill`: whole masked softmax
    over the gathered pages, float32. The kernel's test reference."""
    b, m, w = q.shape
    rows = pool[layer, 0][tables].reshape(b, -1, w).astype(jnp.float32)
    s = jnp.einsum("bmw,bsw->bms", q.astype(jnp.float32), rows) * scale
    qpos = pos0[:, None] + jnp.arange(m)[None, :] % chunk
    ok = jnp.arange(rows.shape[1])[None, None, :] <= qpos[:, :, None]
    p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), -1)
    return jnp.einsum("bms,bsr->bmr", p, rows[..., :rank]).astype(q.dtype)


def _mla_prefill_kernel(tables_ref, pos_ref, nv_ref, layer_ref, q_ref, *refs,
                        scale, page, steps, group, rank, chunk):
    pages, (o_ref, acc, m_scr, l_scr) = refs[:group], refs[group:]
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    pos0 = pos_ref[b]
    start = j * group * page

    # a step past the chunk's last position, or of a row with nothing to
    # prefill, computes nothing (and its pages were not fetched). The
    # first step holds position 0, which every row sees: no row's running
    # maximum stays at its floor.
    @pl.when((start < pos0 + chunk) & (nv_ref[b] > 0))
    def _():
        kv = (pages[0][0, 0] if group == 1 else
              jnp.concatenate([r[0, 0] for r in pages], 0))
        s = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [hb*c, g*page]
        kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + start
        qpos = pos0 + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0),
            jnp.int32(chunk))
        _mla_online_softmax(jnp.where(kpos <= qpos, s, NEG_INF), kv, rank,
                            acc, m_scr, l_scr)

    @pl.when(j == steps - 1)
    def _():
        l = l_scr[:, 0:1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, ONE_F32, l)).astype(
            o_ref.dtype)


def mla_paged_prefill(q, pool, block_tables, pos0, nvalid, *, layer, rank,
                      scale, chunk, heads_block=8, group=4, interpret=None):
    """Absorbed latent attention of a prefill chunk against the paged
    latent history (the chunk's own rows already written).

    q [B, H * chunk, width], head-major (row ``h * chunk + t`` is head h
    at position ``pos0[b] + t``), queries in the latent space as for
    :func:`mla_paged_attention`; the pool, ``layer``, ``rank``, ``scale``
    and block_tables as there; pos0 [B] the chunk's first position,
    nvalid [B] its real positions (a row with none is skipped and reads
    zeros). Returns [B, H * chunk, rank]. Scores never leave VMEM: a
    tile of ``heads_block`` heads keeps its running softmax while the
    pages stream past, and pages past the chunk's end are not fetched.
    """
    from . import use_interpret

    if interpret is None:
        interpret = use_interpret()
    b, m, w = q.shape
    _, one, num_pages, page, pw = pool.shape
    rows = min(heads_block * chunk, m)
    if one != 1 or pw != w or w % 128 or rank % 128 or m % rows:
        raise ValueError(f"mla_paged_prefill: pool {pool.shape} against q "
                         f"{q.shape}, rank {rank}, tiles of {rows} rows")
    pps = block_tables.shape[1]
    group = min(group, pps)
    steps = -(-pps // group)

    def page_spec(g):
        def index(bi, hi, j, tables, pos, nv, li):
            last = (pos[bi] + chunk - 1) // page
            jj = jnp.minimum(j * group + g, jnp.minimum(last, pps - 1))
            return (li[0], 0, _table_page(tables, bi, jj, num_pages), 0, 0)

        return pl.BlockSpec((None, 1, 1, page, w), index)

    kern = functools.partial(_mla_prefill_kernel, scale=np.float32(scale),
                             page=page, steps=steps, group=group, rank=rank,
                             chunk=chunk)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kern,
            name="mla_paged_prefill",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(b, m // rows, steps),
                in_specs=[pl.BlockSpec(
                    (1, rows, w), lambda bi, hi, j, T, P, N, li: (bi, hi, 0))]
                + [page_spec(g) for g in range(group)],
                out_specs=pl.BlockSpec(
                    (1, rows, rank),
                    lambda bi, hi, j, T, P, N, li: (bi, hi, 0)),
                scratch_shapes=[
                    pltpu.VMEM((rows, rank), jnp.float32),
                    pltpu.VMEM((rows, 128), jnp.float32),
                    pltpu.VMEM((rows, 128), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, m, rank), q.dtype),
            interpret=bool(interpret),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024),
            cost_estimate=pl.CostEstimate(
                flops=2 * b * m * pps * page * (w + rank),
                bytes_accessed=(b * m * (w + rank)
                                + b * (m // rows) * pps * page * w)
                * q.dtype.itemsize,
                transcendentals=b * m * pps * page,
            ),
        )(block_tables.astype(jnp.int32), pos0.astype(jnp.int32),
          nvalid.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1), q, *([pool] * group))
