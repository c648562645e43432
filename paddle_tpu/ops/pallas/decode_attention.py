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


def _pool_spec(page, width, num_pages):
    """One page of one kv head of one layer, read where it lies in the
    stacked pool: the layer axis is squeezed (the kernel sees
    ``[1, 1, page, width]``), the block table picks the page."""

    def index(bi, h, j, tables, lens, layer):
        return (layer[0], h, _table_page(tables, bi, j, num_pages), 0, 0)

    return pl.BlockSpec((None, 1, 1, page, width), index)


def _paged_kernel(tables_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                  o_ref, acc, m_scr, l_scr, *, scale, page, npages):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    length = len_ref[b]

    @pl.when(j * page < length)
    def _():
        q = q_ref[0, 0]                # [rep, d]
        k = k_ref[0, 0]                # [page, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                      # [rep, page]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page
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

    @pl.when(j == npages - 1)
    def _():
        l = l_scr[:, 0:1]
        o_ref[0, 0] = (acc[:] / jnp.where(l == 0.0, ONE_F32, l)).astype(o_ref.dtype)


def _paged_int8_kernel(tables_ref, len_ref, layer_ref, q_ref, kc_ref,
                       ks_ref, vc_ref, vs_ref, o_ref, acc, m_scr, l_scr,
                       *, scale, page, npages):
    """Paged decode over int8 KV pages: dequantize (codes, scales)
    INSIDE the kernel, so only ~1/4 of the exact cache's bytes cross
    HBM->VMEM per token (int8 codes + one f32 scale per head_dim row vs
    f32/bf16 rows) — the serving int8_kv mode's gather+dequantize-in-HBM
    path becomes a streaming read (docs/SERVING.md)."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    length = len_ref[b]

    @pl.when(j * page < length)
    def _():
        q = q_ref[0, 0]                # [rep, d]
        # per-row dequant: codes [page, d] int8 * scale [page] f32 —
        # the quantize_rows_int8 grid (block = the head_dim row the
        # page table already addresses)
        k = kc_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0, 0][:, None]
        v = vc_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32
        ) * scale                      # [rep, page]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = alpha * l_scr[:, 0:1] + jnp.sum(p, -1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0:1] = m_new

    @pl.when(j == npages - 1)
    def _():
        l = l_scr[:, 0:1]
        o_ref[0, 0] = (acc[:] / jnp.where(l == 0.0, ONE_F32, l)).astype(o_ref.dtype)


def paged_attention_int8(q, k_codes, k_scales, v_codes, v_scales,
                         block_tables, lengths, *, layer=None, scale=None,
                         interpret=None):
    """Paged-KV decode attention over int8 pages (the serving
    ``int8_kv=True`` storage: ``memory.quantize_rows_int8`` codes
    ``[L, Hkv, NumPages, PageSize, D]`` int8 + scales
    ``[L, Hkv, NumPages, PageSize, 1]`` f32, read at ``layer``; or one
    layer's four-dimensional pages with ``layer`` left out). The codes
    are addressed in place by (layer, page); only the layer's scales
    are sliced out, to be laid along the lanes.
    Dequantization happens in VMEM per fetched page — numerically
    identical to gathering the owned pages and dequantizing in HBM
    (same codes * scales product), without ever materializing the
    dequantized cache.
    """
    from . import use_interpret

    if interpret is None:
        interpret = use_interpret()
    (k_codes, k_scales, v_codes, v_scales), layer = _stacked(
        (k_codes, k_scales, v_codes, v_scales), layer)
    li = layer[0]
    b, hq, d = q.shape
    _, hkv, num_pages, page, _ = k_codes.shape
    rep = hq // hkv
    pages_per_seq = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    qg = q.reshape(b, hkv, rep, d)
    codes = _pool_spec(page, d, num_pages)

    def lane_dense(scales):
        # the codes are read where they lie; the scales cannot be. Held
        # as [.., page, 1] columns, Mosaic would want each padded to 128
        # lanes, a relayout of the whole pool of them. So the layer's
        # scales (1/32 of its codes' bytes at D=128) are sliced out and
        # laid [Hkv, P, 8, page]: page along the lanes, sublane-padded
        # (the lse8 pattern: Mosaic blocks need >= 8 sublanes)
        s = jax.lax.dynamic_index_in_dim(scales, li, 0, keepdims=False)
        return jnp.broadcast_to(s.reshape(hkv, num_pages, 1, page),
                                (hkv, num_pages, 8, page))

    scales = pl.BlockSpec(
        (1, 1, 8, page), lambda bi, h, j, tables, lens, layer: (
            h, _table_page(tables, bi, j, num_pages), 0, 0))
    kern = functools.partial(_paged_int8_kernel, scale=scale, page=page,
                             npages=pages_per_seq)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern,
            name="paged_attention_int8",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b, hkv, pages_per_seq),
                in_specs=[
                    pl.BlockSpec((1, 1, rep, d),
                                 lambda bi, h, j, T, L, li: (bi, h, 0, 0)),
                    codes, scales, codes, scales,
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, rep, d), lambda bi, h, j, T, L, li: (bi, h, 0, 0)),
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
                flops=4 * b * hq * pages_per_seq * page * d,
                bytes_accessed=(b * hq * d * q.dtype.itemsize
                                + 2 * b * hkv * pages_per_seq * page
                                * (d + 4)),
                transcendentals=b * hq * pages_per_seq * page,
            ),
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), layer,
          qg, k_codes, lane_dense(k_scales), v_codes, lane_dense(v_scales))
    return out.reshape(b, hq, d)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    layer=None, scale=None, interpret=None):
    """Paged-KV decode attention (block_multi_head_attention slot).

    q [B, Hq, D]; the stacked pool [L, Hkv, NumPages, PageSize, D] read
    at ``layer`` (an int or a traced scalar: the serving programs hand
    over the whole pool and never slice a layer out of it), or one
    layer's pages [Hkv, NumPages, PageSize, D] with ``layer`` left out;
    block_tables [B, PagesPerSeq] (page ids per sequence, row-major);
    lengths [B] valid kv length. The BlockSpec index map reads the layer
    and the block table via scalar prefetch, so only the pages a
    sequence actually owns are fetched from HBM.
    """
    from . import use_interpret

    if interpret is None:
        interpret = use_interpret()
    (k_pages, v_pages), layer = _stacked((k_pages, v_pages), layer)
    b, hq, d = q.shape
    _, hkv, num_pages, page, _ = k_pages.shape
    rep = hq // hkv
    pages_per_seq = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    qg = q.reshape(b, hkv, rep, d)
    pages = _pool_spec(page, d, num_pages)
    kern = functools.partial(_paged_kernel, scale=scale, page=page,
                             npages=pages_per_seq)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern,
            name="paged_attention",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b, hkv, pages_per_seq),
                in_specs=[
                    pl.BlockSpec((1, 1, rep, d),
                                 lambda bi, h, j, T, L, li: (bi, h, 0, 0)),
                    pages, pages,
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, rep, d), lambda bi, h, j, T, L, li: (bi, h, 0, 0)),
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
                flops=4 * b * hq * pages_per_seq * page * d,
                bytes_accessed=(b * hq * d
                                + 2 * b * hkv * pages_per_seq * page * d)
                * q.dtype.itemsize,
                transcendentals=b * hq * pages_per_seq * page,
            ),
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), layer,
          qg, k_pages, v_pages)
    return out.reshape(b, hq, d)
