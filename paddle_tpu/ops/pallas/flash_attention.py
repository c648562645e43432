"""Flash attention as a Pallas TPU kernel (fwd + bwd), with custom_vjp.

Capability parity: the reference binds the external CUDA flashattn library
(``phi/kernels/gpu/flash_attn_kernel.cu``); on TPU the same slot is a tiled
online-softmax kernel that keeps q/k/v blocks in VMEM and accumulates in
float32 — O(S) memory instead of the O(S^2) score matrix.

Layout: public entry takes paddle's [B, S, H, D]; kernels run on [BH, S, D].
GQA is handled in the BlockSpec index maps (q-head blocks read their shared
kv head directly) — kv is never materialised at q-head width.

Causal semantics match the XLA fallback (`_xla_sdpa`): when sq != sk the
queries align to the END of the key sequence (kv-cache decode convention),
i.e. query row i sees key cols <= i + (sk - sq).

Grid convention (TPU grids execute the LAST dimension innermost &
sequentially, so scratch accumulators carry across it):
  forward:  (B*Hq, Sq/bq, Sk/bk)   — k-blocks stream through a fixed q-block
  backward: dq   (B*Hq, Sq/bq, Sk/bk)
            dkdv (B*Hkv, Sk/bk, rep*Sq/bq) — the q sweep covers all rep
            q-heads sharing the kv head, keeping accumulation sequential.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32-typed constants: the package runs with x64 on, where a weak python
# float would trace as f64 next to the kernel's f32 operands
NEG_INF = np.float32(-1e30)  # large-negative instead of -inf: keeps exp()
                 # exact zero without nan from (-inf) - (-inf) in rescale
ONE_F32 = np.float32(1.0)


def _block_for(s: int, env="PTPU_FA_BLOCK", default=1024):
    """Pick a seq block size whose lse/delta blocks satisfy Mosaic's
    last-dim tiling (multiple of 128, or the full dimension).
    PTPU_FA_BLOCK / PTPU_FA_BWD_BLOCK override the preferred fwd/bwd sizes
    (perf knobs; measured on v5e at seq 2048 end-to-end 1.3B pretrain:
    fwd 1024 > 512 by 4.3%, 512 > 256/128 by 17%/40% — bigger q/k tiles
    amortise the VMEM streaming; the bwd kernels hold more live blocks so
    their sweet spot can differ)."""
    import os

    raw = os.environ.get(env)
    if raw is None:
        pref = default
    else:
        try:
            pref = int(raw)
        except ValueError:
            # a mistyped knob must not silently masquerade as a measured
            # configuration — the sweeps record these envs verbatim
            raise ValueError(
                f"{env}={raw!r}: expected an integer block size in "
                "tokens (a multiple of 128)") from None
        if pref % 128:
            import warnings

            warnings.warn(
                f"{env}={pref} is not a multiple of 128 — Mosaic block "
                f"tiling requires it; IGNORING the override and using "
                f"the default {default}. Fix the knob or the recorded "
                "perf numbers will not measure what the env claims.",
                RuntimeWarning, stacklevel=2)
            pref = default
    if s <= 512:
        return s  # full-dim block (always tileable at these sizes)
    for b in (pref, 1024, 512, 256, 128):
        if b % 128 == 0 and s % b == 0:
            return b
    return None


def _bwd_block_for(s: int):
    # 1024 measured best once causally-skipped blocks stopped being
    # fetched (the clamp halved bwd DMA volume; before it, 512 won)
    return _block_for(s, env="PTPU_FA_BWD_BLOCK", default=1024)


def supported_seq(s: int) -> bool:
    return _block_for(s) is not None


def to_bh(x, h):
    """[B, S, H, D] -> the kernel layout [B*H, S, D]."""
    b, s, _, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, d)


def from_bh(x, b, h):
    """[B*H, S, D] -> [B, S, H, D]."""
    s, d = x.shape[1], x.shape[2]
    return jnp.transpose(x.reshape(b, h, s, d), (0, 2, 1, 3))


def _causal_mask(qi, ki, bq, bk, offset):
    """[bq, bk] bool: True where key col <= query row + offset."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
    return cols <= rows + offset


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                *, scale, causal, bq, bk, nk, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def compute():
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk, offset), s, NEG_INF)

        m_prev = m_scr[:, 0:1]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)              # [bq, 1]
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_new

    if causal:
        # k-blocks entirely above the (offset) diagonal are fully masked
        @pl.when(ki * bk <= qi * bq + (bq - 1) + offset)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, ONE_F32, l)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        lse_row = m_scr[:, 0] + jnp.log(
            jnp.where(l[:, 0] == 0.0, ONE_F32, l[:, 0]))
        # [8, bq] sublane-padded block: Mosaic needs >=8 sublanes per block
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], (8, lse_row.shape[0]))


def _kv_index(b_idx, hq, hk):
    """Map a flat (batch*q_head) grid index to its (batch*kv_head) block.

    Uses lax primitives directly: jnp operator dispatch on the int32 grid
    tracer recurses inside Mosaic's index-map tracing."""
    if hq == hk:
        return b_idx
    rep = hq // hk
    hq_c = jnp.int32(hq)
    bi = jax.lax.div(b_idx, hq_c)
    hi = jax.lax.rem(b_idx, hq_c)
    return jax.lax.add(
        jax.lax.mul(bi, jnp.int32(hk)),
        jax.lax.div(hi, jnp.int32(rep)),
    )


def _fwd(q, k, v, scale, causal, interpret, hq, hk):
    bhq, sq, d = q.shape
    sk = k.shape[1]
    bq = _block_for(sq)
    bk = _block_for(sk)
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention: seq lens ({sq}, {sk}) not tileable — pad to a "
            "multiple of 128 (or <= 512) or use the XLA fallback"
        )
    nq, nk = sq // bq, sk // bk
    offset = sk - sq

    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        offset=offset,
    )
    # x64 mode (enabled globally for float64 API parity) must not leak into
    # kernel tracing: Mosaic has no 64-bit types and its lowering crashes on
    # the int64 literals x64 promotion produces.
    with jax.enable_x64(False):
        o, lse = _fwd_call(kern, q, k, v, bhq, sq, sk, d, bq, bk, nq, nk,
                           hq, hk, interpret, causal)
    return o, lse[:, 0, :]


def _clamp_kv_j(j, i, bq, bk, offset):
    """Causal fetch clamp: kv blocks past the diagonal are never computed
    (pl.when guards), so point their index map at the LAST VALID block —
    Mosaic skips the DMA when consecutive grid steps map the same block,
    removing the wasted fetches entirely."""
    jmax = jax.lax.div(
        jax.lax.add(jax.lax.mul(i, jnp.int32(bq)),
                    jnp.int32(bq - 1 + offset)),
        jnp.int32(bk))
    return jax.lax.min(j, jax.lax.max(jmax, jnp.int32(0)))


def _clamp_qi(qi, jk, bq, bk, offset):
    """Causal fetch clamp for the dkdv sweep: q blocks strictly above the
    diagonal contribute nothing for kv block jk; clamp to the first valid."""
    qi_min = jax.lax.max(
        jnp.int32(0),
        jax.lax.div(
            jax.lax.sub(jax.lax.mul(jk, jnp.int32(bk)), jnp.int32(offset)),
            jnp.int32(bq)))
    return jax.lax.max(qi, qi_min)


def _fwd_call(kern, q, k, v, bhq, sq, sk, d, bq, bk, nq, nk, hq, hk,
              interpret, causal):
    if causal:
        def kv_j(b, i, j):
            return (_kv_index(b, hq, hk),
                    _clamp_kv_j(j, i, bq, bk, sk - sq), 0)
    else:
        def kv_j(b, i, j):
            return (_kv_index(b, hq, hk), j, 0)

    # Mosaic's scoped-VMEM default is 16 MiB, and at bq = bk = 2048 the
    # f32 score tile alone is bq*bk*4 = 16 MiB: inside the LLaMA-arch
    # ZeRO-3 step on the v5e the compiler asked for 16.48 MiB and refused
    # the kernel by 496 KiB (PR 23; standalone and in the GPT-1.3B step
    # the same block squeezed under the limit). Where two score tiles (s
    # and exp(s - m) are live together) exceed the default, ask for
    # exactly that — 32 MiB of the v5e's 128 MiB VMEM at block 2048;
    # smaller blocks keep the default.
    score_tiles = 2 * bq * bk * 4
    vmem_limit = score_tiles if score_tiles > (16 << 20) else None
    return pl.pallas_call(
        kern,
        name="flash_fwd",
        grid=(bhq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_j),
            pl.BlockSpec((1, bk, d), kv_j),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bhq, 8, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=4 * bhq * sq * sk * d,
            bytes_accessed=(2 * bhq * sq * d + 2 * (bhq // (hq // hk)) * sk * d)
            * q.dtype.itemsize,
            transcendentals=bhq * sq * sk,
        ),
    )(q, k, v)


# ---------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, bq, bk, nk, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk, offset), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])         # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                               # [bq, bk]
        ds = p * (dp - delta_ref[0, 0][:, None])        # [bq, bk]
        dq_acc[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(ki * bk <= qi * bq + (bq - 1) + offset)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, bq, bk, nq, nq_total, offset):
    ki = pl.program_id(1)
    ji = pl.program_id(2)          # sweeps rep * nq q-blocks, sequential
    qi = ji % nq                   # q-block index within one q-head

    @pl.when(ji == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk, offset), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])         # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0][:, None])        # [bq, bk]
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # [bk, d]

    if causal:
        @pl.when(qi * bq + (bq - 1) + offset >= ki * bk)
        def _():
            compute()
    else:
        compute()

    @pl.when(ji == nq_total - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_acc, dv_acc,
                      *, scale, causal, bq, bk, nq, nq_total, nk, offset,
                      sq):
    """ONE kernel for dq AND dk/dv (VERDICT r3/r4 'fused dq+dkdv' probe,
    unblocked in r5): the dkv sweep already computes s/p/dp/ds per
    (ki, qi) tile — dq's contribution (scale * ds @ k) reuses them for
    one extra MXU op instead of a whole second kernel pass re-reading
    q/k/v/do and re-computing three matmuls per tile.

    The r3 blocker was cross-grid accumulation: dq[qi] accumulates over
    the OUTER grid dim (ki), which Mosaic's consecutive-revisit rule
    forbids for an output block. Resolution: dq lives in a per-(batch,
    kv-head) f32 VMEM scratch [rep*sq, d] (1-4MB — scratch persists
    across the sequential grid), accumulated via dynamic-slice adds, and
    the OUTPUT block (1, rep*sq, d) has a constant index per b — only
    consecutive revisits, written once at the final (ki, ji) step."""
    ki = pl.program_id(1)
    ji = pl.program_id(2)
    qi = ji % nq

    @pl.when((ki == 0) & (ji == 0))
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(ji == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk, offset), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])         # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])        # [bq, bk]
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [bk, d]
        # the fused extra: dq rows for this q-block accumulate in scratch
        row0 = pl.multiple_of((ji // nq) * sq + qi * bq, bq)
        dq_scr[pl.ds(row0, bq), :] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [bq, d]

    if causal:
        @pl.when(qi * bq + (bq - 1) + offset >= ki * bk)
        def _():
            compute()
    else:
        compute()

    @pl.when(ji == nq_total - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((ki == nk - 1) & (ji == nq_total - 1))
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_index_maps(hq, hk, rep, nq, bq, bk, offset, causal):
    """Shared by the split-dkv and fused backward pallas_calls: the
    q-head owning sweep step j, and the (clamped, causal-skipping)
    q-block fetch index."""
    def q_index(b, j):
        bi = b // hk
        hi = b % hk
        return bi * hq + hi * rep + j // nq

    if causal:
        def qi_of(jk, j):
            return _clamp_qi(jax.lax.rem(j, jnp.int32(nq)), jk, bq, bk,
                             offset)
    else:
        def qi_of(jk, j):
            return jax.lax.rem(j, jnp.int32(nq))
    return q_index, qi_of


def _bwd(q, k, v, o, lse, do, scale, causal, interpret, hq, hk):
    with jax.enable_x64(False):
        return _bwd_impl(q, k, v, o, lse, do, scale, causal, interpret,
                         hq, hk)


def _bwd_impl(q, k, v, o, lse, do, scale, causal, interpret, hq, hk):
    bhq, sq, d = q.shape
    bhk, sk, _ = k.shape
    # PTPU_FA_BWD_KBLOCK decouples the bwd k tile (uniform 2048 holds too
    # many live blocks and compile-OOMs; mixed tiles may fit)
    import os as _os

    bq = _bwd_block_for(sq)
    bk = _block_for(sk, env="PTPU_FA_BWD_KBLOCK",
                    default=int(_os.environ.get("PTPU_FA_BWD_BLOCK",
                                                "1024")))
    nq, nk = sq // bq, sk // bk
    rep = hq // hk
    offset = sk - sq

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # [bh, 8, sq] sublane-padded control tensors (Mosaic block tiling)
    lse8 = jnp.broadcast_to(lse[:, None, :], (lse.shape[0], 8, lse.shape[1]))
    delta8 = jnp.broadcast_to(delta[:, None, :],
                              (delta.shape[0], 8, delta.shape[1]))

    # Fused single-pass backward (default where the dq scratch fits):
    # measured on v5e 1.3B/b3 GPT 0.5596 -> 0.5788 MFU, LLaMA-arch
    # 0.6382 -> 0.6462 (r5 A/B, docs/ROUND5_RESPONSE.md).
    # PTPU_FA_FUSED_BWD=1 forces it, =0 forces the split kernels; unset ->
    # auto by VMEM budget (the [rep*sq, d] f32 dq scratch must leave room
    # for the k/v/do blocks).
    flag = _os.environ.get("PTPU_FA_FUSED_BWD", "")
    dq_scratch_bytes = rep * sq * d * 4
    use_fused = (flag != "0" if flag
                 else dq_scratch_bytes <= (8 << 20))
    if use_fused:
        return _bwd_fused(q, k, v, do, lse8, delta8, scale=scale,
                          causal=causal, interpret=interpret, hq=hq,
                          hk=hk, bq=bq, bk=bk, nq=nq, nk=nk, rep=rep,
                          offset=offset)

    if causal:
        def _dq_kv_j(b, i, j):
            return (_kv_index(b, hq, hk), _clamp_kv_j(j, i, bq, bk, offset), 0)
    else:
        def _dq_kv_j(b, i, j):
            return (_kv_index(b, hq, hk), j, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, offset=offset),
        name="flash_bwd_dq",
        grid=(bhq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), _dq_kv_j),
            pl.BlockSpec((1, bk, d), _dq_kv_j),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse8, delta8)

    # flat (batch*kv_head, j) -> the q-head block owning sweep step j
    _q_index, _qi_of = _bwd_index_maps(hq, hk, rep, nq, bq, bk, offset,
                                       causal)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nq_total=rep * nq,
                          offset=offset),
        name="flash_bwd_dkv",
        grid=(bhk, nk, rep * nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, jk, j: (_q_index(b, j), _qi_of(jk, j), 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bq, d), lambda b, jk, j: (_q_index(b, j), _qi_of(jk, j), 0)),
            pl.BlockSpec((1, 8, bq), lambda b, jk, j: (_q_index(b, j), 0, _qi_of(jk, j))),
            pl.BlockSpec((1, 8, bq), lambda b, jk, j: (_q_index(b, j), 0, _qi_of(jk, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhk, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse8, delta8)
    return dq, dk, dv


def _bwd_fused(q, k, v, do, lse8, delta8, *, scale, causal, interpret,
               hq, hk, bq, bk, nq, nk, rep, offset):
    """Single-pass backward: see _bwd_fused_kernel. dq comes back as
    [bhk, rep*sq, d] with q-heads contiguous per kv head — a pure
    reshape recovers [bhq, sq, d] (row bi*hq + hi*rep + r)."""
    bhq, sq, d = q.shape
    bhk, sk, _ = k.shape
    _q_index, _qi_of = _bwd_index_maps(hq, hk, rep, nq, bq, bk, offset,
                                       causal)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nq_total=rep * nq, nk=nk,
                          offset=offset, sq=sq),
        name="flash_bwd_fused",
        grid=(bhk, nk, rep * nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, jk, j: (_q_index(b, j), _qi_of(jk, j), 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bq, d), lambda b, jk, j: (_q_index(b, j), _qi_of(jk, j), 0)),
            pl.BlockSpec((1, 8, bq), lambda b, jk, j: (_q_index(b, j), 0, _qi_of(jk, j))),
            pl.BlockSpec((1, 8, bq), lambda b, jk, j: (_q_index(b, j), 0, _qi_of(jk, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, rep * sq, d), lambda b, jk, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda b, jk, j: (b, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, rep * sq, d), q.dtype),
            jax.ShapeDtypeStruct((bhk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhk, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep * sq, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse8, delta8)
    return dq.reshape(bhq, sq, d), dk, dv


# ---------------------------------------------------------------- public api

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, interpret, hq, hk):
    o, _ = _fwd(q, k, v, scale, causal, interpret, hq, hk)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, interpret, hq, hk):
    o, lse = _fwd(q, k, v, scale, causal, interpret, hq, hk)
    # name the residuals for selective remat: with a policy saving
    # attn_res/attn_lse the backward reuses them instead of re-running
    # this kernel just to regenerate lse (o is b*s*h*d, lse a tiny f32
    # sidecar — saving both removes a full fwd-kernel launch per layer
    # from the backward pass). Distinct from the model-level "attn_out"
    # tag so the two never double-save the same activation.
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "attn_res")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, interpret, hq, hk, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, scale, causal, interpret, hq, hk)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal=False, scale=None, interpret=None):
    """[B, S, H, D] flash attention. Differentiable (custom flash backward).

    GQA (fewer kv heads than q heads) reads shared kv heads via the kernel
    index maps — no materialised head repeat.
    """
    from . import use_interpret

    if interpret is None:
        interpret = use_interpret()
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hq % hk != 0:
        raise ValueError(f"q heads ({hq}) must be a multiple of kv heads ({hk})")
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    o = _flash(to_bh(q, hq), to_bh(k, hk), to_bh(v, hk), float(scale),
               bool(causal), bool(interpret), hq, hk)
    return from_bh(o, b, hq)


# Back-compat name used by nn.functional.flash_attention
flash_attention_fwd = flash_attention
