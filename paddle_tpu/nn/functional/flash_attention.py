"""Attention functionals (parity: python/paddle/nn/functional/flash_attention.py:358).

On TPU the flash-attention capability slot (reference: CUDA flashattn lib at
``phi/kernels/gpu/flash_attn_kernel.cu``) is filled by a Pallas splash/flash
kernel when running on real TPU hardware; CPU test meshes and shapes Mosaic
cannot tile take a pure-XLA path that still fuses well.

Layout note: paddle attention tensors are [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import apply_op


def _xla_sdpa(q, k, v, mask=None, causal=False, dropout=0.0, scale=None, key=None):
    """Reference attention in pure XLA: [B, S, H, D] layout."""
    q, k, v = _constrain_heads_over_mp(q, k, v)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # [B,H,S,D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh * scale, kh)
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((s, t), bool), t - s)
        logits = jnp.where(cmask, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def _use_pallas(q_shape):
    from ...ops.pallas import on_tpu_device

    if not on_tpu_device():
        return False
    from ...ops.pallas.flash_attention import supported_seq

    b, s, h, d = q_shape
    # the kernel needs Mosaic-tileable seq blocks and the whole head_dim in
    # VMEM; other shapes fall back to the XLA path
    return supported_seq(s) and d <= 256


def _constrain_heads_over_mp(q, k, v):
    """spmd rule `flash_attention` (distributed/spmd_rules.py): shard the
    heads dim over "mp", never seq_kv or head_dim. Binds the Megatron
    attention layout inside jit instead of trusting propagation (the
    explicit analogue of `flash_attn_spmd_rule`)."""
    from ...distributed.fleet import active_mesh
    from ...distributed.spmd_rules import constraints_enabled

    from ...distributed import collectives as _coll

    if _coll.in_manual_grad_region():
        # inside the composed/quantized manual region (docs/COMMS.md)
        # every live axis is already manual — a with_sharding_constraint
        # naming 'mp' there is illegal, and the per-shard trace already
        # holds exactly its head slice
        return q, k, v
    mesh = active_mesh()
    mp_size = (
        mesh.get_dim_size("mp")
        if mesh is not None and "mp" in mesh.dim_names
        else 1
    )
    if mp_size == 1 or q.ndim != 4 or not constraints_enabled():
        return q, k, v
    from jax.sharding import PartitionSpec

    from ...distributed.auto_parallel import shard_activation
    from ...distributed.spmd_rules import DistTensorSpec, get_spmd_rule

    mp = mesh.dim_names.index("mp")
    specs = [DistTensorSpec(list(t.shape), [-1, -1, mp, -1]) for t in (q, k, v)]
    ins, _ = get_spmd_rule("flash_attention").infer_forward(*specs)
    # Pin only the semantic dims the rule decides: heads over "mp",
    # head_dim replicated. Batch and seq stay UNCONSTRAINED so GSPMD keeps
    # whatever dp/sharding/sep layout the surrounding program chose (sep
    # shards the sequence dim; forcing it here would gather the sequence).
    # GQA: constrain each tensor independently — an MQA/GQA kv with
    # indivisible heads is skipped while q still gets pinned.
    U = PartitionSpec.UNCONSTRAINED
    out = []
    for t, s in zip((q, k, v), ins):
        if t.shape[2] % mp_size != 0:
            out.append(t)
            continue
        rule_spec = s.partition_spec(mesh.dim_names)
        ext = list(rule_spec) + [None] * (4 - len(rule_spec))
        spec = PartitionSpec(U, U, ext[2], ext[3])
        out.append(shard_activation(t, mesh=mesh, spec=spec))
    return tuple(out)


def sdpa_arrays(q, k, v, causal=True, scale=None):
    """Array-level attention: the pallas flash kernel on a TPU for
    Mosaic-tileable shapes (:func:`_use_pallas`), XLA SDPA otherwise.
    The shape test is the whole choice: an error from the kernel
    propagates, it never selects the XLA path.

    The single dispatch point shared by the functional API and the pure
    model paths (models/gpt.py stacked decoder)."""
    from ...ops.pallas import log_path_once

    q, k, v = _constrain_heads_over_mp(q, k, v)
    if _use_pallas(q.shape):
        from ...ops.pallas import flash_attention as _fa_kernel

        out = _fa_kernel(q, k, v, causal=causal, scale=scale)
        log_path_once("sdpa", "pallas_flash")
        return out
    log_path_once("sdpa", "xla_sdpa")
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return _xla_sdpa(q, k, v, causal=causal, scale=scale)


def flash_attention(
    query,
    key,
    value,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    from ... import framework

    drop_key = framework.next_rng_key() if (dropout > 0.0 and training) else None

    def _fa(q, k, v):
        if dropout == 0.0 or not training:
            return sdpa_arrays(q, k, v, causal=causal)
        return _xla_sdpa(q, k, v, causal=causal, dropout=dropout, key=drop_key)

    out = apply_op(_fa, query, key, value, _op_name="flash_attention")
    if return_softmax:
        return out, None
    return out, None


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p=0.0,
    is_causal=False,
    training=True,
    name=None,
):
    """parity: nn/functional/flash_attention.py:1139 — [B,S,H,D] layout."""
    from ... import framework

    drop_key = framework.next_rng_key() if (dropout_p > 0.0 and training) else None

    def _sdpa(q, k, v, m):
        if m is None and (dropout_p == 0.0 or not training):
            return sdpa_arrays(q, k, v, causal=is_causal)
        return _xla_sdpa(
            q, k, v, mask=m, causal=is_causal,
            dropout=dropout_p if training else 0.0, key=drop_key,
        )

    return apply_op(_sdpa, query, key, value, attn_mask, _op_name="sdpa")


def flashmask_attention(
    query, key, value, startend_row_indices=None, dropout=0.0, causal=False,
    window_size=None, return_softmax_lse=False, return_seed_offset=False,
    fixed_seed_offset=None, rng_name="", training=True, name=None,
):
    """Sparse-mask attention (parity: flash_attention.py:1299 flashmask).

    startend_row_indices: [B, H, S, 1] (causal) — LT masking: key j is masked
    for query rows >= start index. Fallback builds the dense mask.
    """
    if startend_row_indices is None:
        return flash_attention(query, key, value, dropout, causal, training=training)[0]

    def _fm(q, k, v, sri):
        b, s, h, d = q.shape
        rows = jnp.arange(s)[:, None, None]  # query index
        start = jnp.swapaxes(sri, 1, 2)  # [B, S, H, n]
        # mask[b, h, i, j]: allowed if i < start[b, j, h, 0]
        st = sri[..., 0]  # [B, H, S_k]
        i_idx = jnp.arange(s)[None, None, :, None]
        allowed = i_idx < st[:, :, None, :]
        if causal:
            j_idx = jnp.arange(s)[None, None, None, :]
            allowed = allowed & (j_idx <= i_idx)
        logits_mask = jnp.where(allowed, 0.0, -jnp.inf)
        return _xla_sdpa(q, k, v, mask=logits_mask, causal=False)

    out = apply_op(_fm, query, key, value, startend_row_indices, _op_name="flashmask_attention")
    if return_softmax_lse or return_seed_offset:
        return (out, None, None)[: 1 + int(return_softmax_lse) + int(return_seed_offset)]
    return out


def sdp_kernel(*a, **k):  # compat context manager
    import contextlib

    return contextlib.nullcontext()


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen attention over packed sequences (parity:
    nn/functional/flash_attention.py:756 flash_attn_unpadded).

    query/key/value: [total_tokens, num_heads, head_dim] with sequences
    packed back to back; cu_seqlens_*: [batch+1] int32 cumulative
    offsets. TPU-native form: one dense segment-masked attention — the
    segment-id mask keeps cross-sequence scores at -inf and XLA fuses the
    mask into the softmax; per-sequence dynamic shapes would defeat the
    compiler, so the packed layout IS the fast path on TPU."""
    def _varlen(q, k, v, cq, ck):
        tq, h, d = q.shape
        tk = k.shape[0]
        # segment id per token: index of the sequence it belongs to
        seg_q = jnp.searchsorted(cq, jnp.arange(tq), side="right") - 1
        seg_k = jnp.searchsorted(ck, jnp.arange(tk), side="right") - 1
        # position within the sequence (for causal masking)
        pos_q = jnp.arange(tq) - cq[seg_q]
        pos_k = jnp.arange(tk) - ck[seg_k]
        qf = q.astype(jnp.float32) * scale
        logits = jnp.einsum("qhd,khd->hqk", qf, k.astype(jnp.float32))
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            mask = mask & (pos_k[None, :] <= pos_q[:, None])
        logits = jnp.where(mask[None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        if dropout > 0.0 and training:
            from ... import framework

            keep = jax.random.bernoulli(
                framework.next_rng_key(), 1.0 - dropout, probs.shape)
            probs = probs * keep / (1.0 - dropout)
        out = jnp.einsum("hqk,khd->qhd", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)

    out = apply_op(_varlen, query, key, value, cu_seqlens_q, cu_seqlens_k,
                   _op_name="flash_attn_unpadded")
    return out, None
