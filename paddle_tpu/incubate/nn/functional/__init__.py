"""Fused-op functional APIs (parity: python/paddle/incubate/nn/functional).

Reference implements these as hand-written CUDA fusions
(phi/kernels/fusion/gpu); on TPU they are either Pallas kernels (flash
attention path) or straight-line jnp that XLA fuses into single kernels —
measured to fuse fully under jit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....core.dispatch import apply_op


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6, begin_norm_axis=-1, bias=None, residual=None, quant_scale=-1, **kw):
    """RMS norm with optional fused bias/residual add.

    Matches the reference contract (incubate/nn/functional/fused_rms_norm.py:59):
    with ``residual`` the op returns ``(out, residual_out)`` where
    ``residual_out = x (+bias) + residual`` is the updated residual stream;
    without it, just ``out``. On TPU the norm runs the Pallas rms kernel
    (ops/pallas/rms_norm.py)."""
    def _frms(a, w, b, bias_in, res):
        if bias_in is not None:
            a = a + bias_in
        ax = begin_norm_axis % a.ndim
        rows = 1
        for s in a.shape[:-1]:
            rows *= s
        from ....ops.pallas import on_tpu_device

        fast = (ax == a.ndim - 1 and b is None and rows % 8 == 0
                and on_tpu_device())
        if res is not None:
            a = a + res
        if fast:
            from ....ops.pallas import rms_norm as _pallas_rms

            out = _pallas_rms(a, w, epsilon)
            return (out, a) if res is not None else out
        axes = tuple(range(ax, a.ndim))
        var = jnp.mean(jnp.square(a.astype(jnp.float32)), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)).astype(a.dtype)
        out = out * w
        if b is not None:
            out = out + b
        return (out, a) if res is not None else out

    return apply_op(_frms, x, norm_weight, norm_bias, bias, residual, _op_name="fused_rms_norm")


def fused_layer_norm(x, norm_weight, norm_bias=None, epsilon=1e-5, begin_norm_axis=-1, bias=None, residual=None, **kw):
    def _fln(a, w, b, bias_in, res):
        if bias_in is not None:
            a = a + bias_in
        if res is not None:
            a = a + res
        ax = begin_norm_axis % a.ndim
        axes = tuple(range(ax, a.ndim))
        af = a.astype(jnp.float32)
        mean = jnp.mean(af, axis=axes, keepdims=True)
        var = jnp.var(af, axis=axes, keepdims=True)
        out = ((af - mean) * jax.lax.rsqrt(var + epsilon)).astype(a.dtype)
        if w is not None:
            out = out * w
        if b is not None:
            out = out + b
        # reference contract: residual path returns (out, residual_out)
        return (out, a) if res is not None else out

    return apply_op(_fln, x, norm_weight, norm_bias, bias, residual, _op_name="fused_layer_norm")


def _apply_rotary(x, sin, cos, neox):
    """Shared rotary core: x [..., D] with sin/cos broadcastable [..., D/2].
    neox rotates halves; interleaved pairs otherwise."""
    d = x.shape[-1]
    if neox:
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def _rotary_sin_cos(pos, d, theta):
    """Standard rope table rows for integer positions `pos` -> [T, D/2]."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = pos.astype(jnp.float32)[..., None] * inv
    return jnp.sin(freqs), jnp.cos(freqs)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None, position_ids=None, use_neox_rotary_style=True, time_major=False, rotary_emb_base=10000.0):
    """parity: incubate/nn/functional/fused_rotary_position_embedding."""

    def _rope_one(x, sin_t, cos_t):
        if x is None:
            return None
        # x: [B, S, H, D]
        d = x.shape[-1]
        if sin_t is None:
            pos = jnp.arange(x.shape[1], dtype=jnp.float32)
            sin_l, cos_l = _rotary_sin_cos(pos, d, rotary_emb_base)
        else:
            sin_l = sin_t.reshape(sin_t.shape[-2], -1)[:, : d // 2]
            cos_l = cos_t.reshape(cos_t.shape[-2], -1)[:, : d // 2]
        sin_b = sin_l[None, :, None, :]
        cos_b = cos_l[None, :, None, :]
        return _apply_rotary(x, sin_b, cos_b, use_neox_rotary_style)

    def _rope(q_, k_, v_, sin_t, cos_t):
        return tuple(_rope_one(t, sin_t, cos_t) for t in (q_, k_, v_) if t is not None)

    outs = apply_op(_rope, q, k, v, sin, cos, _op_name="fused_rope")
    res = []
    it = iter(outs)
    for t in (q, k, v):
        res.append(next(it) if t is not None else None)
    return tuple(res)


def swiglu(x, y=None, name=None):
    from ....nn.functional.activation import swiglu as _swiglu

    return _swiglu(x, y)


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None, smooth=None, act_method="gelu", **kw):
    def _fba(a, b):
        if b is not None:
            a = a + b
        if act_method in ("gelu", "geglu"):
            return jax.nn.gelu(a)
        if act_method in ("swiglu",):
            a1, a2 = jnp.split(a, 2, axis=-1)
            return jax.nn.silu(a1) * a2
        return jax.nn.relu(a)

    return apply_op(_fba, x, bias, _op_name="fused_bias_act")


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    def _fl(a, w, b):
        if transpose_weight:
            w = w.T
        out = jnp.matmul(a, w)
        if b is not None:
            out = out + b
        return out

    return apply_op(_fl, x, weight, bias, _op_name="fused_linear")


def _quantize_rows_int8(a):
    """Per-row absmax int8 quantisation: a [R, H] -> (q int8, scale [R,1]).
    ONE implementation, shared with the chunked-CE head — the int8 parity
    gate probes the same quantizer every int8 path runs (lazy import: the
    fused-CE module is a leaf, but this package loads early)."""
    from ....nn.functional.fused_cross_entropy import _quantize_rows

    return _quantize_rows(a)


@jax.custom_vjp
def _int8_head_core(hc, w2, qw, sw):
    """int8 x int8 LM-head matmul: per-token-row scales on h, per-vocab-
    row scales on w — on int8-capable MXUs (v5e: 2x the bf16 rate) this
    halves the head's forward cost. VERDICT r3 slot: the optional int8
    weight-only LM-head, behind PTPU_INT8_HEAD with a parity test.

    The weight quantisation (qw, sw) is computed ONCE by the caller and
    passed in — re-quantising the [V, H] matrix inside every CE chunk
    (and again in each chunk's checkpointed backward) was a measured
    share of the flag's regression. ``w2`` rides along only so the
    straight-through backward can use the REAL weights."""
    qh, sh = _quantize_rows_int8(hc)
    acc = jnp.einsum("ch,vh->cv", qh, qw,
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sh * sw.T


def _int8_head_fwd(hc, w2, qw, sw):
    return _int8_head_core(hc, w2, qw, sw), (hc, w2)


def _int8_head_bwd(res, g):
    # wide backward: the quantised forward approximates the loss surface,
    # but gradients flow through the REAL weights (straight-through) —
    # the standard weight-quantised-training recipe
    import numpy as _np

    hc, w2 = res
    gf = g.astype(jnp.float32)
    dh = (gf @ w2.astype(jnp.float32)).astype(hc.dtype)
    dw = jnp.einsum("cv,ch->vh", gf,
                    hc.astype(jnp.float32)).astype(w2.dtype)
    # the quantised operands are derived values: int8 qw gets the float0
    # cotangent integers require; sw gets zeros (w2's dw is the real
    # grad). Shapes derive from w2 — qw matches it, sw is [V, 1] f32.
    dqw = _np.zeros(w2.shape, jax.dtypes.float0)
    dsw = jnp.zeros((w2.shape[0], 1), jnp.float32)
    return dh, dw, dqw, dsw


_int8_head_core.defvjp(_int8_head_fwd, _int8_head_bwd)


def _int8_head_logits(hc, w, transpose_y, qw=None, sw=None):
    w2 = w if transpose_y else w.T          # [V, H]
    if qw is None:
        qw, sw = _quantize_rows_int8(w2)
    return _int8_head_core(hc, w2, qw, sw)


def fused_linear_cross_entropy(x, weight, labels, transpose_y=True,
                               chunk_size=512, ignore_index=-100, name=None):
    """LM-head matmul + softmax cross entropy WITHOUT materializing the
    [N, vocab] logits (capability slot: the reference's fused CE path —
    c_softmax_with_cross_entropy / fused kernels in phi/kernels/fusion).

    Chunks the flattened rows; each chunk computes its logits with fp32
    accumulation, takes logsumexp, and is dropped — jax.checkpoint makes the
    backward recompute per chunk, so peak memory is O(chunk_size * vocab)
    instead of O(N * vocab). Returns the mean loss over non-ignored rows.

    x: [..., H] hidden states; weight: [V, H] (transpose_y=True, the tied
    embedding layout) or [H, V]; labels: [...] int.
    """
    def _flce(h, w, y):
        H = h.shape[-1]
        hf = h.reshape(-1, H)
        yf = y.reshape(-1).astype(jnp.int32)
        n = hf.shape[0]
        # bigger chunks = fewer serialized lax.map steps, more logits
        # resident (O(chunk * vocab) fp32)
        c = min(max(1, int(chunk_size)), n)
        pad = (-n) % c
        if pad:
            hf = jnp.concatenate([hf, jnp.zeros((pad, H), hf.dtype)])
            yf = jnp.concatenate([yf, jnp.full((pad,), ignore_index, yf.dtype)])
        valid = (yf != ignore_index)
        hs = hf.reshape(-1, c, H)
        ys = jnp.where(valid, yf, 0).reshape(-1, c)
        ms = valid.astype(jnp.float32).reshape(-1, c)

        spec = "ch,vh->cv" if transpose_y else "ch,hv->cv"
        # parity-gated default (PTPU_INT8_HEAD forces either way) — the
        # same resolver as the chunked-CE head, docs/PERF.md
        from ....nn.functional.fused_cross_entropy import int8_head_enabled

        int8_head = int8_head_enabled()
        if int8_head:
            # quantise the [V, H] weight ONCE for all chunks (and their
            # checkpointed backward recomputes)
            w2_full = w if transpose_y else w.T
            qw_full, sw_full = _quantize_rows_int8(
                jax.lax.stop_gradient(w2_full))

        def chunk_fn(args):
            hc, yc, mc = args
            if int8_head:
                logits = _int8_head_logits(hc, w, transpose_y,
                                           qw=qw_full, sw=sw_full)
            else:
                logits = jnp.einsum(spec, hc, w,
                                    preferred_element_type=jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
            return ((lse - gold) * mc).sum()

        sums = jax.lax.map(jax.checkpoint(chunk_fn), (hs, ys, ms))
        count = jnp.maximum(ms.sum(), 1.0)
        return sums.sum() / count

    return apply_op(_flce, x, weight, labels,
                    _op_name="fused_linear_cross_entropy")


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train", name=None):
    from ....nn.functional.common import dropout

    return dropout(x, p, training=training, mode=mode) + y


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    def _fmb(a, b, bias_a):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2)
        out = jnp.matmul(a, b)
        if bias_a is not None:
            out = out + bias_a
        return out

    return apply_op(_fmb, x, y, bias, _op_name="fused_matmul_bias")


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    out = fused_matmul_bias(x, y, bias, trans_x, trans_y)
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "none": lambda a: a, "": lambda a: a}[activation]
    return apply_op(act, out, _op_name="fused_linear_activation")


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, num_heads=None,
                               transpose_qkv_wb=False, name=None):
    """Functional fused MHA (fused_transformer.py parity).

    qkv_weight: [3, H, D, E] (or [E, 3E] with transpose_qkv_wb).
    """
    from .... import framework
    from ....nn.functional.flash_attention import sdpa_arrays

    if cache_kv is not None:
        raise NotImplementedError(
            "fused_multi_head_attention cache_kv: use the kv-cache decode "
            "path (models/llama.py generate) or masked_multihead_attention")
    drop_key = (framework.next_rng_key()
                if training and dropout_rate > 0.0 else None)
    attn_key = (framework.next_rng_key()
                if training and attn_dropout_rate > 0.0 else None)

    def _fmha(xa, qkvw, lw, pls, plb, lns, lnb, qkvb, lb, mask):
        b, s, e = xa.shape
        h = xa
        if pre_layer_norm:
            mean = jnp.mean(h.astype(jnp.float32), -1, keepdims=True)
            var = jnp.var(h.astype(jnp.float32), -1, keepdims=True)
            h = ((h - mean) * jax.lax.rsqrt(var + pre_ln_epsilon)).astype(xa.dtype)
            if pls is not None:
                h = h * pls
            if plb is not None:
                h = h + plb
        if transpose_qkv_wb:
            nh = num_heads
            qkv = h @ qkvw
            if qkvb is not None:
                qkv = qkv + qkvb
            q, k, v = jnp.split(qkv, 3, axis=-1)
            hd = e // nh
        else:
            three, nh, hd, _ = qkvw.shape
            qkv = jnp.einsum("bse,nhde->bsnhd", h, qkvw)
            if qkvb is not None:
                qkv = qkv + qkvb[None, None]
            q, k, v = qkv[:, :, 0].reshape(b, s, nh * hd), \
                qkv[:, :, 1].reshape(b, s, nh * hd), \
                qkv[:, :, 2].reshape(b, s, nh * hd)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nh, hd)
        v = v.reshape(b, s, nh, hd)
        if mask is not None or attn_key is not None:
            from ....nn.functional.flash_attention import _xla_sdpa

            out = _xla_sdpa(q, k, v, mask=mask,
                            dropout=attn_dropout_rate if attn_key is not None else 0.0,
                            key=attn_key)
        else:
            out = sdpa_arrays(q, k, v, causal=False)
        out = out.reshape(b, s, nh * hd)
        out = out @ lw
        if lb is not None:
            out = out + lb
        if drop_key is not None:
            keep = jax.random.bernoulli(drop_key, 1.0 - dropout_rate,
                                        out.shape)
            out = jnp.where(keep, out / (1.0 - dropout_rate), 0.0)
        if add_residual:
            out = xa + out
        if not pre_layer_norm:
            mean = jnp.mean(out.astype(jnp.float32), -1, keepdims=True)
            var = jnp.var(out.astype(jnp.float32), -1, keepdims=True)
            out = ((out - mean) * jax.lax.rsqrt(var + ln_epsilon)).astype(xa.dtype)
            if lns is not None:
                out = out * lns
            if lnb is not None:
                out = out + lnb
        return out

    return apply_op(_fmha, x, qkv_weight, linear_weight, pre_ln_scale,
                    pre_ln_bias, ln_scale, ln_bias, qkv_bias, linear_bias,
                    attn_mask, _op_name="fused_multi_head_attention")


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1, name=None):
    from .... import framework

    key1 = (framework.next_rng_key()
            if training and dropout1_rate > 0.0 else None)
    key2 = (framework.next_rng_key()
            if training and dropout2_rate > 0.0 else None)

    def _ffn(xa, w1, w2, b1, b2, s1, sb1, s2, sb2):
        h = xa
        def ln(a, scale, bias, eps):
            mean = jnp.mean(a.astype(jnp.float32), -1, keepdims=True)
            var = jnp.var(a.astype(jnp.float32), -1, keepdims=True)
            out = ((a - mean) * jax.lax.rsqrt(var + eps)).astype(a.dtype)
            if scale is not None:
                out = out * scale
            if bias is not None:
                out = out + bias
            return out

        if pre_layer_norm:
            h = ln(h, s1, sb1, ln1_epsilon)
        act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[activation]
        h = act(h @ w1 + (b1 if b1 is not None else 0))
        if key1 is not None:
            keep = jax.random.bernoulli(key1, 1.0 - dropout1_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout1_rate), 0.0)
        h = h @ w2 + (b2 if b2 is not None else 0)
        if key2 is not None:
            keep = jax.random.bernoulli(key2, 1.0 - dropout2_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout2_rate), 0.0)
        out = xa + h
        if not pre_layer_norm:
            out = ln(out, s2, sb2, ln2_epsilon)
        return out

    return apply_op(_ffn, x, linear1_weight, linear2_weight, linear1_bias,
                    linear2_bias, ln1_scale, ln1_bias, ln2_scale, ln2_bias,
                    _op_name="fused_feedforward")


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                            linear_weights, linear_biases, ffn_ln_scales,
                            ffn_ln_biases, ffn1_weights, ffn1_biases,
                            ffn2_weights, ffn2_biases, pre_layer_norm=True,
                            epsilon=1e-05, cache_kvs=None, pre_caches=None,
                            seq_lens=None, rotary_embs=None, time_step=None,
                            attn_mask=None, dropout_rate=0.0,
                            activation="gelu", training=False, mode=None,
                            trans_qkvw=True, ring_id=-1, name=None, **kw):
    """Stacked fused decoder inference layers (context/prefill form)."""
    if cache_kvs is not None or time_step is not None or pre_caches is not None:
        raise NotImplementedError(
            "fused_multi_transformer incremental decode (cache_kvs/"
            "time_step): use models/llama.py generate() — the fixed-shape "
            "kv-cache decode path")
    out = x
    n_layers = len(qkv_weights)
    for i in range(n_layers):
        out = fused_multi_head_attention(
            out, qkv_weights[i], linear_weights[i],
            pre_layer_norm=pre_layer_norm,
            pre_ln_scale=ln_scales[i] if ln_scales else None,
            pre_ln_bias=ln_biases[i] if ln_biases else None,
            qkv_bias=qkv_biases[i] if qkv_biases else None,
            linear_bias=linear_biases[i] if linear_biases else None,
            attn_mask=attn_mask, dropout_rate=dropout_rate,
            attn_dropout_rate=dropout_rate, training=training)
        out = fused_feedforward(
            out, ffn1_weights[i], ffn2_weights[i],
            linear1_bias=ffn1_biases[i] if ffn1_biases else None,
            linear2_bias=ffn2_biases[i] if ffn2_biases else None,
            ln1_scale=ffn_ln_scales[i] if ffn_ln_scales else None,
            ln1_bias=ffn_ln_biases[i] if ffn_ln_biases else None,
            pre_layer_norm=pre_layer_norm, activation=activation,
            dropout1_rate=dropout_rate, dropout2_rate=dropout_rate,
            training=training)
    return out


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, ln_epsilon=1e-5,
                                           training=True,
                                           mode="upscale_in_train",
                                           name=None):
    from .... import framework

    dkey = (framework.next_rng_key()
            if training and dropout_rate > 0.0 else None)

    def _f(xa, res, b, s, lb):
        h = xa + (b if b is not None else 0)
        if dkey is not None:
            keep = jax.random.bernoulli(dkey, 1.0 - dropout_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout_rate), 0.0)
        h = h + res
        mean = jnp.mean(h.astype(jnp.float32), -1, keepdims=True)
        var = jnp.var(h.astype(jnp.float32), -1, keepdims=True)
        out = ((h - mean) * jax.lax.rsqrt(var + ln_epsilon)).astype(xa.dtype)
        if s is not None:
            out = out * s
        if lb is not None:
            out = out + lb
        return out

    return apply_op(_f, x, residual, bias, ln_scale, ln_bias,
                    _op_name="fused_bias_dropout_residual_ln")


def fused_moe(x, gate_weight, expert_weights1, expert_biases1,
              expert_weights2, expert_biases2, moe_topk=2,
              norm_topk_prob=True, group_moe=False, name=None):
    """Fused MoE FFN (fusion/gpu fused_moe parity): top-k gate + stacked
    expert FFNs via the GShard dense-dispatch einsums."""
    from ....incubate.distributed.models.moe import _dense_dispatch_combine

    if group_moe:
        raise NotImplementedError("fused_moe group_moe")

    def _moe(xa, gw, w1, b1, w2, b2):
        shape = xa.shape
        m = shape[-1]
        flat = xa.reshape(-1, m)
        logits = flat @ gw
        e = logits.shape[-1]
        val, idx = jax.lax.top_k(logits, moe_topk)
        cap = flat.shape[0]  # full capacity: no drops in the fused op
        ei, comb = _dense_dispatch_combine(flat, idx, val, e, cap)
        if not norm_topk_prob:
            # reference weights by the full-softmax prob of each selected
            # expert (sum < 1); comb rows are renormalised — rescale back
            full = jax.nn.softmax(logits, -1)
            sel = jnp.take_along_axis(full, idx, -1).sum(-1)
            comb = comb * sel[:, None, None]
        h = jnp.einsum("ecm,emh->ech", ei, w1)
        if b1 is not None:
            h = h + b1[:, None]
        h = jax.nn.gelu(h)
        y = jnp.einsum("ech,ehm->ecm", h, w2)
        if b2 is not None:
            y = y + b2[:, None]
        out = jnp.einsum("nec,ecm->nm", comb.astype(jnp.float32),
                         y.astype(jnp.float32)).astype(xa.dtype)
        return out.reshape(shape)

    return apply_op(_moe, x, gate_weight, expert_weights1, expert_biases1,
                    expert_weights2, expert_biases2, _op_name="fused_moe")


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0,
                                               name=None):
    """Varlen attention: per-sequence validity masks over padded batches.
    Layout [B, H, S, D] (matches the cutlass op)."""
    import math as _math

    def _vl(q, k, v, sl, kvl, m):
        b, h, s, d = q.shape
        sc = scale if scale is not None else 1.0 / _math.sqrt(d)
        logits = jnp.einsum("bhsd,bhtd->bhst", q * sc, k)
        kpos = jnp.arange(k.shape[2])[None, None, None, :]
        valid = kpos < kvl.reshape(-1)[:, None, None, None]
        if causal:
            qpos = jnp.arange(s)[None, None, :, None]
            valid = valid & (kpos <= qpos + pre_cache_length)
        if m is not None:
            logits = logits + m
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        return jnp.einsum("bhst,bhtd->bhsd", probs, v)

    return apply_op(_vl, query, key, value, seq_lens, kv_seq_lens, mask,
                    _op_name="varlen_attention")


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               sequence_lengths=None, rotary_tensor=None,
                               beam_cache_offset=None, qkv_out_scale=None,
                               out_shift=None, out_smooth=None, seq_len=1,
                               rotary_emb_dims=0, use_neox_rotary_style=False,
                               compute_dtype="default",
                               out_scale=-1, quant_round_type=1,
                               quant_max_bound=127.0,
                               quant_min_bound=-127.0, name=None):
    """Single-token decode attention over a [2, B, H, MaxLen, D] cache
    (fusion/gpu masked_multihead_attention parity)."""
    def _mmha(xa, cache, b_in, mask, seq_lens):
        b = xa.shape[0]
        two, _, h, max_len, d = cache.shape
        qkv = xa.reshape(b, 3, h, d)
        if b_in is not None:
            qkv = qkv + b_in.reshape(1, 3, h, d)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        # per-batch write position = that row's current length
        if seq_lens is not None:
            cur = seq_lens.reshape(-1).astype(jnp.int32)  # [B]
        else:
            cur = jnp.zeros((b,), jnp.int32)
        bidx = jnp.arange(b)
        kc = cache[0].at[bidx, :, cur, :].set(k.astype(cache.dtype))
        vc = cache[1].at[bidx, :, cur, :].set(v.astype(cache.dtype))
        from ....ops.pallas import log_path_once, on_tpu_device

        if mask is None and on_tpu_device() and d <= 256 and max_len % 8 == 0:
            # pallas decode kernel (decode_attention.py): online softmax,
            # KV streamed through VMEM — the masked_multihead_attention
            # fusion slot on TPU
            from ....ops.pallas.decode_attention import decode_attention

            log_path_once("mmha", "pallas_decode")
            out = decode_attention(q.astype(kc.dtype), kc, vc, cur + 1)
            return out.reshape(b, h * d), jnp.stack([kc, vc])
        log_path_once("mmha", "xla_decode")
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
        logits = jnp.einsum("bhd,bhtd->bht", q * scale, kc)
        valid = (jnp.arange(max_len)[None, None, :]
                 <= cur[:, None, None])
        logits = jnp.where(valid, logits, -1e30)
        if mask is not None:
            logits = logits + mask.reshape(b, 1, -1)[:, :, :max_len]
        probs = jax.nn.softmax(logits, -1)
        out = jnp.einsum("bht,bhtd->bhd", probs, vc)
        return out.reshape(b, h * d), jnp.stack([kc, vc])

    return apply_op(_mmha, x, cache_kv, bias, src_mask, sequence_lengths,
                    _op_name="masked_multihead_attention")


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size):
    def _g(a, b):
        return jnp.max(a), jnp.max(b)

    return apply_op(_g, seq_lens_encoder, seq_lens_decoder,
                    _op_name="blha_get_max_len")


def paged_attention(q, k_pages, v_pages, block_tables, lengths, scale=None):
    """TPU-native paged-KV decode attention (the clean entry over the
    pallas kernel; `block_multihead_attention` is the reference-shaped
    wrapper). q [B, Hq, D]; pages [Hkv, NumPages, PageSize, D]."""
    from ....ops.pallas.decode_attention import paged_attention as _pa

    def _run(qa, kp, vp, bt, ln):
        return _pa(qa, kp, vp, bt, ln, scale=scale)

    return apply_op(_run, q, k_pages, v_pages, block_tables, lengths,
                    _op_name="paged_attention")


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_encoder,
                              seq_lens_decoder, seq_lens_this_time,
                              padding_offsets, cum_offsets, cu_seqlens_q,
                              cu_seqlens_k, block_tables, pre_key_cache=None,
                              pre_value_cache=None, cache_k_quant_scales=None,
                              cache_v_quant_scales=None,
                              cache_k_dequant_scales=None,
                              cache_v_dequant_scales=None, qkv_out_scale=None,
                              qkv_bias=None, out_shift=None, out_smooth=None,
                              max_enc_len_this_time=None,
                              max_dec_len_this_time=None, rope_emb=None,
                              mask=None, tgt_mask=None, max_seq_len=-1,
                              block_size=64, use_neox_style=False,
                              rope_theta=10000.0, **kwargs):
    """Paged-KV attention (parity: fusion/gpu block_multi_head_attention;
    python surface `incubate/nn/functional/block_multihead_attention.py:56`).

    Reference cache layout [MaxBlockNum, H, BlockSize, D] with
    block_tables [B, BlocksPerSeq]. Decode steps (every live slot's
    seq_lens_this_time <= 1) run the pallas paged kernel
    (`ops/pallas/decode_attention.py`) — finished slots (== 0) are simply
    excluded from the batch; prefill writes each sequence's tokens into
    its pages and runs causal attention per sequence (eager path — the
    serving engine drives steps eagerly). KV-cache int8 quantization is
    not implemented (raises). Returns (out, qkv, key_cache, value_cache).
    """
    import numpy as _np

    if any(s is not None for s in (cache_k_quant_scales, cache_v_quant_scales,
                                   cache_k_dequant_scales,
                                   cache_v_dequant_scales, qkv_out_scale,
                                   out_shift, out_smooth)):
        raise NotImplementedError(
            "block_multihead_attention: int8 KV-cache / output quantization "
            "is not implemented on the TPU path")

    def _to_arr(t):
        return t.value if hasattr(t, "value") else (
            t._data if hasattr(t, "_data") else t)

    qkv_a = _to_arr(qkv)
    kc = _to_arr(key_cache)
    vc = _to_arr(value_cache)
    tables = _to_arr(block_tables).astype(jnp.int32)
    enc = _np.asarray(_to_arr(seq_lens_encoder)).reshape(-1)
    dec = _np.asarray(_to_arr(seq_lens_decoder)).reshape(-1)
    this = _np.asarray(_to_arr(seq_lens_this_time)).reshape(-1)
    rope = None if rope_emb is None else _to_arr(rope_emb)
    tmask = None if tgt_mask is None else _to_arr(tgt_mask)
    pmask = None if mask is None else _to_arr(mask)
    b = this.shape[0]
    nblocks, h, bsz, d = kc.shape           # h = kv heads
    hq = qkv_a.shape[-1] // d - 2 * h       # GQA: qkv packs [hq + 2*h] heads

    if qkv_bias is not None:
        qkv_a = qkv_a + _to_arr(qkv_bias).reshape(1, -1)

    def _split_qkv(rows):
        """[T, (hq+2h)*d] -> q [T,hq,d], k [T,h,d], v [T,h,d]."""
        t = rows.shape[0]
        flat = rows.reshape(t, hq + 2 * h, d)
        return flat[:, :hq], flat[:, hq:hq + h], flat[:, hq + h:]

    def _rope_at(x, pos, seq_idx):
        """Rotary at integer positions, [T, H, D]. Uses the CALLER's rope
        table (rope_emb [2, B, max_seq, 1, D/2]: [0]=cos rows, [1]=sin —
        NTK/linear scaling arrives through the table, never recomputed)."""
        if rope is not None:
            cos_t = rope[0, seq_idx, pos].reshape(pos.shape[0], 1, -1)
            sin_t = rope[1, seq_idx, pos].reshape(pos.shape[0], 1, -1)
        else:
            sin_t, cos_t = _rotary_sin_cos(pos, d, rope_theta)
            sin_t, cos_t = sin_t[:, None, :], cos_t[:, None, :]
        return _apply_rotary(x, sin_t, cos_t, use_neox_style)

    use_rope = rope_emb is not None
    live = this > 0

    if (this[live] == 1).all() and (enc == 0).all():
        # ---- decode: one token per LIVE slot, pallas paged kernel ------
        active = _np.nonzero(live)[0]                       # slot ids, in order
        ba = len(active)
        act = jnp.asarray(active, jnp.int32)
        cur = jnp.asarray(dec[active], jnp.int32)           # cached lengths
        tab_a = tables[act]                                 # [Ba, pages]

        def _decode(rows, kc, vc):
            q, k, v = _split_qkv(rows)                      # [Ba, hq|h, D]
            if use_rope:
                q = _rope_at(q, cur, act)
                k = _rope_at(k, cur, act)
            page_ids = tab_a[jnp.arange(ba), cur // bsz]    # [Ba]
            offs = cur % bsz
            kc = kc.at[page_ids, :, offs, :].set(k.astype(kc.dtype))
            vc = vc.at[page_ids, :, offs, :].set(v.astype(vc.dtype))
            from ....ops.pallas import log_path_once

            if tmask is None:
                from ....ops.pallas.decode_attention import (
                    paged_attention as _pa,
                )

                log_path_once("blha", "pallas_paged")
                out = _pa(q, jnp.swapaxes(kc, 0, 1), jnp.swapaxes(vc, 0, 1),
                          tab_a, cur + 1)
            else:
                # masked decode: dense gather fallback (kernel is unmasked)
                log_path_once("blha", "xla_paged_masked")
                kd = jnp.swapaxes(kc[tab_a], 1, 2).reshape(ba, h, -1, d)
                vd = jnp.swapaxes(vc[tab_a], 1, 2).reshape(ba, h, -1, d)
                s = kd.shape[2]
                kd = jnp.repeat(kd, hq // h, 1).astype(jnp.float32)
                vd = jnp.repeat(vd, hq // h, 1).astype(jnp.float32)
                logits = jnp.einsum(
                    "bhd,bhtd->bht", q.astype(jnp.float32) / (d ** 0.5), kd)
                valid = jnp.arange(s)[None, None, :] <= cur[:, None, None]
                logits = jnp.where(valid, logits, -1e30)
                logits = logits + tmask.reshape(b, 1, -1)[act, :, :s]
                out = jnp.einsum("bht,bhtd->bhd",
                                 jax.nn.softmax(logits, -1), vd)
            return out.reshape(ba, hq * d).astype(rows.dtype), kc, vc

        out, kc, vc = apply_op(_decode, qkv_a, kc, vc, _op_name="blha_decode")
    else:
        # ---- prefill / mixed: eager per-sequence causal attention -------
        from ....ops.pallas import log_path_once

        log_path_once("blha", "xla_prefill")
        cu = _np.zeros(b + 1, _np.int64)
        _np.cumsum(this, out=cu[1:])

        def _prefill(qkv_a, kc, vc):
            outs = []
            for i in range(b):
                t = int(this[i])
                if t == 0:
                    continue
                q, k, v = _split_qkv(qkv_a[int(cu[i]): int(cu[i]) + t])
                start = int(dec[i])
                pos = jnp.arange(start, start + t)
                if use_rope:
                    q, k = _rope_at(q, pos, i), _rope_at(k, pos, i)
                pids = tables[i, (_np.arange(start, start + t) // bsz)]
                offs = jnp.asarray(_np.arange(start, start + t) % bsz)
                kc = kc.at[pids, :, offs, :].set(k.astype(kc.dtype))
                vc = vc.at[pids, :, offs, :].set(v.astype(vc.dtype))
                # causal attention over this sequence's full cache
                total = start + t
                npg = (total + bsz - 1) // bsz
                kseq = jnp.concatenate(
                    [kc[tables[i, pg]] for pg in range(npg)], axis=1)[:, :total]
                vseq = jnp.concatenate(
                    [vc[tables[i, pg]] for pg in range(npg)], axis=1)[:, :total]
                if hq != h:                                  # GQA repeat
                    kseq = jnp.repeat(kseq, hq // h, axis=0)
                    vseq = jnp.repeat(vseq, hq // h, axis=0)
                logits = jnp.einsum(
                    "thd,hxd->htx", q.astype(jnp.float32) / (d ** 0.5),
                    kseq.astype(jnp.float32))
                qpos = pos[None, :, None]
                kpos = jnp.arange(total)[None, None, :]
                logits = jnp.where(kpos <= qpos, logits, -1e30)
                if pmask is not None:
                    logits = logits + pmask[i, 0][start:start + t, :total][None]
                probs = jax.nn.softmax(logits, -1)
                o = jnp.einsum("htx,hxd->thd", probs, vseq.astype(jnp.float32))
                outs.append(o.reshape(t, hq * d).astype(qkv_a.dtype))
            return jnp.concatenate(outs, axis=0), kc, vc

        out, kc, vc = apply_op(_prefill, qkv_a, kc, vc,
                               _op_name="blha_prefill")

    from ....core.tensor import Tensor as _T

    def _wrap(x):
        return x if isinstance(x, _T) else _T(x)

    return _wrap(out), qkv, _wrap(kc), _wrap(vc)

from .fp8 import fp8_gemm, fp8_linear  # noqa: E402,F401
