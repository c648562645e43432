"""Int8 activation checkpointing: quantized save points for selective remat.

The r3-r5 MFU climb was funded by HBM headroom bought by hand — factored
Adam, the int8 LM head, hand-picked ``save_only_these_names`` lists, and
hand-set batch caps. Every bf16 activation a remat policy
saves costs ``2 * B * S * dim`` bytes per layer; EQuARX-style blockwise
int8 (arXiv:2506.17615) stores the same residual at ~half that (1 byte of
mantissa + one fp32 scale per 256-elem block) with negligible quality
cost for bandwidth/memory-bound tensors.

``int8_checkpoint(x, name)`` is the save/restore pair: at checkpoint-save
time the tensor is quantized to blockwise int8 (+fp32 scales) and BOTH
pieces are tagged with ``checkpoint_name`` (``int8:<name>`` /
``int8:<name>:scale``); the value flowing downstream is the dequantized
round-trip, so the backward replay rebuilds it from the saved int8 pair
instead of re-running the producing matmuls. A ``custom_vjp`` makes the
round-trip a straight-through estimator — the cotangent passes through
exactly (round() would otherwise zero the gradient), the standard
quantised-training recipe shared with the int8 LM head
(incubate/nn/functional/_int8_head_core).

Exposed through the existing ``recompute_policy`` name syntax: an
``int8:<anchor>`` entry in a ``names:`` policy (parsed by
``parse_save_names``) switches that anchor's save point in
``models/gpt.py::_block_pure`` from a bf16 ``checkpoint_name`` to this
quantized pair. Unlike the exact-forward ``_ffn_i8`` block (whose
hand-written backward is specific to the swiglu FFN), this is generic
over any named anchor; the price is that forward numerics downstream of
the save point see the round-tripped value (the parity test bounds the
end-to-end loss drift <2%, tests/test_memory.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: block length for the per-block absmax scales (matches the 8-bit Adam
#: moment blocks, optimizer/__init__.py _Q8_BLOCK)
INT8_BLOCK = 256

#: absmax scale floor shared by every quantizer in the repo (blockwise int8
#: saves here, the serving KV rows, incubate fp8, and paddle_tpu/quant) — an
#: all-zero tensor divides by this instead of 0 and round-trips to exact 0.
SCALE_EPS = 1e-12


def quantize_blockwise_int8(x, block=INT8_BLOCK):
    """Blockwise absmax int8: flatten, pad to a block multiple, one fp32
    scale per ``block`` elements. Returns (q int8 [nb, block], s f32 [nb, 1])."""
    n = x.size
    xf = x.astype(jnp.float32).reshape(-1)
    pad = (-n) % block
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad,), jnp.float32)])
    xb = xf.reshape(-1, block)
    s = jnp.maximum(jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0,
                    SCALE_EPS)
    q = jnp.clip(jnp.round(xb / s), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_blockwise_int8(q, s, shape, dtype):
    """Inverse of quantize_blockwise_int8 for a tensor of ``shape``/``dtype``."""
    xf = (q.astype(jnp.float32) * s).reshape(-1)
    n = 1
    for d in shape:
        n *= int(d)
    return xf[:n].reshape(shape).astype(dtype)


def quantize_rows_int8(x, eps=SCALE_EPS):
    """Absmax int8 over the LAST axis: one fp32 scale per row.

    The paged-KV grid (docs/SERVING.md): the serving engine's int8 KV
    cache quantizes each (layer, kv-head, page-slot) row of ``head_dim``
    elements independently, so a single-token scatter write updates one
    block and its one scale without re-reading neighbours — the
    :func:`quantize_blockwise_int8` recipe with block = the row the page
    table already addresses. Returns ``(q int8 [..., D], s f32 [..., 1])``.
    """
    xf = x.astype(jnp.float32)
    s = jnp.maximum(
        jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0, eps)
    q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_rows_int8(q, s, dtype=None):
    """Inverse of :func:`quantize_rows_int8`; ``dtype`` casts the result
    (default: stay fp32)."""
    x = q.astype(jnp.float32) * s
    return x if dtype is None else x.astype(dtype)


def int8_saved_nbytes(numel, block=INT8_BLOCK):
    """Bytes one int8-saved tensor of ``numel`` elements holds in HBM
    (int8 payload + fp32 block scales, padding included)."""
    nb = (int(numel) + block - 1) // block
    return nb * block + nb * 4


@functools.lru_cache(maxsize=None)
def _int8_ckpt_fn(name, block):
    """One custom_vjp per (name, block): the tag string must be baked in
    (checkpoint_name takes a static python string), and lru_cache keeps
    the function identity stable so jit caches don't churn per call."""

    def roundtrip(x):
        q, s = quantize_blockwise_int8(x, block)
        q = checkpoint_name(q, f"int8:{name}")
        s = checkpoint_name(s, f"int8:{name}:scale")
        return dequantize_blockwise_int8(q, s, x.shape, x.dtype)

    @jax.custom_vjp
    def f(x):
        return roundtrip(x)

    def fwd(x):
        return roundtrip(x), None

    def bwd(_, g):
        # straight-through: the round-trip is treated as identity by AD
        return (g,)

    f.defvjp(fwd, bwd)
    return f


def int8_checkpoint(x, name, block=INT8_BLOCK):
    """Quantized remat save point. Under ``jax.checkpoint`` with a policy
    saving ``int8:<name>`` + ``int8:<name>:scale`` (what
    ``parse_save_names`` emits for an ``int8:<name>`` entry), the backward
    replay reconstructs this tensor from the saved int8 pair — ~half the
    HBM of a bf16 save. Without such a policy the tags are inert, but the
    forward still sees the round-tripped value."""
    return _int8_ckpt_fn(str(name), int(block))(x)


#: anchors tagged INSIDE custom kernels' vjps (pallas flash / rms) —
#: their save points are not routeable through ``int8_checkpoint``, so
#: an ``int8:`` request would silently drop the real save (the anchor
#: recomputes every backward) while claiming the memory win. Reject
#: loudly instead.
KERNEL_ANCHORS = frozenset({"attn_res", "attn_lse", "rms_rstd"})


def parse_save_names(spec):
    """Parse a comma-separated remat name list with optional ``int8:``
    prefixes (the payload of a ``names:`` recompute_policy).

    ``"attn_q,int8:resid_mid"`` -> (save_names, int8_names) where
    save_names = ("attn_q", "int8:resid_mid", "int8:resid_mid:scale")
    feeds ``jax.checkpoint_policies.save_only_these_names`` and
    int8_names = frozenset({"resid_mid"}) tells the model which anchors
    to route through :func:`int8_checkpoint`.
    """
    save, int8 = [], set()
    for raw in str(spec).split(","):
        nm = raw.strip()
        if not nm:
            continue
        if nm.startswith("int8:"):
            base = nm[len("int8:"):]
            if not base:
                raise ValueError(f"empty int8: entry in remat names {spec!r}")
            if base in KERNEL_ANCHORS:
                raise ValueError(
                    f"int8:{base}: {base!r} is tagged inside a custom "
                    "kernel's vjp and cannot be int8-saved — use the "
                    f"plain name {base!r} (eligible int8 anchors: "
                    "docs/MEMORY.md)")
            int8.add(base)
            save.append(f"int8:{base}")
            save.append(f"int8:{base}:scale")
        else:
            save.append(nm)
    return tuple(save), frozenset(int8)
