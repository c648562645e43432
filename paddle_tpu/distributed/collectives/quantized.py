"""Quantized all-reduce kernels (EQuARX-style, arXiv:2506.17615).

Two lowerings of the same contract — ``psum(x)`` over a named mesh axis
with blockwise-int8 wire format and EXACT integer accumulation:

- :func:`quantized_psum` — shared-scale int8 + lane-packed int32 psum.
  The per-256-block scales are pmax-shared across ranks first, so every
  rank's int8 codes live on one grid and the cross-rank sum can ride a
  single integer AllReduce (two 8-bit lanes biased into each int32 word,
  carry-free for <=128 ranks). AllReduce is the ONLY collective this
  path emits, which makes it safe inside partial-auto (manual-subgroup)
  ``shard_map`` regions: this XLA build hard-crashes the SPMD
  partitioner on AllGather/ReduceScatter/CollectivePermute with manual
  subgroups (the same limitation behind the pre-existing pipeline test
  failures), but AllReduce lowers fine. This is the kernel the
  ``ShardedTrainStep`` dp-grad reduce uses.

- :func:`quantized_all_reduce_rs_ag` — the full EQuARX decomposition:
  quantize -> reduce-scatter with int32 accumulation -> dequant ->
  re-quantize -> all-gather. ~1 byte/element on the wire in BOTH phases
  (vs 2 for bf16, 4 for f32) at the cost of a second quantization
  round-trip. Requires a FULLY-manual region (every mesh axis manual),
  which is where ReduceScatter/AllGather lower correctly here — the
  eager collective API's 1-D group meshes qualify.

Both kernels bound the per-element error by ``block_absmax / 127`` per
quantization phase (one phase for the psum kernel, two for rs+ag); the
shared-scale psum kernel's integer accumulation adds NO further error.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: block length for the per-block absmax scales — matches the int8
#: activation-checkpoint blocks (memory/int8_ckpt.INT8_BLOCK)
QUANT_BLOCK = 256

#: lane packing rides two biased 8-bit codes per int32 word; the hi
#: lane's worst-case sum is 255 * nranks * 2**16, which must stay under
#: int32 — carry-free through 128 ranks
_PACK_MAX_RANKS = 128


def _blockify(x, block):
    """Flatten to f32 [nb, block] (zero-padded) + (shape, dtype, numel)."""
    shape, dtype = x.shape, x.dtype
    xf = x.astype(jnp.float32).reshape(-1)
    n = xf.size
    pad = (-n) % block
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad,), jnp.float32)])
    return xf.reshape(-1, block), (shape, dtype, n)


def _unblockify(xb, meta):
    shape, dtype, n = meta
    return xb.reshape(-1)[:n].reshape(shape).astype(dtype)


def quantize_shared_scale_int8(x, axis_names, block=QUANT_BLOCK):
    """Blockwise int8 with ONE scale grid shared by every rank on
    ``axis_names`` (per-block absmax pmax'd across ranks). Must run
    inside a shard_map region where those axes are manual. Returns
    (q int32 codes in [-127, 127], scale f32 [nb, 1], meta)."""
    xb, meta = _blockify(x, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    amax = jax.lax.pmax(amax, axis_names)
    scale = jnp.maximum(amax / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int32)
    return q, scale, meta


def _pack_lanes_default():
    """Lane packing halves the AllReduce payload on a real interconnect
    but is pure extra arithmetic when the "wire" is an in-process memcpy
    — ON for accelerator backends, OFF for the CPU host-platform
    simulation."""
    return jax.default_backend() not in ("cpu",)


def packed_int32_psum(q, axis_names, nranks, pack=None):
    """psum int8-range codes (as int32) over ``axis_names``, packing two
    biased lanes per int32 word when carry-free (nranks <= 128 and an
    even trailing dim) — halves the AllReduce payload vs raw int32."""
    if pack is None:
        pack = _pack_lanes_default()
    if not pack or nranks > _PACK_MAX_RANKS or q.shape[-1] % 2:
        return jax.lax.psum(q, axis_names)
    qb = q + 128                                   # [1, 255]: lanes stay >= 0
    packed = qb[..., 1::2] * 65536 + qb[..., 0::2]
    s = jax.lax.psum(packed, axis_names)
    lo = s % 65536 - 128 * nranks
    hi = s // 65536 - 128 * nranks
    out = jnp.stack([lo, hi], axis=-1)             # [..., half, 2]
    return out.reshape(q.shape)


def quantized_psum(x, axis_names, nranks, *, block=QUANT_BLOCK, mean=False):
    """Shared-scale blockwise-int8 psum of ``x`` over manual
    ``axis_names``. AllReduce-only lowering (partial-auto safe); exact
    int32 accumulation; per-element error <= shared_block_absmax/127.
    ``mean=True`` folds the 1/nranks into the pre-quantization scaling so
    the shared scales see the final magnitudes."""
    if mean:
        x = x / nranks
    q, scale, meta = quantize_shared_scale_int8(x, axis_names, block)
    s = packed_int32_psum(q, axis_names, nranks)
    return _unblockify(s.astype(jnp.float32) * scale, meta)


def quantized_all_reduce_rs_ag(x, axis_name, nranks, *, block=QUANT_BLOCK,
                               mean=False):
    """EQuARX pipeline: int8 quantize -> reduce-scatter (int32 accum) ->
    dequant -> re-quantize -> all-gather -> dequant. FULLY-manual regions
    only (see module docstring); ~1 byte/element wire format per phase."""
    if mean:
        x = x / nranks
    # pad so the block grid splits evenly into nranks scatter chunks
    xb, meta = _blockify(x, block)
    nb = xb.shape[0]
    pad_rows = (-nb) % nranks
    if pad_rows:
        xb = jnp.concatenate(
            [xb, jnp.zeros((pad_rows, block), jnp.float32)])
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    amax = jax.lax.pmax(amax, axis_name)
    scale = jnp.maximum(amax / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    # int32-accumulated reduce-scatter: rank r receives the exact integer
    # sums of its block-row chunk (127 * nranks stays far inside int32)
    ssum = jax.lax.psum_scatter(q.astype(jnp.int32), axis_name,
                                scatter_dimension=0, tiled=True)
    # this rank's rows of the SHARED scale grid, without lax.axis_index
    # (PartitionId does not lower on every runtime): scatter-summing a
    # replicated value yields nranks * my_rows
    my_scale = jax.lax.psum_scatter(scale, axis_name, scatter_dimension=0,
                                    tiled=True) / nranks
    chunk = ssum.astype(jnp.float32) * my_scale
    # phase 2: re-quantize the reduced chunk for the gather
    amax2 = jnp.maximum(jnp.max(jnp.abs(chunk), axis=-1, keepdims=True),
                        1e-30)
    s2 = amax2 / 127.0
    q2 = jnp.clip(jnp.round(chunk / s2), -127, 127).astype(jnp.int8)
    qg = jax.lax.all_gather(q2, axis_name, axis=0, tiled=True)
    sg = jax.lax.all_gather(s2, axis_name, axis=0, tiled=True)
    out = qg.astype(jnp.float32) * sg
    if pad_rows:
        out = out[:nb]
    return _unblockify(out, meta)


def quantized_wire_bytes(numel, nranks, *, block=QUANT_BLOCK, impl="psum"):
    """Approximate per-rank wire bytes one quantized reduce moves, for
    the telemetry split (docs/COMMS.md). psum: 2 B/elem packed-int32
    AllReduce + the f32 scale grid; rsag: ~1 B/elem per phase."""
    nb = (int(numel) + block - 1) // block
    scales = nb * 4
    if impl == "rsag":
        return 2 * int(numel) + 2 * scales
    payload = int(numel) * (2 if nranks <= _PACK_MAX_RANKS else 4)
    return payload + scales
