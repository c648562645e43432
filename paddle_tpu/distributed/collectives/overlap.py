"""Bucketed backward-overlap for the dp gradient reduce.

The pre-PR step leaves the dp grad all-reduce entirely to GSPMD, which
emits ONE fused psum over the whole flattened grad tree — it cannot
start until the LAST gradient of the backward walk exists, so reduce
time serializes after compute ("Optimizing Distributed ML Communication
with Fused Computation-Collective Operations", PAPERS.md, motivates
breaking exactly this barrier). Here the grad tree is partitioned into
size-bounded buckets (paddle parity: EagerReducer's comm_buffer_size
bucketing, reducer.h:88) and each bucket is reduced by its OWN
collective whose operands are only that bucket's grads — the dataflow
lets XLA's scheduler issue a bucket's reduce as soon as its gradients
are produced in the backward walk, hiding it under the remaining
backward compute instead of after it.

Caveat (honest): for the scan-over-layers ``StackedDecoder`` every
stacked parameter's gradient finishes only when the backward scan
completes, so cross-layer overlap needs the unrolled path
(``PTPU_SCAN_LAYERS=0``); bucket separation still overlaps the embedding
/head/norm reduces with the decoder backward, and caps the collective's
working-set vs one tree-sized fusion.

Buckets are split by (exact-vs-quantized, dtype) so exact buckets psum
in their native dtype — elementwise identical to per-tensor psum, which
the parity tests check bitwise.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp

from .quantized import QUANT_BLOCK, quantized_psum, quantized_wire_bytes

#: default bucket bound (MB) — mirrors the reference DataParallel
#: comm_buffer_size=25 default, rounded to a power of two
DEFAULT_BUCKET_MB = 32

#: grads smaller than this quantize poorly relative to their collective's
#: latency cost — they stay exact (norms/biases are also name-excluded)
DEFAULT_MIN_QUANT_NUMEL = 65536

#: name fragments whose tensors always reduce exactly (ISSUE: "norms,
#: embeddings stay exact")
EXACT_NAME_FRAGMENTS = ("norm", "ln", "bias", "embed", "lm_head", "scale")


def bucket_bytes_cap():
    mb = float(os.environ.get("PTPU_COMM_BUCKET_MB", DEFAULT_BUCKET_MB))
    return int(mb * 2**20) if mb > 0 else 0


def min_quant_numel():
    return int(os.environ.get("PTPU_QUANT_MIN_NUMEL",
                              DEFAULT_MIN_QUANT_NUMEL))


def is_exact_grad(name, shape, dtype=None):
    """Per-tensor opt-out: small/sensitive tensors reduce exactly.
    ``PTPU_QUANT_EXCLUDE`` appends comma-separated name fragments."""
    numel = 1
    for d in shape:
        numel *= int(d)
    if numel < min_quant_numel() or len(shape) <= 1:
        return True
    frags = EXACT_NAME_FRAGMENTS + tuple(
        f for f in os.environ.get("PTPU_QUANT_EXCLUDE", "").split(",") if f)
    low = name.lower()
    return any(f in low for f in frags)


@dataclasses.dataclass(frozen=True)
class GradBucket:
    names: tuple          # leaf names, reduce order
    numels: tuple         # flattened element counts, aligned with names
    dtype: str
    quantized: bool

    @property
    def numel(self):
        return sum(self.numels)

    @property
    def payload_bytes(self):
        """Bytes ENTERING the reduce (the pre-PR exact cost basis)."""
        return self.numel * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class GradReducePlan:
    """Static description of one step's dp-grad reduce, built once at
    TrainStep build time (parallel_step._build_reduce_plan): which mesh
    axes are manual, and how the grad tree partitions into buckets.

    Under ``sharding_stage >= 2`` on a pure-data mesh the step builds a
    :class:`~.zero.ZeroPlan` instead — it duck-types this accounting
    surface (calls/bytes/summary) and additionally reduce-SCATTERS each
    bucket into the dp-sharded update's layout (docs/ZERO.md)."""
    axes: tuple           # manual mesh axis names the reduce runs over
    nranks: int
    buckets: tuple        # GradBucket, issue order
    quant_block: int = QUANT_BLOCK

    @property
    def axis_label(self):
        return "+".join(self.axes)

    @property
    def exact_bytes(self):
        return sum(b.payload_bytes for b in self.buckets if not b.quantized)

    @property
    def quantized_payload_bytes(self):
        return sum(b.payload_bytes for b in self.buckets if b.quantized)

    @property
    def quantized_wire_bytes(self):
        return sum(
            quantized_wire_bytes(b.numel, self.nranks, block=self.quant_block)
            for b in self.buckets if b.quantized)

    @property
    def calls(self):
        return len(self.buckets)

    def summary(self):
        """JSON-able shape for the bench/dryrun "comms" block."""
        return {
            "axes": list(self.axes), "nranks": self.nranks,
            "buckets": len(self.buckets),
            "quantized_buckets": sum(1 for b in self.buckets if b.quantized),
            "exact_bytes": int(self.exact_bytes),
            "quantized_payload_bytes": int(self.quantized_payload_bytes),
            "quantized_wire_bytes": int(self.quantized_wire_bytes),
            "quantized_fraction": (
                float(self.quantized_payload_bytes)
                / float(self.exact_bytes + self.quantized_payload_bytes)
                if self.buckets else 0.0),
        }


#: layer-index fragment in a per-layer parameter name
#: ("model.layers.3.attn.q_proj.weight" -> family
#: "model.layers.*.attn.q_proj.weight")
_LAYER_IDX_RE = re.compile(r"(?<=\.)\d+(?=\.)")


def slab_grouping_enabled():
    """``PTPU_COMM_SLAB=1``: group per-layer grad leaves of the same
    weight family into ONE bucket per slab (docs/SCAN.md). The scanned
    eager model keeps per-layer parameter leaves while the stacked
    flagship carries one [L, ...] leaf per weight kind — slab grouping
    makes the per-layer tree's reduce plan match the stacked tree's
    (one collective per slab, one per non-layer tensor) so the wire
    behavior doesn't depend on which layout the model stores. Off by
    default: the size-capped partition below is the measured r6 plan."""
    return os.environ.get("PTPU_COMM_SLAB", "") not in ("", "0")


def _slab_key(name):
    # wildcard ONLY the first (layer) index: a second index (MoE
    # expert ordinals, "...layers.3.mlp.experts.5.weight") stays
    # literal — in the stacked layout each expert is its own [L, ...]
    # leaf, so each expert must be its own slab family too
    return _LAYER_IDX_RE.sub("*", name, count=1)


def _partition_slabs(named_shapes, quantized):
    """One GradBucket per (weight family, exactness, dtype), first-seen
    order; non-layer-indexed tensors are their own single-leaf family
    (mirroring the stacked layout, where each slab IS one leaf)."""
    fams = {}
    order = []
    for name, shape, dtype in named_shapes:
        numel = 1
        for d in shape:
            numel *= int(d)
        dt = str(jnp.dtype(dtype))
        q = quantized and not is_exact_grad(name, shape, dtype)
        key = (_slab_key(name), q, dt)
        if key not in fams:
            fams[key] = []
            order.append(key)
        fams[key].append((name, numel))
    return tuple(
        GradBucket(names=tuple(n for n, _ in fams[k]),
                   numels=tuple(m for _, m in fams[k]),
                   dtype=k[2], quantized=k[1])
        for k in order)


def partition_buckets(named_shapes, bucket_bytes=None, quantized=True,
                      slab=None):
    """Partition ``[(name, shape, dtype), ...]`` (reduce order) into
    size-bounded :class:`GradBucket`\\ s. Consecutive leaves of the same
    (exactness, dtype) share a bucket up to ``bucket_bytes``; an
    oversized leaf gets its own bucket (never split — the collective
    granularity is a whole tensor). ``bucket_bytes=0`` = one bucket per
    tensor. ``slab`` (default: ``PTPU_COMM_SLAB``) switches to one
    bucket per per-layer weight family — see
    :func:`slab_grouping_enabled`."""
    if slab is None:
        slab = slab_grouping_enabled()
    if slab:
        return _partition_slabs(named_shapes, quantized)
    if bucket_bytes is None:
        bucket_bytes = bucket_bytes_cap()
    buckets, cur, cur_bytes, cur_key = [], [], 0, None
    quant_on = quantized

    def flush():
        nonlocal cur, cur_bytes
        if cur:
            q, dt = cur_key
            buckets.append(GradBucket(
                names=tuple(n for n, _ in cur),
                numels=tuple(m for _, m in cur), dtype=dt, quantized=q))
        cur, cur_bytes = [], 0

    for name, shape, dtype in named_shapes:
        numel = 1
        for d in shape:
            numel *= int(d)
        dt = str(jnp.dtype(dtype))
        q = quant_on and not is_exact_grad(name, shape, dtype)
        nbytes = numel * jnp.dtype(dtype).itemsize
        key = (q, dt)
        if cur and (key != cur_key
                    or (bucket_bytes and cur_bytes + nbytes > bucket_bytes)):
            flush()
        cur_key = key
        cur.append((name, numel))
        cur_bytes += nbytes
        if not bucket_bytes or cur_bytes >= bucket_bytes:
            flush()  # bucket_bytes=0: one collective per tensor
    flush()
    return tuple(buckets)


def reduce_grads(grads, plan, *, mean=True):
    """Apply the planned bucketed reduce to a ``{name: grad}`` tree.

    Runs PER-SHARD inside the manual region of ``plan.axes`` — each
    bucket's leaves are flattened into one contiguous operand and reduced
    by one collective (exact psum in the native dtype, or the
    shared-scale int8 psum kernel). ``mean=True`` divides by nranks (the
    dp-mean convention matching d(global mean loss)/dparam)."""
    out = dict(grads)
    inv = 1.0 / plan.nranks
    for bucket in plan.buckets:
        flats = [grads[n].reshape(-1) for n in bucket.names]
        buf = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        if bucket.quantized:
            red = quantized_psum(buf, plan.axes, plan.nranks,
                                 block=plan.quant_block, mean=mean)
        else:
            red = jax.lax.psum(buf, plan.axes)
            if mean:
                red = (red * jnp.asarray(inv, jnp.float32).astype(red.dtype)
                       if jnp.issubdtype(red.dtype, jnp.floating)
                       else red // plan.nranks)
        off = 0
        for name, numel in zip(bucket.names, bucket.numels):
            out[name] = red[off:off + numel].reshape(grads[name].shape)
            off += numel
    return out
