"""True ZeRO execution mode: reduce-scattered grads, dp-sharded weight
update, just-in-time parameter gathers (docs/ZERO.md).

Pre-PR, ``group_sharded_parallel(level="p_g_os")`` only stamped
``Shard(0)`` placements and hoped GSPMD did something reasonable: the
grad reduce stayed a full all-reduce, optimizer slots replicated on the
hot path, and the PR 6 :class:`~.overlap.GradReducePlan` explicitly
declined any param sharded over a data axis. This module is the real
thing — the blueprint is "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (PAPERS.md) plus the EQuARX int8
reduce-scatter (PR 6, :mod:`.quantized`):

- **Stage 3** (``p_g_os``): params stay RESIDENT as their GSPMD dim
  shards (``shard_model_parameters`` placements). Inside the step's
  fully-manual region they are all-gathered just-in-time for the
  forward — the stacked decoder's ``[L, ...]`` weight slabs gather
  per-layer INSIDE the ``lax.scan`` body (:func:`jit_gather_scope`,
  models/gpt.py), so layer *l+1*'s slab gather can overlap layer *l*'s
  compute when the scan is unrolled >= 2 wide. AD of the gather IS the
  reduce-scatter (``all_gather`` transposes to ``psum_scatter``), so
  every sharded param's gradient arrives already scattered into its
  1/degree dim slice — exact, f32 — and the optimizer update runs
  directly on the shard with param-shaped, dp-sharded slots.
- **Stage 2** (``os_g``): params keep replicated storage; each grad
  tensor is reduce-SCATTERED into a flat 1/degree chunk (the EQuARX
  int8 integer-accumulated scatter for quantizable tensors — bitwise
  identical to the replicated int8 all-reduce because integer sums are
  order-free; full psum + static slice for exact tensors — same
  summation order as the replicated path), the update runs on the
  chunk against flat dp-sharded slots, and the updated chunks
  all-gather back into full params.

Numerics contract (proven float32-hex in tests/test_zero3.py on the
1xN CPU mesh): engaging stage 2 or stage 3 changes NOTHING versus the
replicated data-parallel manual path — same per-shard loss, same grad
values, same update bytes. ``PTPU_QUANT_COLLECTIVES=0`` (the PR 6
master escape hatch) disengages the whole mode and restores the pre-PR
GSPMD placement-hint program byte-for-byte; ``PTPU_ZERO_MODE=0``
disengages just this mode while keeping the PR 6 replicated plan
eligible.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp

from .overlap import is_exact_grad
from .quantized import QUANT_BLOCK, _blockify, quantize_shared_scale_int8

#: group_sharded_parallel level -> ZeRO stage
STAGE_LEVELS = {"os": 1, "os_g": 2, "p_g_os": 3}


def zero_mode_enabled():
    """The zero execution mode rides behind BOTH the PR 6 master switch
    (``PTPU_QUANT_COLLECTIVES=0`` must reproduce the pre-PR program
    byte-for-byte, and the pre-PR stage-3 program is the GSPMD
    placement-hint path) and its own ``PTPU_ZERO_MODE`` knob."""
    from . import quant_collectives_enabled

    if not quant_collectives_enabled():
        return False
    return os.environ.get("PTPU_ZERO_MODE", "1") not in ("0", "off")


def jit_gather_enabled():
    """``PTPU_ZERO_JIT_GATHER`` (default on): defer stacked-decoder slab
    gathers into the scan body (fsdp-style; remat re-gathers in
    backward). ``=0`` gathers every param up front instead — the layout
    and numerics are identical (proven hex in tests), only the gather
    timing moves."""
    return os.environ.get("PTPU_ZERO_JIT_GATHER", "1") not in ("0", "off")


def param_gather_quantized():
    """``PTPU_QUANT_PARAM_GATHER=1``: ride the stage-3 param gathers on
    the PR 6 int8 all-gather (codes + f32 scales on the wire, ~1B/elem).
    Default OFF — unlike gradient traffic, int8 params perturb the
    forward, so the exact gather is the default and the bitwise-parity
    contract. Master switch (``PTPU_QUANT_COLLECTIVES``) also gates.

    Stacking rule (docs/QUANT.md): with the knob UNSET and quantized
    compute force-engaged (``PTPU_QUANT_COMPUTE`` truthy), the int8
    gathers ride along — the forward already runs narrow scaled GEMMs,
    so int8 param perturbation is inside the mode's numerics contract
    and stage-3 traffic halves for free. An explicit ``0``/``off``
    always wins."""
    from . import quant_collectives_enabled

    if not quant_collectives_enabled():
        return False
    env = os.environ.get("PTPU_QUANT_PARAM_GATHER", "")
    if env not in ("", "0", "off"):
        return True
    if env in ("0", "off"):
        return False
    from ...quant import quant_compute_forced

    return quant_compute_forced()


def flat_padded_len(numel, degree, *, quantized, block=QUANT_BLOCK):
    """Padded flat length for a stage-2 chunk-sharded tensor. Quantized
    tensors pad to the int8 block GRID (the scatter moves whole
    [block]-rows, keeping the shared-scale grid identical to the
    replicated ``quantized_psum`` — the bitwise-parity invariant);
    exact tensors pad only to the shard degree."""
    numel = int(numel)
    degree = int(degree)
    if quantized:
        nb = -(-numel // block)
        nb = -(-nb // degree) * degree
        return nb * block
    return -(-numel // degree) * degree


@dataclasses.dataclass(frozen=True)
class ZeroParam:
    """Per-parameter shard recipe inside a :class:`ZeroPlan`.

    kind:
    - ``dim``: storage-sharded (stage 3 GSPMD placement, ``shard_dim``
      over the shard axis). Gathered in-region (up front, or in the
      scan body when ``deferred_attr`` names a StackedDecoder slab);
      grads arrive as exact dim slices via AD; slots are param-shaped
      and follow the param's placement.
    - ``flat``: storage-replicated, update-sharded (stage 2, and
      stage-3 params with no divisible dim). Grad reduce-scatters into
      a flat chunk (int8 when ``quantized``); slots are flat
      ``[padded]`` arrays sharded over the shard axis; the updated
      chunks all-gather back to a full param.
    - ``replicated``: tiny tensors — exact psum + replicated update,
      exactly the PR 6 path.
    """
    name: str
    kind: str
    shape: tuple
    dtype: str
    numel: int
    shard_dim: int | None = None
    deferred_attr: str | None = None
    quantized: bool = False
    padded: int | None = None
    spec: object | None = None      # PartitionSpec of the dim storage

    @property
    def nbytes(self):
        return self.numel * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class ZeroPlan:
    """Static description of one step's ZeRO execution, resolved at
    TrainStep build time (knobs read at BUILD, never per call). Duck-
    types the :class:`~.overlap.GradReducePlan` accounting surface so
    ``note_grad_reduce`` / the bench "comms" block work unchanged, and
    adds the zero accounting behind the bench "zero" block."""
    stage: int
    axes: tuple            # live data axes (the reduce axes)
    shard_axis: str        # the axis params/slots/chunks shard over
    shard_degree: int
    nranks: int            # product over axes (the grad-mean divisor)
    params: tuple          # ZeroParam, state-dict order
    gather_quantized: bool = False
    quant_block: int = QUANT_BLOCK

    @functools.cached_property
    def by_name(self):
        return {p.name: p for p in self.params}

    @property
    def dp_axes(self):
        return tuple(a for a in self.axes if a != self.shard_axis)

    # -- GradReducePlan-compatible accounting (docs/COMMS.md basis:
    # payload bytes ENTERING each grad collective) ----------------------
    @property
    def axis_label(self):
        return "+".join(self.axes)

    @property
    def calls(self):
        return len(self.params)

    @property
    def exact_bytes(self):
        return sum(p.nbytes for p in self.params if not p.quantized)

    @property
    def quantized_payload_bytes(self):
        return sum(p.nbytes for p in self.params if p.quantized)

    @property
    def quantized_wire_bytes(self):
        """~1B/elem int8 codes + the f32 scale grid per quantized
        reduce-scatter (the EQuARX rs phase; docs/ZERO.md)."""
        total = 0
        for p in self.params:
            if p.quantized:
                nb = -(-p.numel // self.quant_block)
                total += p.numel + 4 * nb
        return total

    # -- zero accounting -------------------------------------------------
    @property
    def dim_gather_bytes(self):
        """Full-param bytes of the stage-3 ``dim`` gathers per step (one
        forward gather per dim param; the scan-deferred slabs re-gather
        in the remat backward — counted once here; the telemetry basis
        is gathered bytes OUT of the collective). This is the traffic
        ``PTPU_QUANT_PARAM_GATHER`` moves onto the int8 wire."""
        return sum(p.nbytes for p in self.params if p.kind == "dim")

    @property
    def flat_gather_bytes(self):
        """Padded bytes of the stage-2 post-update chunk all-gathers —
        always the exact wire (the quantized-gather knob only covers
        dim gathers; updated WEIGHTS must reassemble bitwise)."""
        return sum(p.padded * jnp.dtype(p.dtype).itemsize
                   for p in self.params if p.kind == "flat")

    @property
    def param_gather_bytes(self):
        """Full-param bytes materialized by gathers per step: dim
        forward gathers + flat post-update chunk gathers."""
        return self.dim_gather_bytes + self.flat_gather_bytes

    @property
    def grad_rs_bytes(self):
        """Grad bytes entering a reduce-scatter (dim-kind AD scatters +
        flat quantized scatters; exact flat/replicated tensors ride a
        full psum and are not counted here)."""
        return sum(p.nbytes for p in self.params
                   if p.kind == "dim" or (p.kind == "flat" and p.quantized))

    def counts(self):
        out = {"dim": 0, "flat": 0, "replicated": 0, "deferred": 0}
        for p in self.params:
            out[p.kind] += 1
            if p.deferred_attr:
                out["deferred"] += 1
        return out

    def zero_summary(self):
        """JSON-able shape of the ``"zero"`` block."""
        return {
            "stage": self.stage,
            "shard_axis": self.shard_axis,
            "shard_degree": self.shard_degree,
            "axes": list(self.axes),
            "engaged": True,
            "params": self.counts(),
            "param_gather_bytes_per_step": int(self.param_gather_bytes),
            "grad_rs_bytes_per_step": int(self.grad_rs_bytes),
            "quantized_param_gather": bool(self.gather_quantized),
        }

    def summary(self):
        """GradReducePlan-shaped comms summary + the zero block."""
        qp = self.quantized_payload_bytes
        eb = self.exact_bytes
        return {
            "axes": list(self.axes), "nranks": self.nranks,
            "buckets": self.calls,
            "quantized_buckets": sum(1 for p in self.params if p.quantized),
            "exact_bytes": int(eb),
            "quantized_payload_bytes": int(qp),
            "quantized_wire_bytes": int(self.quantized_wire_bytes),
            "quantized_fraction": (float(qp) / float(eb + qp)
                                   if (eb + qp) else 0.0),
            "zero": self.zero_summary(),
        }


def resolve_stage(optimizer, explicit=None):
    """ZeRO stage: an explicit ``sharding_stage`` wins; else the
    ``group_sharded_parallel`` level mark on the optimizer; else 0."""
    if explicit is not None:
        return int(explicit)
    level = getattr(optimizer, "_group_sharded_level", None)
    return STAGE_LEVELS.get(level, 0)


def build_zero_plan(named_entries, mesh, stage, *, optimizer=None,
                    grad_clip=None, deferred=None, reason_out=None):
    """Resolve the ZeRO execution plan for a ShardedTrainStep, or None
    (``reason_out``, when given, receives the structured
    :class:`~.compose.Reason` for a decline).

    ``named_entries``: ``[(name, tensor)]`` for the trainable params in
    state-dict order. Engages only when provably safe on this runtime:

    - stage >= 2 and the mode knobs on (:func:`zero_mode_enabled`);
    - the live mesh axes are a subset of {dp, sharding} — a live mp/pp/
      sep/ep axis keeps the GSPMD path (the fully-manual region this
      mode needs cannot nest their kernels' own manual regions, and
      partial-auto regions reject gather/scatter on this XLA,
      docs/COMMS.md runtime limits);
    - the optimizer's update is elementwise (factored/int8-moment
      variants compute cross-element statistics that are wrong on a
      shard) and grad clip is not the per-tensor-norm variant;
    - param placements are consistent with the stage (stage-2 marks
      with data-axis param shards fall back to GSPMD).
    """
    from .compose import Reason
    from .compose import note_decline as _note

    if stage < 2:
        return _note(reason_out, Reason.STAGE_LT_2)
    if not zero_mode_enabled():
        from . import quant_collectives_enabled

        return _note(reason_out,
                     Reason.MASTER_OFF if not quant_collectives_enabled()
                     else Reason.ZERO_MODE_OFF)
    live = {a: mesh.get_dim_size(a) for a in mesh.dim_names
            if mesh.get_dim_size(a) > 1}
    if not live or not set(live) <= {"dp", "sharding"}:
        return _note(reason_out, Reason.MESH_AXES)
    shard_axis = "sharding" if "sharding" in live else "dp"
    degree = live[shard_axis]
    if degree <= 1:
        return _note(reason_out, Reason.NO_DATA_AXIS)
    if optimizer is not None and (
            getattr(optimizer, "_factored", False)
            or getattr(optimizer, "_moment_dtype", None)):
        return _note(reason_out, Reason.OPTIMIZER_STATS)
    from ...nn.clip import ClipGradByNorm

    if isinstance(grad_clip, ClipGradByNorm):
        # per-tensor norms need the full grad tensor
        return _note(reason_out, Reason.CLIP_BY_NORM)
    from . import grads_quantized
    from ..auto_parallel import Shard, placements_to_spec

    deferred = deferred or {}
    quant = grads_quantized()
    jit_gather = jit_gather_enabled()
    params = []
    nranks = 1
    for a in live:
        nranks *= live[a]
    for name, t in named_entries:
        arr = t._data
        shape = tuple(int(d) for d in arr.shape)
        numel = 1
        for d in shape:
            numel *= d
        dtype = str(jnp.dtype(arr.dtype))
        da = getattr(t, "_dist_attr", None)
        sdim = None
        spec = None
        if da is not None:
            for ax_name, pl in zip(da.process_mesh.dim_names, da.placements):
                if not isinstance(pl, Shard):
                    continue
                if ax_name == shard_axis:
                    sdim = pl.dim
                elif da.process_mesh.get_dim_size(ax_name) > 1:
                    # sharded over an axis this plan can't own
                    return _note(reason_out, Reason.MESH_AXES)
            if sdim is not None:
                spec = placements_to_spec(da.process_mesh, da.placements)
        if sdim is not None:
            if stage < 3:
                # stage-2 marks + stage-3 placements: GSPMD
                return _note(reason_out, Reason.ZERO3_PLACEMENT)
            attr = deferred.get(name)
            params.append(ZeroParam(
                name, "dim", shape, dtype, numel, shard_dim=sdim,
                deferred_attr=(attr if (attr and sdim >= 1 and jit_gather)
                               else None),
                spec=spec))
        elif numel >= degree and shape and jnp.issubdtype(
                jnp.dtype(dtype), jnp.inexact):
            q = quant and not is_exact_grad(name, shape, dtype)
            params.append(ZeroParam(
                name, "flat", shape, dtype, numel, quantized=q,
                padded=flat_padded_len(numel, degree, quantized=q)))
        else:
            params.append(ZeroParam(name, "replicated", shape, dtype, numel))
    if not any(p.kind in ("dim", "flat") for p in params):
        return _note(reason_out, Reason.NO_SHARDABLE_STATE)
    return ZeroPlan(stage=stage,
                    axes=tuple(a for a in ("dp", "sharding") if a in live),
                    shard_axis=shard_axis, shard_degree=degree,
                    nranks=nranks, params=tuple(params),
                    gather_quantized=param_gather_quantized())


# ---------------------------------------------------------------------------
# In-region collectives (all called per-shard inside the fully-manual
# shard_map region the ShardedTrainStep opens)
# ---------------------------------------------------------------------------
def _q_gather_impl(x, axis_name, dim, degree, block):
    # the PR 6 int8 grid, via the shared helpers (NOT an inline copy —
    # the wire format must stay byte-compatible with quantized.py's):
    # _blockify pads the flat shard to [nb, block], and the scale recipe
    # matches quantize_shared_scale_int8 / quantized_all_reduce_rs_ag
    # (amax/127 clamped at 1e-30) — here per-SOURCE-shard, no pmax,
    # since each rank publishes its own shard's codes
    xb, (shard_shape, dtype, n) = _blockify(x, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    qg = jax.lax.all_gather(q, axis_name, tiled=False)       # [S, nb, B]
    sg = jax.lax.all_gather(scale, axis_name, tiled=False)   # [S, nb, 1]
    deq = (qg.astype(jnp.float32) * sg).reshape(degree, -1)[:, :n]
    pieces = [deq[i].reshape(shard_shape).astype(dtype)
              for i in range(degree)]
    return jnp.concatenate(pieces, axis=dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _q_gather(x, axis_name, dim, degree, block):
    return _q_gather_impl(x, axis_name, dim, degree, block)


def _q_gather_fwd(x, axis_name, dim, degree, block):
    return _q_gather_impl(x, axis_name, dim, degree, block), None


def _q_gather_bwd(axis_name, dim, degree, block, _res, g):
    # backward = the EXACT gather's transpose (psum_scatter to this
    # rank's dim slice): jnp.round's zero derivative must not kill the
    # gathered params' gradients, and keeping the grad reduce exact is
    # the same wide-backward discipline as the int8 FFN saves (the
    # output dtype equals the shard dtype, so no cast is needed)
    return (jax.lax.psum_scatter(g, axis_name, scatter_dimension=dim,
                                 tiled=True),)


_q_gather.defvjp(_q_gather_fwd, _q_gather_bwd)


def gather_shard(x, axis_name, dim, *, degree=None, quantized=False,
                 block=QUANT_BLOCK):
    """All-gather a dim-sharded value back to its full shape.

    Exact (default): one tiled ``all_gather`` over ``axis_name`` at
    ``dim`` — reconstructs the original bytes exactly, and AD transposes
    it to the ``psum_scatter`` that IS the stage-3 grad reduce.

    ``quantized=True`` (``PTPU_QUANT_PARAM_GATHER``): the PR 6 int8
    all-gather phase — each rank quantizes its shard blockwise (codes +
    f32 scales on the wire, ~1B/elem), the codes gather, and the full
    value dequantizes per source shard. The backward is hand-written as
    the exact gather's transpose (``psum_scatter``), so gradients stay
    exact while only the forward weights ride int8."""
    if not quantized:
        return jax.lax.all_gather(x, axis_name, axis=dim, tiled=True)
    if degree is None:
        raise ValueError("quantized gather_shard needs the shard degree")
    return _q_gather(x, axis_name, dim, degree, block)


def _mean_scale(red, inv, nranks):
    """The exact-bucket mean convention of ``overlap.reduce_grads`` —
    reused verbatim so zero-mode exact reduces are bitwise identical to
    the replicated plan's."""
    if jnp.issubdtype(red.dtype, jnp.floating):
        return red * jnp.asarray(inv, jnp.float32).astype(red.dtype)
    return red // nranks


def reduce_grad(g, zp, plan, ordinal, *, mean=True):
    """Reduce one param's gradient into its update layout (per-shard).

    - ``dim``: AD already reduce-scattered over the shard axis; psum the
      remaining data axes and apply the mean scale.
    - ``flat`` quantized: shared-scale int8 (the SAME flat grid as the
      replicated ``quantized_psum`` — pmax over ALL reduce axes), int32
      codes psum over dp then psum_scatter over the shard axis (integer
      accumulation: bitwise-equal to the replicated all-reduce chunk),
      dequantized against this rank's scale rows.
    - ``flat`` exact: full psum in the replicated path's summation
      order, then a static chunk slice — parity over wire savings for
      the opted-out tensors (their slots still shard).
    - ``replicated``: the PR 6 exact per-tensor psum.
    """
    axes = plan.axes
    inv = 1.0 / plan.nranks
    if zp.kind == "dim":
        dp = plan.dp_axes
        if dp:
            g = jax.lax.psum(g, dp)
        return _mean_scale(g, inv, plan.nranks) if mean else g
    if zp.kind == "replicated":
        red = jax.lax.psum(g.reshape(-1), axes)
        if mean:
            red = _mean_scale(red, inv, plan.nranks)
        return red.reshape(zp.shape)
    # flat
    S = plan.shard_degree
    chunk = zp.padded // S
    if zp.quantized:
        x = g.reshape(-1)
        if mean:
            x = x / plan.nranks
        q, scale, _meta = quantize_shared_scale_int8(x, axes,
                                                     plan.quant_block)
        nb = q.shape[0]
        nb_pad = zp.padded // plan.quant_block
        if nb_pad > nb:
            q = jnp.pad(q, ((0, nb_pad - nb), (0, 0)))
            scale = jnp.pad(scale, ((0, nb_pad - nb), (0, 0)))
        dp = plan.dp_axes
        if dp:
            q = jax.lax.psum(q, dp)
        qc = jax.lax.psum_scatter(q, plan.shard_axis, scatter_dimension=0,
                                  tiled=True)
        rows = nb_pad // S
        sc = jax.lax.dynamic_slice(
            scale, (ordinal * rows, jnp.zeros((), ordinal.dtype)), (rows, 1))
        return (qc.astype(jnp.float32) * sc).reshape(-1).astype(g.dtype)
    red = jax.lax.psum(g.reshape(-1), axes)
    if mean:
        red = _mean_scale(red, inv, plan.nranks)
    if zp.padded > zp.numel:
        red = jnp.pad(red, (0, zp.padded - zp.numel))
    return jax.lax.dynamic_slice(red, (ordinal * chunk,), (chunk,))


def update_view(params, plan, ordinal):
    """Param values in the UPDATE layout: dim shards pass through (they
    enter the region as their storage shard), flat params slice this
    rank's padded chunk, replicated pass through."""
    out = {}
    for zp in plan.params:
        p = params[zp.name]
        if zp.kind == "flat":
            chunk = zp.padded // plan.shard_degree
            flat = p.reshape(-1)
            if zp.padded > zp.numel:
                flat = jnp.pad(flat, (0, zp.padded - zp.numel))
            out[zp.name] = jax.lax.dynamic_slice(
                flat, (ordinal * chunk,), (chunk,))
        else:
            out[zp.name] = p
    return out


def params_out(new_upd, plan):
    """Updated values back in the STORAGE layout: flat chunks all-gather
    into full params (replicated storage); dim shards and replicated
    params pass through."""
    out = {}
    for zp in plan.params:
        v = new_upd[zp.name]
        if zp.kind == "flat":
            full = jax.lax.all_gather(v, plan.shard_axis, axis=0, tiled=True)
            out[zp.name] = full[:zp.numel].reshape(zp.shape)
        else:
            out[zp.name] = v
    return out


def global_grad_sumsq(grads, plan):
    """f32 sum of squares over the (mixed-layout) grad tree: sharded
    leaves (dim slices + flat chunks — already fully reduced over dp,
    partitioned over the shard axis; flat pad rows are zero) psum over
    the shard axis; replicated leaves count once."""
    local = jnp.zeros((), jnp.float32)
    repl = jnp.zeros((), jnp.float32)
    any_sharded = False
    for zp in plan.params:
        g = grads.get(zp.name)
        if g is None:
            continue
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if zp.kind == "replicated":
            repl = repl + s
        else:
            any_sharded = True
            local = local + s
    if any_sharded:
        repl = repl + jax.lax.psum(local, (plan.shard_axis,))
    return repl


# ---------------------------------------------------------------------------
# Just-in-time slab gathers: the scan-body seam (models/gpt.py)
# ---------------------------------------------------------------------------
# The ShardedTrainStep sets this scope while tracing its per-shard body;
# StackedDecoder._run consults it and gathers each sharded [L, ...] slab
# slice INSIDE the (remat-wrapped) scan block instead of receiving full
# weights — the fsdp recipe: resident state is the shard, the full layer
# weights exist only transiently per layer, and the remat backward
# re-gathers instead of saving them. Tracing is single-threaded per
# process (same discipline as collectives.manual_grad_region).
_JIT_GATHERS = [None]


@contextlib.contextmanager
def jit_gather_scope(info):
    """``info``: {stacked-attr: (axis_name, stacked_dim, degree,
    quantized)} for the slabs whose gathers are deferred into the scan
    body; None/empty clears."""
    prev = _JIT_GATHERS[0]
    _JIT_GATHERS[0] = dict(info) if info else None
    try:
        yield
    finally:
        _JIT_GATHERS[0] = prev


def active_jit_gathers():
    return _JIT_GATHERS[0]
