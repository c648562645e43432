"""Hybrid-mesh composition: ONE manual-region program over dp×mp(×pp).

Pre-PR, every high-value plan in this package was an engage-or-decline
ISLAND: :class:`~.overlap.GradReducePlan` and :class:`~.zero.ZeroPlan`
engaged only on pure-data meshes, the fused tp seams only on
pipeline-free meshes outside the grad region, and the compiled pipeline
schedules opened a partial-manual shard_map this container's XLA cannot
lower at all when another axis is live (CollectivePermute with manual
subgroups hard-aborts the partitioner). The 3-axis hybrid bench
therefore ran the plain GSPMD program with NONE of the quantized /
overlapped / ZeRO machinery.

This module replaces the per-plan islands with an explicit
**compatibility lattice** (:data:`COMPAT_LATTICE`,
:func:`build_composed_plan`) and a :class:`ComposedPlan` that runs the
whole step — forward, loss, backward, grad reduce, sharded update —
inside ONE fully-manual ``shard_map`` region over every live axis:

- **TP seams** (:class:`ManualSeams`): the PR 6 matmul+reduce-scatter /
  all-gather+matmul kernels re-expressed as per-shard ``custom_vjp``
  calls over the manual ``mp`` axis (identical per-shard math to
  :mod:`.fused`'s island bodies; the weight-grad data-axis psum moves
  into the bucketed reduce below). The residual stream between seams is
  SEQUENCE-SHARDED over mp; :meth:`ManualSeams.seq_split` /
  :meth:`~ManualSeams.seq_unsplit` are the hand-written transpose pair
  that brings the stream into and out of that layout, keeping every
  weight gradient outside the decoder replicated-consistent across mp.
- **Bucketed / quantized grad reduce** (:mod:`.overlap`,
  :mod:`.quantized`): every gradient that is partial over the data axes
  reduces through the PR 6 buckets — including the stage-sharded
  decoder slabs, whose grads are local to their mp/pp shard and reduce
  over data only. The in-block norm gains (ln1/ln2) see only their
  sequence shard under engaged seams, so their grads additionally psum
  over mp (exact — norms are name-excluded from quantization).
- **ZeRO** (:mod:`.zero`): stage-2 flat chunk-sharded updates and
  stage-3 dim-shard residency with just-in-time slab gathers ride the
  SAME machinery as the pure-data zero mode — the inner
  :class:`~.zero.ZeroPlan` covers the sharding-axis params while the
  mp/pp stage shards update in place on their storage shard (their
  optimizer slots follow the param placements: pipeline/TP sharding of
  the optimizer state falls out for free).
- **Pipeline** (:mod:`..pipeline`): the explicit 1F1B ring and the
  zero-bubble split-backward schedule run INLINE per shard (the stage
  ordinal comes from the region's sharded iota), composing with the
  dp×mp program per stage — the only lowering of a hybrid pipeline this
  XLA accepts.

Escape hatches (all proven byte-for-byte: a declined plan never touches
the program): ``PTPU_QUANT_COLLECTIVES=0`` (master), ``PTPU_COMPOSED=0``
(this mode only), ``PTPU_ZERO_MODE=0`` (stage>=2 meshes fall back to the
GSPMD placement-hint program), ``PTPU_PIPELINE_SCHEDULE=0`` (pp-live
meshes fall back likewise).
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import os

import jax
import jax.numpy as jnp

from ... import telemetry as _telemetry
from ..pipeline import _int_cotangent as _f0
from .overlap import GradReducePlan, partition_buckets, reduce_grads as _bucket_reduce
from .quantized import QUANT_BLOCK
from . import zero as _zero


# ---------------------------------------------------------------------------
# Structured engagement verdicts (satellite: every resolved plan logs ONE
# plan_engagement event so a silently-declined hybrid config is visible
# in tools/telemetry_report.py's -- plans -- section)
# ---------------------------------------------------------------------------
class Reason(str, enum.Enum):
    """Why a plan engaged or declined — the enum IS the telemetry label."""

    ENGAGED = "engaged"
    MASTER_OFF = "master_knob_off"
    COMPOSED_OFF = "composed_knob_off"
    CHECKIFY = "checkify_debug"
    MESH_AXES = "unsupported_mesh_axes"
    NOT_HYBRID = "mesh_not_hybrid"
    NO_DATA_AXIS = "no_data_axis"
    SEAM_FORCED = "tp_seam_forced"
    VOCAB_SHARDED_HEAD = "vocab_sharded_head"
    ZERO3_PLACEMENT = "zero3_data_axis_placement"
    NO_QUANTIZABLE_GRAD = "no_quantizable_grad"
    STAGE_LT_2 = "stage_lt_2"
    ZERO_MODE_OFF = "zero_mode_off"
    OPTIMIZER_STATS = "optimizer_cross_element_stats"
    CLIP_BY_NORM = "clip_grad_by_norm"
    FROZEN_SHARD = "frozen_data_axis_shard"
    RING_OFF = "ring_attn_off"
    NO_SEP = "no_sep_axis"
    ZERO_REQUESTED = "zero_stage_requested"
    SEQ_GATE = "seq_shape_gate"
    NO_SHARDABLE_STATE = "no_shardable_state"
    UNSPECIFIED = "unspecified"
    MODEL_INELIGIBLE = "model_ineligible"
    PIPELINE_OFF = "pipeline_schedule_off"
    INTERLEAVE = "interleave_not_composed"
    LAYERS_INDIVISIBLE = "layers_indivisible_by_pp"
    QUANT_GATE = "quant_parity_gate"
    QUANT_SEAM = "tp_seam_owns_gemm"
    QUANT_FUSED_FFN = "fused_kernel_owns_gemm"
    QUANT_PIPELINE = "pipeline_stage_fn"
    QUANT_COMPOSED = "composed_region"


#: human strings for the enum (the "enum + human string" contract)
REASON_TEXT = {
    Reason.ENGAGED: "plan engaged",
    Reason.MASTER_OFF: "PTPU_QUANT_COLLECTIVES=0 master escape hatch",
    Reason.COMPOSED_OFF: "PTPU_COMPOSED=0 escape hatch",
    Reason.CHECKIFY: "FLAGS_check_nan_inf: checkify cannot instrument "
                     "through a manual region",
    Reason.MESH_AXES: "a live mesh axis outside this plan's lattice row",
    Reason.NOT_HYBRID: "no live mp/pp axis — the pure-data plans own "
                       "this mesh",
    Reason.NO_DATA_AXIS: "ZeRO sharded update needs a live data axis",
    Reason.SEAM_FORCED: "PTPU_TP_SEAM=fused: the island seams own the "
                        "manual region",
    Reason.VOCAB_SHARDED_HEAD: "vocab-sharded CE opens its own mp island",
    Reason.ZERO3_PLACEMENT: "a param is sharded over a data axis under a "
                            "live mp axis (pre-compose rule)",
    Reason.NO_QUANTIZABLE_GRAD: "no gradient large enough to quantize — "
                                "the pre-PR program is kept byte-for-byte",
    Reason.STAGE_LT_2: "sharding stage < 2",
    Reason.ZERO_MODE_OFF: "PTPU_ZERO_MODE=0 escape hatch",
    Reason.OPTIMIZER_STATS: "factored/int8-moment optimizer computes "
                            "cross-element statistics wrong on a shard",
    Reason.CLIP_BY_NORM: "ClipGradByNorm needs full grad tensors",
    Reason.FROZEN_SHARD: "a frozen param carries a data-axis shard",
    Reason.RING_OFF: "PTPU_RING_ATTN=0 escape hatch",
    Reason.NO_SEP: "no live sep axis",
    Reason.ZERO_REQUESTED: "sharding stage >= 2 requested: the ring "
                           "yields the manual region (the zero mode "
                           "itself declines sep-live meshes, so neither "
                           "engages there)",
    Reason.SEQ_GATE: "sequence length fails the shape gate for this "
                     "batch signature",
    Reason.NO_SHARDABLE_STATE: "no parameter is big enough to shard",
    Reason.UNSPECIFIED: "builder declined without a recorded reason "
                        "(e.g. a stubbed-out builder)",
    Reason.MODEL_INELIGIBLE: "model has no composable flagship decoder "
                             "stack",
    Reason.PIPELINE_OFF: "PTPU_PIPELINE_SCHEDULE=0 escape hatch",
    Reason.INTERLEAVE: "interleaved (VPP) storage layout is not "
                       "composable yet",
    Reason.LAYERS_INDIVISIBLE: "num_layers not divisible by pp",
    Reason.QUANT_GATE: "numeric parity probe failed (or CPU default-off) — "
                       "scaled GEMMs stay wide",
    Reason.QUANT_SEAM: "engaged tp seams own the row/col matmul layouts "
                       "(PR 6/7 precedence)",
    Reason.QUANT_FUSED_FFN: "the fused FFN kernel (swiglu_down) owns "
                            "this GEMM",
    Reason.QUANT_PIPELINE: "pipeline stage_fn does not thread amax state",
    Reason.QUANT_COMPOSED: "composed manual region does not thread amax "
                           "state",
}


_PLAN_ENGAGEMENT = _telemetry.counter(
    "plan_engagement_total",
    "plan resolutions at step build, by verdict and structured reason "
    "(docs/COMMS.md lattice; one tick per resolved plan)",
    labelnames=("plan", "verdict", "reason"))

#: newest resolution per plan name (host-side, for bench blocks/tests)
_LAST_VERDICTS = {}


def note_plan_engagement(plan_name, reason):
    """Record one plan resolution: ``reason`` is a :class:`Reason` (or
    raw string); verdict derives from it. Returns the verdict string."""
    reason = Reason(reason) if not isinstance(reason, Reason) else reason
    verdict = "engaged" if reason is Reason.ENGAGED else "declined"
    _LAST_VERDICTS[plan_name] = (verdict, reason.value)
    if _telemetry.get_registry().enabled:
        _PLAN_ENGAGEMENT.inc(labels=(plan_name, verdict, reason.value))
    return verdict


def last_verdicts():
    """{plan: (verdict, reason)} of the newest build's resolutions."""
    return dict(_LAST_VERDICTS)


def note_decline(reason_out, reason):
    """Append a structured decline ``reason`` to a builder's
    ``reason_out`` list (when given) and return None — the shared
    decline idiom of every plan builder."""
    if reason_out is not None:
        reason_out.append(reason)
    return None


#: The compatibility lattice, declaratively: for each mechanism, the
#: mesh-axis rows it engages on and the features it composes with.
#: docs/COMMS.md renders this table; tests/test_compose.py asserts it.
COMPAT_LATTICE = {
    "grad_reduce": {
        "axes": ({"dp"}, {"sharding"}, {"dp", "sharding"}),
        "composes_with": ("quantized", "buckets"),
        "owner_when": "pure-data mesh, stage < 2",
    },
    "zero": {
        "axes": ({"dp"}, {"sharding"}, {"dp", "sharding"}),
        "composes_with": ("quantized", "jit_gather"),
        "owner_when": "pure-data mesh, stage >= 2",
    },
    "ring_attn": {
        "axes": ({"sep"}, {"dp", "sep"}, {"sharding", "sep"},
                 {"dp", "sharding", "sep"}),
        "composes_with": ("grad_reduce", "quantized"),
        "owner_when": "sep live (stage < 2, no mp/pp)",
    },
    "composed": {
        "axes": ({"mp"}, {"pp"}, {"dp", "mp"}, {"dp", "pp"},
                 {"dp", "mp", "pp"}, {"dp", "sharding", "mp"},
                 {"dp", "sharding", "pp"}, {"sharding", "mp"},
                 {"sharding", "pp"}, {"dp", "sharding", "mp", "pp"},
                 {"mp", "pp"}, {"sharding", "mp", "pp"}),
        "composes_with": ("tp_seams", "quantized", "buckets", "zero",
                          "jit_gather", "pipeline_1f1b", "pipeline_zb"),
        "owner_when": "mp and/or pp live (flagship decoder)",
    },
}


def lattice_owner(live_axes, *, stage=0):
    """The :data:`COMPAT_LATTICE` row that OWNS a mesh whose live axes
    are ``live_axes`` (any iterable of axis names), or ``None`` when no
    row accepts the set — the declarative pre-build validity check the
    layout autotuner (memory/autotune.py) consults before paying a
    model build or a trace. Precedence mirrors the build walk:
    composed owns any mp/pp-live mesh, ring owns sep-live pure-data
    meshes (stage < 2 — stage >= 2 with sep live falls off every row,
    exactly the ``owner_when`` annotations), else zero (stage >= 2) /
    grad_reduce. An EMPTY set returns "grad_reduce"/"zero": a degree-1
    mesh is the degenerate pure-data case every plan handles."""
    live = frozenset(live_axes)
    if not live:
        return "zero" if int(stage or 0) >= 2 else "grad_reduce"
    if "mp" in live or "pp" in live:
        return ("composed"
                if live in COMPAT_LATTICE["composed"]["axes"] else None)
    if "sep" in live:
        if int(stage or 0) >= 2:
            return None  # zero declines sep, ring declines stage >= 2
        return ("ring_attn"
                if live in COMPAT_LATTICE["ring_attn"]["axes"] else None)
    row = "zero" if int(stage or 0) >= 2 else "grad_reduce"
    return row if live in COMPAT_LATTICE[row]["axes"] else None


def composed_enabled():
    """``PTPU_COMPOSED`` (default on) on top of the PR 6 master switch —
    ``PTPU_QUANT_COLLECTIVES=0`` must keep every program pre-PR."""
    from . import quant_collectives_enabled

    if not quant_collectives_enabled():
        return False
    return os.environ.get("PTPU_COMPOSED", "1") not in ("0", "off", "false")


def pipeline_schedule_env():
    """``PTPU_PIPELINE_SCHEDULE``: '' (default — the model config's
    ``pp_schedule`` decides), '1f1b'/'zb' (force), '0'/'off'/'false'
    (escape hatch: pp-live meshes keep the pre-PR GSPMD program). Any
    other spelling raises — a mistyped forced knob must not silently
    masquerade as a measured configuration (same contract as
    ``PTPU_FA_BLOCK``)."""
    env = os.environ.get("PTPU_PIPELINE_SCHEDULE", "").strip().lower()
    if env not in ("", "1f1b", "zb", "0", "off", "false"):
        raise ValueError(
            f"PTPU_PIPELINE_SCHEDULE={env!r}: expected '1f1b', 'zb', "
            "'' (model config decides) or '0'/'off'/'false' (escape "
            "hatch, docs/PIPELINE.md)")
    return env


def pipeline_schedule_disabled():
    """True when ``PTPU_PIPELINE_SCHEDULE`` spells the escape hatch —
    the ONE place the accepted off-spellings live (the
    :data:`Reason.PIPELINE_OFF` decline calls this)."""
    return pipeline_schedule_env() in ("0", "off", "false")


# ---------------------------------------------------------------------------
# In-region TP seam kernels (per-shard custom_vjp over the manual mp
# axis — the same per-shard math as fused.py's island bodies, minus the
# data-axis weight-grad psum, which the bucketed reduce owns here)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_rs(x, w, axis):
    """Row-parallel seam: x [b, S, k_loc] @ w [k_loc, n] -> partial sums
    resolve directly into sequence shards [b, S/tp, n]."""
    part = x @ w
    return jax.lax.psum_scatter(part, axis, scatter_dimension=1,
                                tiled=True)


def _mm_rs_fwd(x, w, axis):
    return _mm_rs(x, w, axis), (x, w)


def _mm_rs_bwd(axis, res, dy):
    x, w = res
    dyg = jax.lax.all_gather(dy, axis, axis=1, tiled=True)
    dx = (dyg @ w.T).astype(x.dtype)
    dw = jnp.einsum("bsk,bsn->kn", x.astype(jnp.float32),
                    dyg.astype(jnp.float32)).astype(w.dtype)
    return dx, dw


_mm_rs.defvjp(_mm_rs_fwd, _mm_rs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ag_mm(x, w, axis):
    """Column-parallel seam: seq-sharded x [b, S/tp, h] all-gathers into
    the matmul with the mp-sharded weight -> [b, S, n_loc]."""
    xg = jax.lax.all_gather(x, axis, axis=1, tiled=True)
    return xg @ w


def _ag_mm_fwd(x, w, axis):
    # save the SEQ-SHARDED input and re-gather in backward (the
    # remat-friendly choice, mirroring fused.py)
    return _ag_mm(x, w, axis), (x, w)


def _ag_mm_bwd(axis, res, dy):
    x, w = res
    dxp = dy @ w.T                        # partial over tp
    dx = jax.lax.psum_scatter(dxp, axis, scatter_dimension=1,
                              tiled=True).astype(x.dtype)
    xg = jax.lax.all_gather(x, axis, axis=1, tiled=True)
    dw = jnp.einsum("bsh,bsn->hn", xg.astype(jnp.float32),
                    dy.astype(jnp.float32)).astype(w.dtype)
    return dx, dw


_ag_mm.defvjp(_ag_mm_fwd, _ag_mm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _seq_split(x, ordinal, axis, tp):
    """[b, S, ...] replicated over mp -> this shard's seq chunk. The
    hand-written backward ALL-GATHERS the chunk cotangents, so every
    consumer upstream (embedding) sees the replicated-consistent full
    gradient — mp never enters its reduce axes."""
    chunk = x.shape[1] // tp
    return jax.lax.dynamic_slice_in_dim(x, ordinal * chunk, chunk, 1)


def _seq_split_fwd(x, ordinal, axis, tp):
    return _seq_split(x, ordinal, axis, tp), ordinal


def _seq_split_bwd(axis, tp, ordinal, dy):
    return jax.lax.all_gather(dy, axis, axis=1, tiled=True), _f0(ordinal)


_seq_split.defvjp(_seq_split_fwd, _seq_split_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _seq_unsplit(x, ordinal, axis, tp):
    """Seq-sharded [b, S/tp, ...] -> full [b, S, ...] (replicated across
    mp); backward hands each shard ITS chunk of the cotangent — the
    exact transpose of :func:`_seq_split`."""
    return jax.lax.all_gather(x, axis, axis=1, tiled=True)


def _seq_unsplit_fwd(x, ordinal, axis, tp):
    return _seq_unsplit(x, ordinal, axis, tp), ordinal


def _seq_unsplit_bwd(axis, tp, ordinal, dy):
    chunk = dy.shape[1] // tp
    return (jax.lax.dynamic_slice_in_dim(dy, ordinal * chunk, chunk, 1),
            _f0(ordinal))


_seq_unsplit.defvjp(_seq_unsplit_fwd, _seq_unsplit_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_psum(x, axes):
    """Identity whose backward psums the cotangent over ``axes``. The
    AD-reversed inline 1F1B ring consumes its input only on stage 0, so
    the input cotangent is stage-0-local — the shard_map ISLAND version
    got its psum from the replicated in_spec's transpose, and the
    hand-written zero-bubble backward psums dx itself; this restores
    the same replicated-consistency for the inline AD path."""
    return x


def _grad_psum_fwd(x, axes):
    return x, None


def _grad_psum_bwd(axes, _res, dy):
    return (jax.lax.psum(dy, axes),)


_grad_psum.defvjp(_grad_psum_fwd, _grad_psum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_keep(x, axes):
    """psum whose backward is the IDENTITY — the closing reduce of the
    inline 1F1B ring. Per-shard AD of a plain psum sums the cotangents
    of every rank's redundant downstream copy (the loss is computed on
    every pp rank from the replicated ring output), over-counting every
    upstream gradient by pp; the true per-rank adjoint of "replicate
    the last stage's buffer" hands each rank its own copy's cotangent."""
    return jax.lax.psum(x, axes)


def _psum_keep_fwd(x, axes):
    return jax.lax.psum(x, axes), None


def _psum_keep_bwd(axes, _res, dy):
    return (dy,)


_psum_keep.defvjp(_psum_keep_fwd, _psum_keep_bwd)


class ManualSeams:
    """Duck-types :class:`~.fused.TPSeamPlan` for ``_block_pure``'s
    ``_row``/``_col`` routing, but runs PER SHARD inside the composed
    manual region (no nested shard_map island). ``calls`` counts seam
    routings so the build can assert the trace actually engaged them."""

    __slots__ = ("axis", "tp", "ordinal", "calls")

    def __init__(self, axis, tp, ordinal):
        self.axis = axis
        self.tp = tp
        self.ordinal = ordinal
        self.calls = 0

    def _check_seq(self, s, what):
        if s % self.tp != 0:
            raise ValueError(
                f"composed tp seams: {what} length {s} does not divide "
                f"by tp={self.tp} — pad the sequence or disable "
                "composition (PTPU_COMPOSED=0, docs/COMMS.md)")

    def matmul_reduce_scatter(self, x, w):
        self.calls += 1
        self._check_seq(x.shape[1], "sequence")
        return _mm_rs(x, w, self.axis)

    def all_gather_matmul(self, x, w):
        self.calls += 1
        return _ag_mm(x, w, self.axis)

    def seq_split(self, x):
        self._check_seq(x.shape[1], "sequence")
        return _seq_split(x, self.ordinal, self.axis, self.tp)

    def seq_unsplit(self, x):
        return _seq_unsplit(x, self.ordinal, self.axis, self.tp)


# ---------------------------------------------------------------------------
# Composed scope: the ShardedTrainStep opens it while tracing its
# per-shard body; StackedDecoder.forward consults it (models/gpt.py) to
# route seams / the inline pipeline. Tracing is single-threaded per
# process (same discipline as collectives.manual_grad_region).
# ---------------------------------------------------------------------------
_COMPOSED_CTX = [None]


@contextlib.contextmanager
def composed_scope(ctx):
    prev = _COMPOSED_CTX[0]
    _COMPOSED_CTX[0] = ctx
    try:
        yield
    finally:
        _COMPOSED_CTX[0] = prev


def active_composed_context():
    return _COMPOSED_CTX[0]


class ComposedContext:
    """Per-trace context: the plan plus this shard's traced ordinals."""

    def __init__(self, plan, tp_ordinal=None, stage_ordinal=None):
        self.plan = plan
        self.stage_id = stage_ordinal
        self.seams = (ManualSeams(plan.tp_axis, plan.tp, tp_ordinal)
                      if plan.tp_seams else None)
        self.decoder_calls = 0

    def pipeline_apply(self, block, x, params, gather=False):
        """Run the decoder stack as the composed pipeline schedule over
        this shard's stage slab (params are the LOCAL [L/pp, ...]
        leaves). 1F1B is the AD-reversed compiled ring; 'zb' is the
        hand-written split-backward schedule (dgrad ring + batched
        wgrad) — both per-shard, stage ordinal from the region iota."""
        from .. import pipeline as _pl

        plan = self.plan
        n_micro = plan.n_micro
        unroll = 2 if gather else 1

        def stage_fn(stage_params, xm):
            def step(c, p):
                return block(c, p), None

            out, _ = jax.lax.scan(step, xm, tuple(stage_params),
                                  unroll=unroll)
            return out

        if plan.pp_schedule != "zb":
            # the AD ring consumes x only on stage 0: psum the input
            # cotangent over pp so upstream (embedding) grads stay
            # replicated-consistent (the zb backward psums dx itself)
            x = _grad_psum(x, (plan.pp_axis,))
        x_mb = _pl.microbatch(x, n_micro)
        if plan.pp_schedule == "zb":
            out = _pl.zero_bubble_schedule(
                stage_fn, tuple(params), x_mb, plan.pp, self.stage_id,
                axis_name=plan.pp_axis)
        else:
            out = _pl.pipeline_schedule(
                lambda xm: stage_fn(tuple(params), xm), x_mb, plan.pp,
                axis_name=plan.pp_axis, stage_id=self.stage_id,
                psum_fn=_psum_keep)
        return _pl.unmicrobatch(out)


# ---------------------------------------------------------------------------
# The composed plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ComposedPlan:
    """Static description of one composed step, resolved at build time
    (knobs at BUILD, never per call). Duck-types the GradReducePlan
    accounting surface (note_grad_reduce / bench "comms") and carries an
    inner :class:`~.zero.ZeroPlan` for the sharding-axis params."""

    axes: tuple                 # ALL region axes (data + mp? + pp?)
    data_axes: tuple
    nranks: int                 # product over data axes (grad-mean divisor)
    tp_axis: str | None = None
    tp: int = 1
    tp_seams: bool = False
    pp_axis: str | None = None
    pp: int = 1
    pp_schedule: str | None = None      # "1f1b" | "zb" | None
    n_micro: int = 1
    zero: object | None = None          # inner ZeroPlan (data axes only)
    reduce_main: object | None = None   # GradReducePlan over data axes
    tp_partial: tuple = ()              # names needing an extra mp psum
    param_specs: dict = dataclasses.field(default_factory=dict)
    sumsq_axes: dict = dataclasses.field(default_factory=dict)
    # stage-1 (shard_opt_states) slot sharding kept THROUGH the region:
    # name -> (dim, degree) for params whose param-shaped optimizer
    # slots stay stored as 1/degree shards over "sharding" — gathered
    # exactly (all_gather) just before the update, sliced back to the
    # shard right after (the stage-3 JIT-gather discipline applied to
    # slots; resident HBM keeps the stage-1 memory win)
    slot_shards: dict = dataclasses.field(default_factory=dict)
    quant_block: int = QUANT_BLOCK

    # -- GradReducePlan-compatible accounting ---------------------------
    @property
    def axis_label(self):
        return "+".join(self.data_axes) if self.data_axes else "-"

    @property
    def buckets(self):
        return self.reduce_main.buckets if self.reduce_main else ()

    @property
    def calls(self):
        n = len(self.buckets) + len(self.tp_partial)
        if self.zero is not None:
            n += self.zero.calls
        return n

    @property
    def exact_bytes(self):
        n = sum(b.payload_bytes for b in self.buckets if not b.quantized)
        if self.zero is not None:
            n += self.zero.exact_bytes
        return n

    @property
    def quantized_payload_bytes(self):
        n = sum(b.payload_bytes for b in self.buckets if b.quantized)
        if self.zero is not None:
            n += self.zero.quantized_payload_bytes
        return n

    @property
    def quantized_wire_bytes(self):
        from .quantized import quantized_wire_bytes as _qw

        n = sum(_qw(b.numel, self.nranks, block=self.quant_block)
                for b in self.buckets if b.quantized)
        if self.zero is not None:
            n += self.zero.quantized_wire_bytes
        return n

    def composed_summary(self):
        return {
            "engaged": True,
            "axes": list(self.axes),
            "data_axes": list(self.data_axes),
            "tp_axis": self.tp_axis, "tp": self.tp,
            "tp_seams": bool(self.tp_seams),
            "pp_axis": self.pp_axis, "pp": self.pp,
            "pp_schedule": self.pp_schedule,
            "n_micro": self.n_micro,
            "zero_stage": (self.zero.stage if self.zero is not None
                           else 0),
            "stage1_slot_shards": len(self.slot_shards),
            "buckets": len(self.buckets),
            "tp_partial": list(self.tp_partial),
        }

    def summary(self):
        """GradReducePlan-shaped comms summary + the composed lattice
        row (+ the inner zero block when engaged)."""
        qp = self.quantized_payload_bytes
        eb = self.exact_bytes
        out = {
            "axes": list(self.data_axes), "nranks": self.nranks,
            "buckets": self.calls,
            "quantized_buckets":
                sum(1 for b in self.buckets if b.quantized)
                + (sum(1 for p in self.zero.params if p.quantized)
                   if self.zero is not None else 0),
            "exact_bytes": int(eb),
            "quantized_payload_bytes": int(qp),
            "quantized_wire_bytes": int(self.quantized_wire_bytes),
            "quantized_fraction": (float(qp) / float(eb + qp)
                                   if (eb + qp) else 0.0),
            "composed": self.composed_summary(),
        }
        if self.zero is not None:
            out["zero"] = self.zero.zero_summary()
        return out

    def zero_summary(self):
        if self.zero is not None:
            return self.zero.zero_summary()
        return {"stage": 0, "engaged": False}


def _region_spec(t, region_axes):
    """Storage PartitionSpec of a tensor inside the region: placements
    filtered to live region axes (dead axes partition nothing)."""
    from jax.sharding import PartitionSpec as P

    from ..auto_parallel import Shard

    da = getattr(t, "_dist_attr", None)
    if da is None:
        return P()
    by_dim = {}
    for ax_name, pl in zip(da.process_mesh.dim_names, da.placements):
        if (isinstance(pl, Shard) and ax_name in region_axes):
            by_dim.setdefault(pl.dim, []).append(ax_name)
    if not by_dim:
        return P()
    entries = []
    for d in range(max(by_dim) + 1):
        axes = by_dim.get(d, [])
        entries.append(None if not axes
                       else (axes[0] if len(axes) == 1 else tuple(axes)))
    return P(*entries)


def _local_shape(shape, spec, sizes):
    """Per-shard shape of a tensor stored with ``spec`` on the region."""
    out = list(shape)
    for d, e in enumerate(spec or ()):
        if e is None:
            continue
        for ax in (e if isinstance(e, tuple) else (e,)):
            out[d] //= sizes[ax]
    return tuple(out)


def _find_decoder(model):
    from ...models.gpt import StackedDecoder

    hits = [(prefix, l) for prefix, l in
            model.named_sublayers(include_self=True)
            if isinstance(l, StackedDecoder)]
    return hits[0] if len(hits) == 1 else (None, None)


def stage1_slot_dim(shape, size):
    """The dim a stage-1 (``shard_opt_states``) param-shaped optimizer
    slot shards over: the FIRST dim divisible by the sharding degree —
    ONE resolver shared by ``ShardedTrainStep._slot_sharding`` (storage
    placement) and the composed plan (region in/out specs), so the two
    can never disagree about the layout. None = not shardable."""
    for d, n in enumerate(shape):
        if n and n % size == 0:
            return d
    return None


def stage1_slot_spec(param_spec, dim):
    """``param_spec`` with the "sharding" axis appended at ``dim`` —
    the storage PartitionSpec of a stage-1 sharded slot whose param is
    stored with ``param_spec`` (mp/pp slabs keep their placements)."""
    from jax.sharding import PartitionSpec as P

    entries = list(param_spec) + [None] * (dim + 1 - len(param_spec))
    cur = entries[dim]
    if cur is None:
        entries[dim] = "sharding"
    else:
        cur = tuple(cur) if isinstance(cur, tuple) else (cur,)
        entries[dim] = cur + ("sharding",)
    return P(*entries)


def build_composed_plan(model, optimizer, mesh, *, sharding_stage=None,
                        shard_vocab_head=None, grad_clip=None,
                        shard_opt_states=False):
    """Resolve the composed hybrid plan, or ``(None, Reason)``.

    Returns ``(ComposedPlan | None, Reason)`` — the reason is
    :data:`Reason.ENGAGED` on success, else the first lattice row the
    config fell off. Callers record it via
    :func:`note_plan_engagement`."""
    from ...core.tensor import Parameter
    from ..auto_parallel import Shard
    from ...models.gpt import StackedDecoder, _BLOCK_PARAM_FIELDS
    from . import grads_quantized
    from .fused import tp_seam_mode

    if not composed_enabled():
        from . import quant_collectives_enabled

        return None, (Reason.MASTER_OFF if not quant_collectives_enabled()
                      else Reason.COMPOSED_OFF)
    live = {a: mesh.get_dim_size(a) for a in mesh.dim_names
            if mesh.get_dim_size(a) > 1}
    if not (live.get("mp", 1) > 1 or live.get("pp", 1) > 1):
        return None, Reason.NOT_HYBRID
    if not set(live) <= {"dp", "sharding", "mp", "pp"}:
        return None, Reason.MESH_AXES
    from ...utils.flags import get_flags

    if get_flags("check_nan_inf")["check_nan_inf"]:
        return None, Reason.CHECKIFY
    mp_live = live.get("mp", 1) > 1
    if (shard_vocab_head and shard_vocab_head in mesh.dim_names
            and mesh.get_dim_size(shard_vocab_head) > 1):
        return None, Reason.VOCAB_SHARDED_HEAD
    if tp_seam_mode() == "fused" and mp_live:
        # explicit island forcing: the PR 6 seam islands own the program
        return None, Reason.SEAM_FORCED
    prefix, decoder = _find_decoder(model)
    if decoder is None:
        return None, Reason.MODEL_INELIGIBLE
    cfg = decoder.config
    data_axes = tuple(a for a in ("dp", "sharding") if a in live)
    region_axes = data_axes + tuple(
        a for a in ("mp", "pp") if a in live)
    sizes = dict(live)

    slab_names = {(prefix + "." if prefix else "") + attr: attr
                  for attr, _ in _BLOCK_PARAM_FIELDS}
    tp_dims = StackedDecoder._TP_DIMS

    # -- pipeline row ---------------------------------------------------
    pp_axis, pp, pp_schedule, n_micro = None, 1, None, 1
    staged = False
    if live.get("pp", 1) > 1:
        pp = live["pp"]
        # stage placements must actually shard the slabs (Shard(0) over
        # pp); without them the decoder is replicated over pp and the
        # pre-PR GSPMD program handles the mesh unchanged
        da = getattr(decoder.wq, "_dist_attr", None)
        staged = da is not None and any(
            isinstance(pl, Shard) and pl.dim == 0 and ax == "pp"
            for ax, pl in zip(da.process_mesh.dim_names, da.placements))
        if staged:
            env = pipeline_schedule_env()
            if pipeline_schedule_disabled():
                return None, Reason.PIPELINE_OFF
            if (getattr(cfg, "pp_interleave", 1) or 1) > 1:
                return None, Reason.INTERLEAVE
            if cfg.num_layers % pp != 0:
                return None, Reason.LAYERS_INDIVISIBLE
            pp_axis = "pp"
            pp_schedule = env if env in ("1f1b", "zb") else (
                getattr(cfg, "pp_schedule", "1f1b") or "1f1b")
            n_micro = getattr(cfg, "pp_microbatches", None) or pp

    # -- tp row ---------------------------------------------------------
    tp_axis, tp, tp_seams = None, 1, False
    if mp_live:
        tp_axis, tp = "mp", live["mp"]
        da = getattr(decoder.wq, "_dist_attr", None)
        if da is not None:
            tp_seams = any(
                isinstance(pl, Shard) and pl.dim > 0 and ax == "mp"
                for ax, pl in zip(da.process_mesh.dim_names,
                                  da.placements))

    # composition must ADD something the per-plan paths cannot do: tp
    # seams and/or a staged pipeline. An mp/pp axis that no placement
    # uses is dead weight the pre-PR program already handles (the dp
    # grad-reduce plan engages over the data axes as before).
    if not (tp_seams or staged):
        return None, Reason.NOT_HYBRID

    # -- param walk: eligibility + zero classification ------------------
    stage = _zero.resolve_stage(optimizer, sharding_stage)
    zero_wanted = stage >= 2
    if zero_wanted and not _zero.zero_mode_enabled():
        return None, Reason.ZERO_MODE_OFF
    if zero_wanted and optimizer is not None and (
            getattr(optimizer, "_factored", False)
            or getattr(optimizer, "_moment_dtype", None)):
        return None, Reason.OPTIMIZER_STATS
    # per-tensor norm clip needs FULL grad tensors, but the composed
    # update tail runs per shard on mp/pp slab slices at EVERY stage
    # (global-norm clip psums its sumsq via gsumsq_fn; per-tensor clip
    # has no such channel — a local-slice norm silently diverges)
    from ...nn.clip import ClipGradByNorm

    if isinstance(grad_clip, ClipGradByNorm):
        return None, Reason.CLIP_BY_NORM
    shard_axis = None
    if zero_wanted:
        shard_axis = ("sharding" if "sharding" in live
                      else ("dp" if "dp" in live else None))
        if shard_axis is None:
            return None, Reason.NO_DATA_AXIS

    entries = model.state_dict()
    named = [(n, t) for n, t in entries.items()
             if isinstance(t, Parameter)]
    quant = grads_quantized()
    jit_gather = _zero.jit_gather_enabled()
    zero_params = []
    bucket_named = []          # (name, LOCAL shape, dtype) for the buckets
    tp_partial = []
    param_specs = {}
    sumsq_axes = {}
    degree = live.get(shard_axis, 1) if shard_axis else 1
    for name, t in named:
        arr = t._data
        shape = tuple(int(d) for d in arr.shape)
        dtype = str(jnp.dtype(arr.dtype))
        spec = _region_spec(t, region_axes)
        da = getattr(t, "_dist_attr", None)
        sdim = None
        stage_axes = []
        if da is not None:
            for ax_name, pl in zip(da.process_mesh.dim_names,
                                   da.placements):
                if not isinstance(pl, Shard):
                    continue
                if live.get(ax_name, 1) <= 1:
                    continue          # dead-axis marks partition nothing
                if ax_name == shard_axis:
                    sdim = pl.dim
                elif ax_name in ("mp", "pp"):
                    # only the staged decoder slabs are handled
                    # in-region (an mp shard must also sit on a tp
                    # dim): anything else would swap its LOCAL slice
                    # in as the full tensor — silently wrong numerics
                    if name not in slab_names or (
                            ax_name == "mp"
                            and slab_names[name] not in tp_dims):
                        return None, Reason.MODEL_INELIGIBLE
                    stage_axes.append(ax_name)
                else:
                    return None, Reason.MESH_AXES
        if not t.trainable:
            # any live-axis shard (data OR mp/pp): a frozen shard would
            # ride the region as a replicated buffer while the seam /
            # stage kernels expect a local slice — wrong numerics
            if sdim is not None or stage_axes:
                return None, Reason.FROZEN_SHARD
            continue
        param_specs[name] = spec
        is_slab = name in slab_names
        # in-block norm gains see only their seq shard under engaged
        # seams: their grads are PARTIAL over mp (exact psum — norms are
        # name-excluded from quantization)
        partial_mp = (tp_seams and is_slab
                      and slab_names[name] not in tp_dims)
        if partial_mp:
            tp_partial.append(name)
        numel = 1
        for d in shape:
            numel *= d
        if sdim is not None:
            if stage < 3:
                return None, Reason.ZERO3_PLACEMENT
            attr = slab_names.get(name)
            zero_params.append(_zero.ZeroParam(
                name, "dim", shape, dtype, numel, shard_dim=sdim,
                deferred_attr=(attr if (attr and sdim >= 1 and jit_gather)
                               else None),
                spec=spec))
            sumsq_axes[name] = tuple(
                [shard_axis] + stage_axes
                if not partial_mp else
                [a for a in [shard_axis] + stage_axes if a != "mp"])
        elif (zero_wanted and not stage_axes and numel >= degree
              and shape and jnp.issubdtype(jnp.dtype(dtype), jnp.inexact)):
            q = quant and not _zero.is_exact_grad(name, shape, dtype)
            zero_params.append(_zero.ZeroParam(
                name, "flat", shape, dtype, numel, quantized=q,
                padded=_zero.flat_padded_len(numel, degree, quantized=q)))
            sumsq_axes[name] = (shard_axis,)
        else:
            lshape = _local_shape(shape, spec, sizes)
            bucket_named.append((name, lshape, dtype))
            sumsq_axes[name] = tuple(stage_axes)
    # a dim-sharded slab whose gather rides mp-partial grads: the dim
    # kind's sumsq psums over shard_axis (+pp); mp was already summed by
    # the pre-reduce psum, so exclude it above.

    # one data-rank product: the ZeroPlan and ComposedPlan nranks are
    # both the grad-mean divisor and must stay equal
    nranks = 1
    for a in data_axes:
        nranks *= live[a]

    zplan = None
    if zero_wanted and any(p.kind in ("dim", "flat") for p in zero_params):
        zplan = _zero.ZeroPlan(
            stage=stage, axes=data_axes, shard_axis=shard_axis,
            shard_degree=degree, nranks=nranks,
            params=tuple(zero_params),
            gather_quantized=_zero.param_gather_quantized())

    # -- stage-1 slot sharding (ROADMAP item 2 follow-up (c)) -----------
    # shard_opt_states keeps its dp-sharded slot layout THROUGH the
    # composed region: the region's slot in/out specs carry the
    # storage's "sharding" extension, the update gathers the shard
    # exactly and slices the result back (stage1_gather_slots /
    # stage1_slice_slots) — resident slot HBM stays 1/degree instead of
    # resharding to replicated at the region boundary. Stage >= 2 slots
    # are owned by the inner ZeroPlan and skip this walk.
    slot_shards = {}
    if shard_opt_states and not zero_wanted and live.get("sharding", 1) > 1:
        ssize = live["sharding"]
        for name, t in named:
            if not t.trainable or name not in param_specs:
                continue
            shape = tuple(int(d) for d in t._data.shape)
            d = stage1_slot_dim(shape, ssize)
            if d is None:
                continue
            # the region view divides dims by their mp/pp placements
            # too: only engage when the LOCAL dim still divides evenly
            # (otherwise the slot keeps today's replicated region ride)
            lshape = _local_shape(shape, param_specs[name], sizes)
            if lshape[d] % ssize:
                continue
            slot_shards[name] = (d, ssize)
        note_plan_engagement(
            "zero_stage1",
            Reason.ENGAGED if slot_shards else Reason.NO_SHARDABLE_STATE)
    reduce_main = None
    main_named = [e for e in bucket_named if e[0] not in tp_partial]
    if data_axes and main_named:
        buckets = partition_buckets(main_named, quantized=quant)
        reduce_main = GradReducePlan(axes=data_axes, nranks=nranks,
                                     buckets=buckets)
    return ComposedPlan(
        axes=region_axes, data_axes=data_axes, nranks=max(nranks, 1),
        tp_axis=tp_axis, tp=tp, tp_seams=tp_seams,
        pp_axis=pp_axis, pp=pp, pp_schedule=pp_schedule, n_micro=n_micro,
        zero=zplan, reduce_main=reduce_main,
        tp_partial=tuple(tp_partial), param_specs=param_specs,
        sumsq_axes=sumsq_axes, slot_shards=slot_shards), Reason.ENGAGED


# ---------------------------------------------------------------------------
# Per-shard reduce / update / restore helpers (called inside the region)
# ---------------------------------------------------------------------------
def reduce_grads(grads, plan, zero_ordinal):
    """The composed gradient reduce: zero-kind params through the inner
    ZeroPlan recipes (reduce-scatter / chunk slice), everything else
    through the PR 6 buckets over the data axes; mp-partial norm gains
    psum over mp first (exact)."""
    out = dict(grads)
    tp_ax = (plan.tp_axis,) if plan.tp_axis else ()
    if plan.zero is not None:
        for zp in plan.zero.params:
            g = out.get(zp.name)
            if g is None:
                continue
            if zp.name in plan.tp_partial and tp_ax:
                g = jax.lax.psum(g, tp_ax)
            out[zp.name] = _zero.reduce_grad(g, zp, plan.zero,
                                             zero_ordinal, mean=True)
    if plan.reduce_main is not None:
        out = _bucket_reduce(out, plan.reduce_main, mean=True)
    # mp-partial names outside the zero plan: exact psum over data+mp,
    # mean over the DATA ranks only (the mp terms are partials of one
    # gradient, not copies)
    zcover = set(plan.zero.by_name) if plan.zero is not None else set()
    inv = 1.0 / plan.nranks
    for name in plan.tp_partial:
        g = grads.get(name)
        if g is None or name in zcover:
            continue
        red = jax.lax.psum(g, tuple(plan.data_axes) + tp_ax)
        out[name] = _zero._mean_scale(red, inv, plan.nranks)
    return out


def update_view(params, plan, zero_ordinal):
    out = dict(params)
    if plan.zero is not None:
        sub = {p.name: params[p.name] for p in plan.zero.params}
        out.update(_zero.update_view(sub, plan.zero, zero_ordinal))
    return out


def stage1_gather_slots(opt_state, params, plan):
    """Stage-1 sharded slots -> their full (per-mp/pp-slab) update view:
    one exact tiled all_gather over "sharding" per slot leaf, issued
    just before the update — resident storage stays 1/degree, the
    update math is bit-identical to the replicated layout's."""
    if not plan.slot_shards:
        return opt_state
    out = {}
    for n, slots in opt_state.items():
        sd = plan.slot_shards.get(n)
        p = params.get(n)
        if sd is None or p is None:
            out[n] = slots
            continue
        d, deg = sd
        exp = list(p.shape)
        exp[d] //= deg
        exp = tuple(exp)
        out[n] = {k: (_zero.gather_shard(v, "sharding", d)
                      if tuple(v.shape) == exp else v)
                  for k, v in slots.items()}
    return out


def stage1_slice_slots(new_opt_state, params, plan, ordinal):
    """Updated full slots back to this rank's stage-1 storage shard
    (the gather's exact inverse: a dynamic slice at the shard dim)."""
    if not plan.slot_shards:
        return new_opt_state
    out = {}
    for n, slots in new_opt_state.items():
        sd = plan.slot_shards.get(n)
        p = params.get(n)
        if sd is None or p is None:
            out[n] = slots
            continue
        d, deg = sd
        pshape = tuple(p.shape)
        chunk = pshape[d] // deg
        out[n] = {k: (jax.lax.dynamic_slice_in_dim(
                          v, ordinal * chunk, chunk, axis=d)
                      if tuple(v.shape) == pshape else v)
                  for k, v in slots.items()}
    return out


def params_out(new_upd, plan):
    out = dict(new_upd)
    if plan.zero is not None:
        sub = {p.name: new_upd[p.name] for p in plan.zero.params}
        out.update(_zero.params_out(sub, plan.zero))
    return out


def global_grad_sumsq(grads, plan):
    """f32 sum of squares over the mixed-layout composed grad tree:
    leaves partitioned over some axes in their UPDATE layout psum their
    local sums over exactly those axes; replicated leaves count once."""
    groups = {}
    for name, g in grads.items():
        if g is None:
            continue
        axes = tuple(sorted(plan.sumsq_axes.get(name, ())))
        groups.setdefault(axes, []).append(
            jnp.sum(jnp.square(g.astype(jnp.float32))))
    total = jnp.zeros((), jnp.float32)
    for axes, sums in groups.items():
        s = sum(sums)
        if axes:
            s = jax.lax.psum(s, axes)
        total = total + s
    return total
