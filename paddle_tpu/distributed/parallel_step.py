"""ShardedTrainStep: the hybrid-parallel compiled train step.

This is where the reference's whole distributed-runtime stack (EagerReducer
bucketed allreduce `reducer.h:88`, sharding-stage optimizers
`dygraph_sharding_optimizer.py:54`, hybrid grad clip
`hybrid_parallel_optimizer.py:275`, reshard insertion) collapses into one
TPU-native mechanism: parameters/optimizer slots/batch are placed on the
hybrid mesh with NamedShardings, the (forward, loss, backward, update)
program is jit-compiled once, and GSPMD emits every collective —
dp gradient psum where grads are partial over "dp", reduce-scatter/
all-gather where states are sharded over "sharding" (ZeRO), TP collectives
where mp placements require them — scheduled and fused by XLA over ICI.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..core.tensor import Parameter, Tensor
from ..jit import TrainStep, _step_update_tail, _unwrap_tensors
from .auto_parallel import (
    ProcessMesh,
    Replicate,
    Shard,
    placements_to_spec,
)

P = PartitionSpec


def _param_sharding(mesh: ProcessMesh, p) -> NamedSharding:
    if getattr(p, "_dist_attr", None) is not None:
        return NamedSharding(
            mesh.jax_mesh,
            placements_to_spec(p._dist_attr.process_mesh, p._dist_attr.placements),
        )
    return NamedSharding(mesh.jax_mesh, P())


def _batch_spec(mesh: ProcessMesh, arr) -> NamedSharding:
    """Shard batch dim 0 over every data-ish axis present (dp, sharding,
    sep). With an ENGAGED ring-attention plan (docs/ATTENTION.md) sep
    stops being a batch axis — the batch routes through
    ``_place_batch_ring`` / ``_ring_batch_sharding`` instead and never
    reaches this function."""
    axes = [a for a in ("dp", "sharding", "sep")
            if a in mesh.dim_names and mesh.get_dim_size(a) > 1]
    if not axes or arr.ndim == 0:
        return NamedSharding(mesh.jax_mesh, P())
    total = int(np.prod([mesh.get_dim_size(a) for a in axes]))
    if arr.shape[0] % total != 0:
        return NamedSharding(mesh.jax_mesh, P())
    return NamedSharding(mesh.jax_mesh, P(tuple(axes)))


class ShardedTrainStep(TrainStep):
    """TrainStep over a hybrid ProcessMesh.

    Placement protocol:
    - params with `_dist_attr` (TP layers, ZeRO-3 marks) -> their placements;
      others replicated.
    - optimizer slots follow their parameter (same shape) or replicate
      (scalars); with `shard_opt_states=True` (ZeRO-1/2) param-shaped slots
      are additionally sharded over the "sharding" axis.
    - batch tensors shard dim 0 over dp×sharding×sep.
    """

    def __init__(self, model, train_fn, optimizer, mesh: ProcessMesh,
                 scaler=None, shard_opt_states=False, shard_vocab_head=None,
                 sharding_stage=None):
        super().__init__(model, train_fn, optimizer, scaler)
        self.mesh = mesh
        self.shard_opt_states = shard_opt_states
        # ZeRO stage (docs/ZERO.md): explicit arg wins, else the
        # group_sharded_parallel level mark on the optimizer. Stage >= 2
        # on a pure-data mesh engages the zero execution mode at build
        # (_ensure_zero_plan): reduce-scattered grads, dp-sharded slots
        # and update, just-in-time param gathers.
        self.sharding_stage = sharding_stage
        self._zero_plan = None
        self._zero_plan_ready = False
        # vocab-sharded LM head ("last-stage-sharded pipeline output"):
        # an axis name places the tied head's vocab dim over that tp axis
        # via model.shard_lm_head, routing the loss through the
        # scalars-per-token sharded CE (models/gpt.py compute_loss). None
        # defers to PTPU_SHARDED_HEAD=<axis|1> (1 -> "mp"); default off so
        # existing mp meshes keep their lowered programs bit-stable.
        if shard_vocab_head is None:
            import os

            env = os.environ.get("PTPU_SHARDED_HEAD", "")
            shard_vocab_head = ("mp" if env == "1"
                                else env if env not in ("", "0") else None)
        self.shard_vocab_head = shard_vocab_head
        self._placed = False
        # dp-grad reduce plan (distributed/collectives): resolved at
        # first trace (knobs are build-time, never per call) — None
        # keeps the pre-PR GSPMD grad psum byte-for-byte
        self._reduce_plan = None
        self._reduce_plan_ready = False
        # ring-attention plan (collectives/ring_attention,
        # docs/ATTENTION.md): when it engages, sep stops being a batch
        # axis — the batch's SEQ dim shards over it (zigzag layout) and
        # attention runs as a kv ring inside the manual region. None
        # keeps sep a plain batch axis, byte-for-byte (PTPU_RING_ATTN=0).
        self._ring_plan = None
        self._ring_plan_ready = False
        self._ring_last_active = False
        # composed hybrid plan (collectives/compose, docs/COMMS.md
        # lattice): when mp and/or pp are live and the model carries a
        # composable flagship decoder, ONE fully-manual region over
        # every live axis composes tp seams + bucketed/quantized grad
        # reduce + ZeRO + the explicit pipeline schedule. None keeps the
        # pre-PR GSPMD program byte-for-byte.
        self._composed_plan = None
        self._composed_plan_ready = False

    # -- placement ---------------------------------------------------------
    def _place_model(self):
        ax = self.shard_vocab_head
        if (ax and ax in self.mesh.dim_names
                and self.mesh.get_dim_size(ax) > 1
                and hasattr(self.model, "shard_lm_head")):
            self.model.shard_lm_head(self.mesh, axis=ax)
        entries = self.model.state_dict()
        for name, t in entries.items():
            sh = _param_sharding(self.mesh, t)
            t._data = jax.device_put(t._data, sh)
        self._placed = True

    def _slot_sharding(self, pname, p_sharding, slot_arr, param_shape):
        plan = self._zero_plan if self._zero_plan_ready else None
        if plan is not None:
            zp = plan.by_name.get(pname)
            if (zp is not None and zp.kind == "flat"
                    and tuple(slot_arr.shape) == (zp.padded,)):
                # zero flat layout: the padded flat slot shards evenly
                # over the shard axis — each rank stores 1/degree
                return NamedSharding(self.mesh.jax_mesh,
                                     P(plan.shard_axis))
        if tuple(slot_arr.shape) == tuple(param_shape):
            if self.shard_opt_states:
                spec = list(p_sharding.spec) + [None] * (
                    len(param_shape) - len(p_sharding.spec)
                )
                taken = {a for e in spec if e for a in (e if isinstance(e, tuple) else (e,))}
                if (
                    "sharding" in self.mesh.dim_names
                    and self.mesh.get_dim_size("sharding") > 1
                    and "sharding" not in taken
                    and len(param_shape) > 0
                ):
                    # the ONE shared dim resolver (compose.stage1_slot_dim)
                    # so the composed region's slot specs match this
                    # storage layout exactly (docs/ZERO.md stage 1)
                    from .collectives.compose import stage1_slot_dim

                    size = self.mesh.get_dim_size("sharding")
                    d = stage1_slot_dim(param_shape, size)
                    if d is not None:
                        cur = spec[d]
                        spec[d] = (
                            ("sharding",) if cur is None
                            else (tuple(cur) if isinstance(cur, tuple) else (cur,)) + ("sharding",)
                        )
                        if not isinstance(spec[d], tuple) or len(spec[d]) == 1:
                            spec[d] = spec[d][0] if isinstance(spec[d], tuple) else spec[d]
                return NamedSharding(self.mesh.jax_mesh, P(*spec))
            return p_sharding
        return NamedSharding(self.mesh.jax_mesh, P())

    def _place_opt_state(self, params):
        entries = self.model.state_dict()
        for name, slots in self._opt_state.items():
            p = entries[name]
            psh = _param_sharding(self.mesh, p)
            for sname, arr in slots.items():
                slots[sname] = jax.device_put(
                    arr, self._slot_sharding(name, psh, arr, p._data.shape)
                )

    def _place_batch(self, raw_batch):
        ring, ring_seq = self._ring_batch_info(raw_batch)
        self._ring_last_active = ring is not None
        if ring is not None:
            return self._place_batch_ring(raw_batch, ring, ring_seq)
        placed = []
        for arr in raw_batch:
            if isinstance(arr, jax.ShapeDtypeStruct):
                # planner path (aot_compile over avals): device_put would
                # reject an abstract value — carry the same sharding a
                # real batch would get so the lowered program matches
                placed.append(jax.ShapeDtypeStruct(
                    tuple(arr.shape), arr.dtype,
                    sharding=_batch_spec(self.mesh, arr)))
            elif hasattr(arr, "ndim") and arr.ndim >= 1:
                placed.append(jax.device_put(arr, _batch_spec(self.mesh, arr)))
            else:
                placed.append(arr)
        return tuple(placed)

    def _place_batch_ring(self, raw_batch, plan, seq):
        """Ring placement (docs/ATTENTION.md): seq-dim arrays are
        zigzag-permuted (causal load balance — each rank holds chunk r
        and chunk 2n-1-r) and shard dim 1 over ``sep``; dim 0 shards
        over the remaining data axes only. Loss/grads are permutation-
        invariant (per-token CE over the same token set), so nothing
        un-permutes on the way out."""
        from .collectives import ring_attention as _ring

        plan.set_active_seq(seq)
        perm = jnp.asarray(_ring.zigzag_perm(seq, plan.sep_degree))
        placed = []
        for arr in raw_batch:
            if not hasattr(arr, "ndim") or arr.ndim == 0:
                placed.append(arr)
                continue
            sh = self._ring_batch_sharding(plan, arr, seq)
            if isinstance(arr, jax.ShapeDtypeStruct):
                placed.append(jax.ShapeDtypeStruct(
                    tuple(arr.shape), arr.dtype, sharding=sh))
                continue
            if arr.ndim >= 2 and arr.shape[1] == seq:
                arr = jnp.take(arr, perm, axis=1)
            placed.append(jax.device_put(arr, sh))
        return tuple(placed)

    def _ring_batch_sharding(self, plan, arr, seq):
        data = plan.data_axes
        total = int(np.prod([self.mesh.get_dim_size(a) for a in data])) \
            if data else 1
        dim0 = (tuple(data) if data and arr.shape[0] % total == 0
                else None)
        if arr.ndim >= 2 and arr.shape[1] == seq:
            return NamedSharding(self.mesh.jax_mesh, P(dim0, plan.axis))
        return NamedSharding(self.mesh.jax_mesh,
                             P(dim0) if dim0 else P())

    def _ring_batch_info(self, raw_batch):
        """(plan, seq) when the resolved ring plan engages for this
        batch's shapes, else (None, None). Shared by placement and the
        in-step region so the two can never disagree: every ndim>=2
        leaf must carry the SAME dim-1 length and it must pass the
        plan's seq gate (zigzag divisibility + kernel tiling)."""
        plan = self._ensure_ring_plan()
        if plan is None:
            return None, None
        seqs = [int(a.shape[1]) for a in raw_batch
                if hasattr(a, "ndim") and a.ndim >= 2]
        if not seqs:
            return None, None
        seq = seqs[0]
        if any(s != seq for s in seqs) or not plan.seq_ok(seq):
            return None, None
        return plan, seq

    def _prepare_batch(self, raw_batch):
        """memory_stats hook: mirror __call__'s placement so the lowered
        program matches the one real steps run (sharded batch, placed
        model/opt state)."""
        if not self._placed:
            self._place_model()
        if self._opt_state is None:
            entries = self.model.state_dict()
            params = {n: entries[n]._data for n in self._param_names}
            self._opt_state = self._init_opt_state(params)
            self._place_opt_state(params)
        return self._place_batch(raw_batch)

    # -- ZeRO execution mode (distributed/collectives/zero, docs/ZERO.md) --
    def _zero_deferred(self):
        """{param_name: stacked-attr} for StackedDecoder ``[L, ...]``
        slabs — the params whose stage-3 gathers defer into the scan
        body (models/gpt.py consults ``zero.active_jit_gathers``)."""
        out = {}
        try:
            from ..models.gpt import _BLOCK_PARAM_FIELDS, StackedDecoder
        except Exception:  # pragma: no cover - models optional
            return out
        attrs = [a for a, _ in _BLOCK_PARAM_FIELDS]
        for prefix, layer in self.model.named_sublayers(include_self=True):
            if isinstance(layer, StackedDecoder):
                for attr in attrs:
                    out[(prefix + "." if prefix else "") + attr] = attr
        return out

    def _ensure_zero_plan(self):
        """Resolve (once, at build) whether this step runs the ZeRO
        execution mode. None falls through to the PR 6 reduce plan /
        GSPMD placement-hint path — which is also what
        ``PTPU_QUANT_COLLECTIVES=0`` (pre-PR bytes) and
        ``PTPU_ZERO_MODE=0`` force."""
        if self._zero_plan_ready:
            return self._zero_plan
        self._zero_plan_ready = True
        self._zero_plan = None
        from ..utils.flags import get_flags
        from .collectives import compose as _compose
        from .collectives import zero as _zero

        Reason = _compose.Reason

        def _decline(reason):
            _compose.note_plan_engagement("zero", reason)
            return None

        stage = _zero.resolve_stage(self.optimizer, self.sharding_stage)
        if stage < 2:
            return _decline(Reason.STAGE_LT_2)
        if get_flags("check_nan_inf")["check_nan_inf"]:
            # checkify cannot instrument through the manual region
            return _decline(Reason.CHECKIFY)
        entries = self.model.state_dict()
        named = []
        for n, t in entries.items():
            if not isinstance(t, Parameter):
                continue
            if t.trainable:
                named.append((n, t))
                continue
            # a FROZEN param with a data-axis Shard placement would ride
            # the zero step as a replicated "buffer" — gathered every
            # step and written back full, silently dropping its shard
            # residency (and pmean'd). The GSPMD hint path handles
            # frozen shards correctly, so decline the whole mode
            # (partial-finetune stage-3 keeps the pre-PR program).
            da = getattr(t, "_dist_attr", None)
            if da is not None and any(
                    isinstance(pl, Shard)
                    and da.process_mesh.get_dim_size(ax) > 1
                    and ax in ("dp", "sharding")
                    for ax, pl in zip(da.process_mesh.dim_names,
                                      da.placements)):
                return _decline(Reason.FROZEN_SHARD)
        reasons = []
        self._zero_plan = _zero.build_zero_plan(
            named, self.mesh, stage, optimizer=self.optimizer,
            grad_clip=self.optimizer._grad_clip,
            deferred=self._zero_deferred(), reason_out=reasons)
        _compose.note_plan_engagement(
            "zero", Reason.ENGAGED if self._zero_plan is not None
            else (reasons[0] if reasons else Reason.UNSPECIFIED))
        return self._zero_plan

    def zero_plan(self):
        """The resolved ZeroPlan (None = GSPMD / PR 6 path) — the bench
        "zero" block embeds its zero_summary()."""
        return self._zero_plan if self._zero_plan_ready else None

    def _build(self):
        cplan = self._ensure_composed_plan()
        if cplan is not None:
            # the composed plan owns the whole step: comms accounting
            # rides its GradReducePlan duck-type, and its inner zero
            # plan (possibly None) drives the slot-layout hooks
            self._reduce_plan = cplan
            self._reduce_plan_ready = True
            self._zero_plan = cplan.zero
            self._zero_plan_ready = True
            return self._build_composed(cplan)
        plan = self._ensure_zero_plan()
        if plan is None:
            return super()._build()
        # the zero plan owns the whole step: the PR 6 reduce plan must
        # not also engage (one manual region), and the comms accounting
        # rides the same seam (ZeroPlan duck-types GradReducePlan)
        self._reduce_plan = plan
        self._reduce_plan_ready = True
        self._build_zero(plan)

    # -- composed hybrid mode (distributed/collectives/compose) ------------
    def _ensure_composed_plan(self):
        """Resolve (once, at build) whether this step runs the composed
        hybrid mode — see collectives/compose.py's lattice. None falls
        through to the zero / reduce / ring plans (pure-data meshes) or
        the pre-PR GSPMD program (declined hybrids)."""
        if self._composed_plan_ready:
            return self._composed_plan
        self._composed_plan_ready = True
        self._composed_plan = None
        from .collectives import compose as _compose

        plan, reason = _compose.build_composed_plan(
            self.model, self.optimizer, self.mesh,
            sharding_stage=self.sharding_stage,
            shard_vocab_head=self.shard_vocab_head,
            grad_clip=self.optimizer._grad_clip,
            shard_opt_states=self.shard_opt_states)
        _compose.note_plan_engagement("composed", reason)
        self._composed_plan = plan
        return plan

    def composed_plan(self):
        """The resolved ComposedPlan (None = per-plan/GSPMD path) — the
        bench "comms" block embeds its summary()."""
        return self._composed_plan if self._composed_plan_ready else None

    def _build_composed(self, plan):
        """Compile the composed step: ONE fully-manual shard_map region
        over every live axis containing (gather/stage-slice params ->
        forward with in-region tp seams and the inline pipeline ring ->
        loss -> backward -> bucketed/quantized + zero grad reduce ->
        clip/guard -> sharded update). Mirrors _build_zero's step
        semantics operation for operation (docs/COMMS.md lattice,
        docs/PIPELINE.md schedule contract)."""
        import jax as _jax
        from jax import shard_map

        from .. import framework
        from ..jit import _wrap_arrays
        from ..utils.flags import get_flags as _gf
        from . import collectives
        from .collectives import compose as _compose
        from .collectives import zero as _zero
        from .. import telemetry as _telemetry

        model, train_fn, opt = self.model, self.train_fn, self.optimizer
        _telemetry.record_compile(
            self._compile_label(),
            ("build", bool(_gf("check_nan_inf")["check_nan_inf"]),
             "composed", plan.tp, plan.pp,
             plan.zero.stage if plan.zero else 0))
        entries = model.state_dict()
        self._param_names = [
            n for n, t in entries.items()
            if isinstance(t, Parameter) and t.trainable
        ]
        self._buffer_names = [n for n in entries
                              if n not in self._param_names]
        buffer_names = tuple(self._buffer_names)
        clip = opt._grad_clip
        reg = opt.regularization
        axes = plan.axes
        data_axes = plan.data_axes
        data_total = int(np.prod([self.mesh.get_dim_size(a)
                                  for a in data_axes])) if data_axes else 1
        zplan = plan.zero
        deferred_info = {}
        if zplan is not None:
            deferred_info = {
                p.deferred_attr: (zplan.shard_axis, p.shard_dim,
                                  zplan.shard_degree,
                                  zplan.gather_quantized)
                for p in zplan.params if p.deferred_attr}

        def make_loss_of(buffers, key_arr, batch):
            def loss_of(params):
                state = {}
                for n, p in params.items():
                    zp = zplan.by_name.get(n) if zplan is not None else None
                    if (zp is not None and zp.kind == "dim"
                            and zp.deferred_attr is None):
                        p = _zero.gather_shard(
                            p, zplan.shard_axis, zp.shard_dim,
                            degree=zplan.shard_degree,
                            quantized=zplan.gather_quantized)
                    state[n] = p
                state.update(buffers)
                with model._swap_state(state) as mutated:
                    with framework.no_grad(), framework.rng_key_scope(key_arr):
                        loss_t = train_fn(*_wrap_arrays(batch))
                new_buffers = {n: mutated[n] for n in buffer_names}
                return loss_t._data, new_buffers

            return loss_of

        def per_shard(params, buffers, opt_state, lr_, guard_, key_,
                      rng_ids, z_ids, s1_ids, tp_ids, pp_ids, *batch):
            # ordinals ride in as sharded iotas (lax.axis_index lowers
            # to PartitionId, rejected here); the RNG stream folds the
            # DATA ordinal only — mp/pp ranks replicate the same draws
            key = _jax.random.fold_in(key_, rng_ids[0])
            ctx = _compose.ComposedContext(
                plan, tp_ordinal=tp_ids[0], stage_ordinal=pp_ids[0])
            loss_of = make_loss_of(buffers, key, batch)
            with _compose.composed_scope(ctx), \
                    _zero.jit_gather_scope(deferred_info):
                (loss, new_buffers), grads = _jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            if plan.tp_seams and (ctx.seams is None
                                  or ctx.seams.calls == 0):
                raise RuntimeError(
                    "composed plan engaged tp seams but the model's "
                    "trace never routed a matmul through them "
                    "(models/gpt.py _block_pure) — the step would "
                    "compute on weight SHARDS as if they were full. "
                    "Use a flagship decoder stack or disable with "
                    "PTPU_COMPOSED=0 (docs/COMMS.md).")
            if data_axes:
                loss = _jax.lax.pmean(loss, data_axes)
                new_buffers = {
                    n: (_jax.lax.pmean(v, data_axes)
                        if jnp.issubdtype(v.dtype, jnp.inexact) else v)
                    for n, v in new_buffers.items()}
            zero_ord = z_ids[0]
            grads = _compose.reduce_grads(grads, plan, zero_ord)
            upd_params = _compose.update_view(params, plan, zero_ord)
            # stage-1 slot sharding (shard_opt_states): gather the
            # 1/degree slot shards to their full update view exactly;
            # the update runs the replicated math bit-for-bit and the
            # result slices back to the shard below — resident slot
            # storage never leaves its dp-sharded layout
            opt_state = _compose.stage1_gather_slots(opt_state, params,
                                                     plan)
            loss, new_upd, new_buffers, new_opt_state, health = \
                _step_update_tail(
                    opt, clip, reg, upd_params, grads, loss, new_buffers,
                    buffers, opt_state, lr_, guard_,
                    gsumsq_fn=lambda g: _compose.global_grad_sumsq(
                        g, plan))
            new_opt_state = _compose.stage1_slice_slots(
                new_opt_state, params, plan, s1_ids[0])
            new_params = _compose.params_out(new_upd, plan)
            return loss, new_params, new_buffers, new_opt_state, health

        def step(params, buffers, opt_state, lr, guard, key_arr, batch):
            def leaf_spec(arr):
                if (data_axes and hasattr(arr, "ndim") and arr.ndim >= 1
                        and arr.shape[0] % data_total == 0):
                    return P(data_axes)
                return P()

            batch_specs = tuple(leaf_spec(a) for a in batch)
            pspecs = {n: plan.param_specs.get(n, P()) for n in params}
            bspecs = {n: P() for n in buffers}
            nbspecs = {n: P() for n in buffer_names}

            def slot_spec(n, leaf):
                zp = zplan.by_name.get(n) if zplan is not None else None
                if (zp is not None and zp.kind == "flat"
                        and tuple(leaf.shape) == (zp.padded,)):
                    return P(zplan.shard_axis)
                # param-shaped slots follow the param's storage spec
                # (pipeline/TP-sharded optimizer state for free); a
                # stage-1 (shard_opt_states) slot additionally carries
                # its "sharding" extension — the dp-sharded layout rides
                # THROUGH the region instead of resharding to replicated
                if tuple(leaf.shape) == tuple(entries[n]._data.shape):
                    base = plan.param_specs.get(n, P())
                    sd = plan.slot_shards.get(n)
                    if sd is not None:
                        return _compose.stage1_slot_spec(base, sd[0])
                    return base
                return P()

            sspecs = {n: {k: slot_spec(n, v) for k, v in slots.items()}
                      for n, slots in opt_state.items()}
            rng_ids = jnp.arange(max(data_total, 1), dtype=jnp.int32)
            rng_spec = P(data_axes) if data_axes else P()
            if zplan is not None:
                z_ids = jnp.arange(zplan.shard_degree, dtype=jnp.int32)
                z_spec = P(zplan.shard_axis)
            else:
                z_ids = jnp.zeros((1,), jnp.int32)
                z_spec = P()
            if plan.slot_shards:
                s1_deg = next(iter(plan.slot_shards.values()))[1]
                s1_ids = jnp.arange(s1_deg, dtype=jnp.int32)
                s1_spec = P("sharding")
            else:
                s1_ids = jnp.zeros((1,), jnp.int32)
                s1_spec = P()
            if plan.tp_axis:
                tp_ids = jnp.arange(plan.tp, dtype=jnp.int32)
                tp_spec = P(plan.tp_axis)
            else:
                tp_ids = jnp.zeros((1,), jnp.int32)
                tp_spec = P()
            if plan.pp_axis:
                pp_ids = jnp.arange(plan.pp, dtype=jnp.int32)
                pp_spec = P(plan.pp_axis)
            else:
                pp_ids = jnp.zeros((1,), jnp.int32)
                pp_spec = P()
            with collectives.manual_grad_region():
                return shard_map(
                    per_shard, mesh=self.mesh.jax_mesh,
                    in_specs=(pspecs, bspecs, sspecs, P(), P(), P(),
                              rng_spec, z_spec, s1_spec, tp_spec, pp_spec)
                    + batch_specs,
                    out_specs=(P(), pspecs, nbspecs, sspecs, P()),
                    check_vma=False,
                    axis_names=self._manual_axes(axes),
                )(params, buffers, opt_state, lr, guard, key_arr,
                  rng_ids, z_ids, s1_ids, tp_ids, pp_ids, *batch)

        self._execs = {}
        self._checkified = False
        self._compiled = jax.jit(step, donate_argnums=(0, 2))

    def _manual_axes(self, axes):
        """``axis_names`` of a manual region over ``axes``: those, plus
        every mesh axis of size 1. A size-1 axis has nothing to
        partition, so making it manual changes no program — but Mosaic
        refuses to lower a Pallas kernel inside a region that leaves ANY
        mesh axis auto ("Mosaic kernels cannot be automatically
        partitioned", met by the ZeRO-3 line on the four-chip v5e host,
        PR 23: the fleet mesh always carries all five axes). Where
        another axis is live (a true hybrid mesh) the region stays
        partial, and a kernel inside it still needs a shard_map of its
        own (ROADMAP Queue 1 item 5)."""
        names = set(axes)
        names.update(n for n in self.mesh.dim_names
                     if self.mesh.get_dim_size(n) == 1)
        return names

    def _build_zero(self, plan):
        """Compile the ZeRO step: one fully-manual shard_map region over
        the data axes containing (gather params -> forward -> loss ->
        backward -> reduce-scatter grads -> clip/guard -> SHARDED
        optimizer update). Mirrors TrainStep._build's step semantics
        operation for operation — the chaos seam, regularizer, global-
        norm clip, StepHealth bundle, and guard skip-select all behave
        identically, just on 1/degree shards (docs/ZERO.md numerics
        contract)."""
        import jax as _jax
        from jax import shard_map

        from .. import framework
        from ..jit import _wrap_arrays
        from ..utils.flags import get_flags as _gf
        from . import collectives
        from .collectives import zero as _zero
        from .. import telemetry as _telemetry

        model, train_fn, opt = self.model, self.train_fn, self.optimizer
        _telemetry.record_compile(
            self._compile_label(),
            ("build", bool(_gf("check_nan_inf")["check_nan_inf"]), "zero",
             plan.stage))
        entries = model.state_dict()
        self._param_names = [
            n for n, t in entries.items()
            if isinstance(t, Parameter) and t.trainable
        ]
        self._buffer_names = [n for n in entries
                              if n not in self._param_names]
        buffer_names = tuple(self._buffer_names)
        clip = opt._grad_clip
        reg = opt.regularization
        axes = plan.axes
        total = plan.nranks
        deferred_info = {
            p.deferred_attr: (plan.shard_axis, p.shard_dim,
                              plan.shard_degree, plan.gather_quantized)
            for p in plan.params if p.deferred_attr}

        def make_loss_of(buffers, key_arr, batch):
            def loss_of(params):
                # stage-3 just-in-time gathers: non-deferred dim shards
                # gather here (AD of the gather IS the grad reduce-
                # scatter); deferred slabs stay shards — the scan body
                # gathers them per layer via the jit_gather scope
                state = {}
                for n, p in params.items():
                    zp = plan.by_name[n]
                    if zp.kind == "dim" and zp.deferred_attr is None:
                        p = _zero.gather_shard(
                            p, plan.shard_axis, zp.shard_dim,
                            degree=plan.shard_degree,
                            quantized=plan.gather_quantized)
                    state[n] = p
                state.update(buffers)
                with model._swap_state(state) as mutated:
                    with framework.no_grad(), framework.rng_key_scope(key_arr):
                        loss_t = train_fn(*_wrap_arrays(batch))
                new_buffers = {n: mutated[n] for n in buffer_names}
                return loss_t._data, new_buffers

            return loss_of

        def per_shard(params, buffers, opt_state, lr_, guard_, key_,
                      rng_ids, shard_ids, *batch):
            # per-shard RNG stream + ordinals ride in as sharded iotas
            # (lax.axis_index lowers to PartitionId, rejected here)
            key = _jax.random.fold_in(key_, rng_ids[0])
            ordinal = shard_ids[0]
            loss_of = make_loss_of(buffers, key, batch)
            with _zero.jit_gather_scope(deferred_info):
                (loss, new_buffers), grads = _jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            loss = _jax.lax.pmean(loss, axes)
            new_buffers = {
                n: (_jax.lax.pmean(v, axes)
                    if jnp.issubdtype(v.dtype, jnp.inexact) else v)
                for n, v in new_buffers.items()}
            grads = {n: _zero.reduce_grad(g, plan.by_name[n], plan,
                                          ordinal, mean=True)
                     for n, g in grads.items()}
            upd_params = _zero.update_view(params, plan, ordinal)
            # the ONE step tail (chaos inject -> reg -> health -> clip
            # -> update -> guard keep-select, jit._step_update_tail):
            # shared with the base TrainStep so PR 5 guard semantics
            # cannot drift between zero and non-zero steps — here it
            # runs on the shard views, with the sumsq psum'd over the
            # shard axis (ClipGradByNorm declined the plan at build)
            loss, new_upd, new_buffers, new_opt_state, health = \
                _step_update_tail(
                    opt, clip, reg, upd_params, grads, loss, new_buffers,
                    buffers, opt_state, lr_, guard_,
                    gsumsq_fn=lambda g: _zero.global_grad_sumsq(g, plan))
            new_params = _zero.params_out(new_upd, plan)
            return loss, new_params, new_buffers, new_opt_state, health

        def step(params, buffers, opt_state, lr, guard, key_arr, batch):
            def leaf_spec(arr):
                if (hasattr(arr, "ndim") and arr.ndim >= 1
                        and arr.shape[0] % total == 0):
                    return P(axes)
                return P()

            batch_specs = tuple(leaf_spec(a) for a in batch)
            pspecs = {n: (plan.by_name[n].spec
                          if plan.by_name[n].kind == "dim" else P())
                      for n in params}
            bspecs = {n: P() for n in buffers}
            nbspecs = {n: P() for n in buffer_names}

            def slot_spec(n, leaf):
                zp = plan.by_name[n]
                if (zp.kind == "flat"
                        and tuple(leaf.shape) == (zp.padded,)):
                    return P(plan.shard_axis)
                if zp.kind == "dim" and tuple(leaf.shape) == zp.shape:
                    return zp.spec
                return P()

            sspecs = {n: {k: slot_spec(n, v) for k, v in slots.items()}
                      for n, slots in opt_state.items()}
            rng_ids = jnp.arange(total, dtype=jnp.int32)
            shard_ids = jnp.arange(plan.shard_degree, dtype=jnp.int32)
            with collectives.manual_grad_region():
                return shard_map(
                    per_shard, mesh=self.mesh.jax_mesh,
                    in_specs=(pspecs, bspecs, sspecs, P(), P(), P(),
                              P(axes), P(plan.shard_axis)) + batch_specs,
                    out_specs=(P(), pspecs, nbspecs, sspecs, P()),
                    check_vma=False,
                    axis_names=self._manual_axes(axes),
                )(params, buffers, opt_state, lr, guard, key_arr,
                  rng_ids, shard_ids, *batch)

        self._execs = {}
        self._checkified = False
        self._compiled = jax.jit(step, donate_argnums=(0, 2))

    # -- zero slot layout --------------------------------------------------
    def _functional_state(self, params):
        """Fresh functional slots in the layout the step runs: under an
        engaged ZeroPlan, flat-kind params get flat ``[padded]`` slots
        (Optimizer.functional_state shard_spec) so the dp-sharded update
        owns a contiguous chunk per rank."""
        plan = self._ensure_zero_plan()
        spec = None
        if plan is not None:
            spec = {p.name: p.padded for p in plan.params
                    if p.kind == "flat"}
        return self.optimizer.functional_state(params,
                                               shard_spec=spec or None)

    def _adapt_restored_slot(self, arr, tgt, pname, pshape):
        """Flat-layout conversions for restored slots (docs/ZERO.md
        checkpoint contract), on top of the base rules: when the target
        is a flat ``[padded]`` dp-sharded slot, accept a same-length
        flat slot, a param-shaped slot (flatten + zero-pad — a non-zero
        checkpoint restoring into a zero run), or ANOTHER degree's flat
        slot (un-pad to numel, re-pad — the elastic-restart case where
        the padded length changed with the shard degree)."""
        plan = self._zero_plan if self._zero_plan_ready else None
        zp = plan.by_name.get(pname) if plan is not None else None
        if (zp is not None and zp.kind == "flat"
                and tuple(tgt.shape) == (zp.padded,)):
            if tuple(arr.shape) == (zp.padded,):
                return arr
            flat = arr.reshape(-1)
            if flat.size == zp.numel or (arr.ndim == 1
                                         and flat.size >= zp.numel):
                flat = flat[:zp.numel]
                return jnp.pad(flat, (0, zp.padded - zp.numel))
            return None
        return super()._adapt_restored_slot(arr, tgt, pname, pshape)

    # -- quantized/bucketed dp-grad reduce (distributed/collectives) -------
    def _ensure_reduce_plan(self):
        """Resolve (once) whether this step owns its dp grad reduce.

        Falls back to the inherited GSPMD program (plan None) whenever
        the restructure is unsafe or worthless on this runtime: master
        knob off, checkify debug mode, a live mesh axis outside
        {dp, sharding, mp} (pipeline/sep/ep kernels open their own
        manual regions, which cannot nest inside ours on this XLA), a
        param placement on a data axis (ZeRO-3), a vocab-sharded head
        (same nesting limit), or no gradient big enough to quantize."""
        if self._reduce_plan_ready:
            return self._reduce_plan
        self._reduce_plan_ready = True
        self._reduce_plan = None
        from ..utils.flags import get_flags
        from . import collectives
        from .collectives import compose as _compose

        Reason = _compose.Reason

        def _decline(reason):
            _compose.note_plan_engagement("grad_reduce", reason)
            return None

        if not collectives.quant_collectives_enabled():
            return _decline(Reason.MASTER_OFF)
        if get_flags("check_nan_inf")["check_nan_inf"]:
            return _decline(Reason.CHECKIFY)
        mp_live = ("mp" in self.mesh.dim_names
                   and self.mesh.get_dim_size("mp") > 1)
        if self.shard_vocab_head and mp_live:
            # the vocab-sharded CE opens its own mp shard_map island
            return _decline(Reason.VOCAB_SHARDED_HEAD)
        if collectives.tp_seam_mode() == "fused" and mp_live:
            # explicit seam forcing: the seam islands win the one manual
            # region this XLA allows (docs/COMMS.md precedence)
            return _decline(Reason.SEAM_FORCED)
        entries = self.model.state_dict()
        taken = set()
        for n in self._param_names:
            da = getattr(entries[n], "_dist_attr", None)
            if da is None:
                continue
            for ax_name, pl in zip(da.process_mesh.dim_names, da.placements):
                if isinstance(pl, Shard):
                    taken.add(ax_name)
        if taken & {"dp", "sharding"}:
            # ZeRO-3: a param placement on a DATA axis means the forward
            # must all-gather params inside the region, and gather with
            # manual subgroups is exactly the lowering this XLA rejects
            # (docs/COMMS.md runtime limits) — those placements stay
            # with GSPMD end to end, on every data axis
            return _decline(Reason.ZERO3_PLACEMENT)
        named = [(n, tuple(entries[n]._data.shape),
                  entries[n]._data.dtype) for n in self._param_names]
        reasons = []
        self._reduce_plan = collectives.build_grad_reduce_plan(
            named, self.mesh, reason_out=reasons)
        _compose.note_plan_engagement(
            "grad_reduce", Reason.ENGAGED if self._reduce_plan is not None
            else (reasons[0] if reasons else Reason.UNSPECIFIED))
        return self._reduce_plan

    def comms_plan(self):
        """The active grad-reduce plan (None = pre-PR GSPMD path) — the
        bench/dryrun "comms" block embeds its summary(). An engaged ring
        plan owns its own composed reduce (axes = data + sep)."""
        if self._ring_last_active and self._ring_plan is not None:
            return self._ring_plan.reduce
        return self._reduce_plan if self._reduce_plan_ready else None

    # -- ring attention over sep (collectives/ring_attention) --------------
    def _ensure_ring_plan(self):
        """Resolve (once, at build) whether this step runs context
        parallelism as ring attention over ``sep`` (docs/ATTENTION.md).
        Declines — keeping sep a plain batch axis and the program
        byte-for-byte pre-PR — on: the PTPU_RING_ATTN=0 escape hatch,
        checkify debug mode, ZeRO stage >= 2 (the zero mode owns the
        manual region, and itself declines sep-live meshes), a vocab-
        sharded head (its shard_map island cannot nest in ours), any
        live axis outside {dp, sharding, sep}, and models without a
        ring-eligible decoder stack."""
        if self._ring_plan_ready:
            return self._ring_plan
        self._ring_plan_ready = True
        self._ring_plan = None
        from ..utils.flags import get_flags
        from .collectives import compose as _compose
        from .collectives import ring_attention as _ring
        from .collectives import zero as _zero

        Reason = _compose.Reason

        def _decline(reason):
            _compose.note_plan_engagement("ring_attn", reason)
            return None

        if ("sep" not in self.mesh.dim_names
                or self.mesh.get_dim_size("sep") < 2):
            return None  # not a sep mesh at all: nothing to resolve
        if not _ring.ring_attn_enabled():
            from . import collectives

            return _decline(Reason.MASTER_OFF
                            if not collectives.quant_collectives_enabled()
                            else Reason.RING_OFF)
        if get_flags("check_nan_inf")["check_nan_inf"]:
            return _decline(Reason.CHECKIFY)
        if _zero.resolve_stage(self.optimizer, self.sharding_stage) >= 2:
            return _decline(Reason.ZERO_REQUESTED)
        if (self.shard_vocab_head
                and self.shard_vocab_head in self.mesh.dim_names
                and self.mesh.get_dim_size(self.shard_vocab_head) > 1):
            return _decline(Reason.VOCAB_SHARDED_HEAD)
        entries = self.model.state_dict()
        if not self._param_names:
            self._param_names = [
                n for n, t in entries.items()
                if isinstance(t, Parameter) and t.trainable]
        named = [(n, tuple(entries[n]._data.shape), entries[n]._data.dtype)
                 for n in self._param_names]
        reasons = []
        self._ring_plan = _ring.build_ring_attn_plan(
            named, self.mesh, self.model, reason_out=reasons)
        _compose.note_plan_engagement(
            "ring_attn", Reason.ENGAGED if self._ring_plan is not None
            else (reasons[0] if reasons else Reason.UNSPECIFIED))
        return self._ring_plan

    def ring_plan(self):
        """The resolved RingAttnPlan (None = sep stays a batch axis) —
        the bench "ring" block embeds its summary()."""
        return self._ring_plan if self._ring_plan_ready else None

    def _ring_value_and_grads(self, plan, seq, make_loss_of, params,
                              buffers, key_arr, batch):
        """The engaged-ring differentiation seam: ONE manual shard_map
        region over (data axes + sep). The residual stream stays
        sep-sharded between layers — only attention communicates, as a
        kv ring (models/gpt.py routes ``_sdpa_pure`` through
        ``ring_attention`` while the scope is active, and rope reads
        zigzag GLOBAL positions from the context). The fused-CE head
        runs on the token shard (no logits or hidden gather); the loss
        pmeans and every grad — partial over sep because each shard
        back-propagated only its local tokens — reduces through the
        plan's composed bucketed/quantized reduce."""
        import jax as _jax
        from jax import shard_map

        from . import collectives
        from .collectives import ring_attention as _ring

        axes = plan.axes
        data_axes = plan.data_axes
        data_total = int(np.prod([self.mesh.get_dim_size(a)
                                  for a in data_axes])) if data_axes else 1

        def leaf_spec(arr):
            if not hasattr(arr, "ndim") or arr.ndim == 0:
                return P()
            dim0 = (tuple(data_axes)
                    if data_axes and arr.shape[0] % data_total == 0
                    else None)
            if arr.ndim >= 2 and arr.shape[1] == seq:
                return P(dim0, plan.axis)
            return P(dim0) if dim0 else P()

        batch_specs = tuple(leaf_spec(a) for a in batch)
        pspecs = {n: P() for n in params}
        bspecs = {n: P() for n in buffers}
        nbspecs = {n: P() for n in self._buffer_names}

        def per_shard(params, buffers, key_arr, shard_id, sep_id, *batch):
            # per-shard RNG: fold the GLOBAL (dp x sep) ordinal into the
            # step key — the PR 6 dp discipline extended with the sep
            # ordinal, so dropout-style draws stay independent across
            # token shards too. Both ordinals ride in as sharded iotas
            # (lax.axis_index lowers to PartitionId, rejected here).
            key = _jax.random.fold_in(key_arr, shard_id[0])
            ctx = _ring.RingContext(plan.axis, plan.sep_degree,
                                    sep_id[0], plan=plan)
            loss_of = make_loss_of(buffers, key, batch)
            with _ring.ring_scope(ctx):
                (loss, new_buffers), grads = _jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            # mean of per-shard token means == the global mean when
            # shards hold equal valid-token counts (the dp caveat,
            # docs/COMMS.md, now also across sep token shards)
            loss = _jax.lax.pmean(loss, axes)
            new_buffers = {
                n: (_jax.lax.pmean(v, axes)
                    if jnp.issubdtype(v.dtype, jnp.inexact) else v)
                for n, v in new_buffers.items()}
            grads = collectives.reduce_grads(grads, plan.reduce,
                                             mean=True)
            return loss, new_buffers, grads

        shard_ids = jnp.arange(plan.nranks, dtype=jnp.int32)
        sep_ids = jnp.arange(plan.sep_degree, dtype=jnp.int32)
        plan.calls_traced = 0
        with collectives.manual_grad_region():
            out = shard_map(
                per_shard, mesh=self.mesh.jax_mesh,
                in_specs=(pspecs, bspecs, P(), P(axes), P(plan.axis))
                + batch_specs,
                out_specs=(P(), nbspecs, pspecs),
                check_vma=False,
                    axis_names=self._manual_axes(axes),
            )(params, buffers, key_arr, shard_ids, sep_ids, *batch)
        if plan.calls_traced == 0:
            raise RuntimeError(
                "ring attention plan engaged but the model's trace never "
                "routed attention through the ring seam "
                "(models/gpt.py _sdpa_pure) — the step would silently "
                "compute LOCAL-only attention. Use a flagship decoder "
                "stack or disable with PTPU_RING_ATTN=0 "
                "(docs/ATTENTION.md).")
        loss, new_buffers, grads = out
        return (loss, new_buffers), grads

    def _value_and_grads(self, make_loss_of, params, buffers, key_arr,
                         batch):
        # checkify debug rebuilds (FLAGS_check_nan_inf flipped after the
        # first build) must not reuse an engaged plan: checkify cannot
        # instrument through the manual region
        if getattr(self, "_checkified", False):
            return super()._value_and_grads(make_loss_of, params, buffers,
                                            key_arr, batch)
        ring, ring_seq = self._ring_batch_info(batch)
        if ring is not None:
            return self._ring_value_and_grads(ring, ring_seq,
                                              make_loss_of, params,
                                              buffers, key_arr, batch)
        plan = self._ensure_reduce_plan()
        if plan is None:
            return super()._value_and_grads(make_loss_of, params, buffers,
                                            key_arr, batch)
        import jax as _jax
        from jax import shard_map

        from . import collectives

        axes = plan.axes
        total = int(np.prod([self.mesh.get_dim_size(a) for a in axes]))

        def leaf_spec(arr):
            # mirror _batch_spec: dim 0 over the data axes when it splits
            if (hasattr(arr, "ndim") and arr.ndim >= 1
                    and arr.shape[0] % total == 0):
                return P(axes)
            return P()

        batch_specs = tuple(leaf_spec(a) for a in batch)
        pspecs = {n: P() for n in params}
        bspecs = {n: P() for n in buffers}
        nbspecs = {n: P() for n in self._buffer_names}

        def per_shard(params, buffers, key_arr, shard_id, *batch):
            # per-shard loss over the LOCAL batch rows; grads are the
            # per-rank partials the bucketed/quantized reduce combines.
            # NOTE the dp-mean here averages per-shard means — identical
            # to the global mean when shards hold equal valid-token
            # counts (a masked-loss skew shifts weighting by at most the
            # count imbalance; docs/COMMS.md)
            #
            # per-shard RNG stream: fold the shard ordinal into the step
            # key so dropout masks are independent across data shards
            # (the pre-PR global trace drew one mask per GLOBAL row; the
            # same key on every shard would tile one local mask pattern
            # across the batch). lax.axis_index lowers to PartitionId,
            # which this XLA rejects — the ordinal rides in as a
            # P(axes)-sharded iota instead (the sharded-CE trick).
            key = _jax.random.fold_in(key_arr, shard_id[0])
            loss_of = make_loss_of(buffers, key, batch)
            (loss, new_buffers), grads = _jax.value_and_grad(
                loss_of, has_aux=True)(params)
            loss = _jax.lax.pmean(loss, axes)
            # dp-consistent buffers: a batch-updated float buffer (BN-
            # style running stats) is computed from the LOCAL shard here
            # where the pre-PR program saw the global batch — pmean makes
            # the stored value deterministic and exact for linear
            # running-stat updates (mean of per-shard means). Replicated
            # untouched buffers pass through bitwise for power-of-two
            # shard counts; non-float buffers stay local (docs/COMMS.md).
            new_buffers = {
                n: (_jax.lax.pmean(v, axes)
                    if jnp.issubdtype(v.dtype, jnp.inexact) else v)
                for n, v in new_buffers.items()}
            grads = collectives.reduce_grads(grads, plan, mean=True)
            return loss, new_buffers, grads

        shard_ids = jnp.arange(total, dtype=jnp.int32)
        # a live-but-placement-free mp axis joins the region as a MANUAL
        # axis (params enter replicated; every mp rank runs the same
        # per-shard math redundantly, exactly what GSPMD computed for
        # it). Leaving it AUTO lets sharding propagation reach
        # instructions inside the manual region, which this XLA's
        # partitioner hard-aborts on (IsManualSubgroup CHECK — the
        # pre-existing example-02 crash class). The reduce axes
        # (plan.axes) are unchanged: no mp collective is ever emitted.
        region_axes = set(axes)
        if ("mp" in self.mesh.dim_names
                and self.mesh.get_dim_size("mp") > 1):
            region_axes.add("mp")
        with collectives.manual_grad_region():
            loss, new_buffers, grads = shard_map(
                per_shard, mesh=self.mesh.jax_mesh,
                in_specs=(pspecs, bspecs, P(), P(axes)) + batch_specs,
                out_specs=(P(), nbspecs, pspecs),
                check_vma=False, axis_names=self._manual_axes(region_axes),
            )(params, buffers, key_arr, shard_ids, *batch)
        return (loss, new_buffers), grads

    # -- step --------------------------------------------------------------
    def _call_impl(self, *batch):
        # the base __call__ owns the per-step instrumentation
        # (train_step_seconds/train_steps_total + the train_step trace
        # span, docs/TELEMETRY.md) — overriding only the impl keeps it
        # in ONE place for exactly the multi-chip runs where step
        # timing matters most
        return self._sharded_call(*batch)

    def _sharded_call(self, *batch):
        if not self._placed:
            self._place_model()
        first_state = self._opt_state is None
        from ..utils.flags import get_flags

        want_check = bool(get_flags("check_nan_inf")["check_nan_inf"])
        if self._compiled is None or want_check != getattr(
                self, "_checkified", False):
            if self._compiled is not None:
                # FLAGS_check_nan_inf flipped since the last build
                # (mirrors TrainStep._call_impl): re-resolve the plans —
                # checkify declines the composed/zero modes and the PR 6
                # reduce plan — and rebuild with/without instrumentation
                self._zero_plan_ready = False
                self._reduce_plan = None
                self._reduce_plan_ready = False
                self._ring_plan = None
                self._ring_plan_ready = False
                self._composed_plan = None
                self._composed_plan_ready = False
            self._build()
        entries = self.model.state_dict()
        params = {n: entries[n]._data for n in self._param_names}
        if first_state:
            self._opt_state = self._init_opt_state(params)
            self._place_opt_state(params)
        raw_batch = self._place_batch(_unwrap_tensors(batch))
        buffers = {n: entries[n]._data for n in self._buffer_names}
        lr = self.optimizer.get_lr()
        guard_arr = self._guard_operand()
        from .. import framework

        key_arr = framework.next_rng_key()
        # no ambient mesh context needed: every input carries an explicit
        # NamedSharding, and constraints inside the program name their mesh.
        out = self._dispatch_compiled(
            params, buffers, self._opt_state, lr, guard_arr, key_arr,
            raw_batch
        )
        if self._checkified:
            # raise BEFORE adopting any output (base-step semantics):
            # params/buffers/opt state stay at their pre-step values
            err, out = out
            err.throw()
        loss, new_params, new_buffers, self._opt_state, health = out
        self._last_health = health
        for n, arr in new_params.items():
            entries[n]._data = arr
        for n, arr in new_buffers.items():
            entries[n]._data = arr
        self.optimizer._step_count += 1
        # comms accounting: one tick per executed step with the plan's
        # static payload split (exact vs int8) — the counters behind the
        # bench "comms" block (docs/COMMS.md)
        from .collectives import (note_grad_reduce, note_ring_attn,
                                  note_zero_step)

        if self._ring_last_active and self._ring_plan is not None:
            # an engaged ring step owns its composed grad reduce (axes =
            # data + sep) and additionally rotates KV around the ring
            note_grad_reduce(self._ring_plan.reduce)
            note_ring_attn(self._ring_plan)
        else:
            note_grad_reduce(self._reduce_plan)
            note_zero_step(self._reduce_plan)
        # quant-compute flops accounting (docs/QUANT.md): per-step tick at
        # the rate the last engaged trace recorded (global batch tokens)
        from ..quant import note_step_tokens

        shape = getattr(raw_batch[0], "shape", ()) if raw_batch else ()
        note_step_tokens(int(shape[0]) * int(shape[1])
                         if len(shape) >= 2 else 0)
        return Tensor(loss)


# ---------------------------------------------------------------------------
# ZeRO / group-sharded marks (parity: group_sharded_parallel,
# dygraph_sharding_optimizer.py:54, group_sharded_stage{2,3}.py)
# ---------------------------------------------------------------------------
def shard_model_parameters(model, mesh: ProcessMesh, axis="sharding"):
    """ZeRO-3: give every parameter a Shard placement over `axis` on its
    first divisible NON-LEADING dim — falling back to dim 0, else
    replicated.

    Non-leading dims are preferred because a multi-dim parameter's
    leading axis is the layer axis for the stacked-decoder ``[L, ...]``
    slabs: a Shard(0) slab cannot defer its gather into the scan body
    (each rank would scan DIFFERENT layers), so the just-in-time gather
    path (docs/ZERO.md) needs shard_dim >= 1 — and on flagship configs
    ``num_layers % degree == 0`` holds exactly where the JIT gathers
    matter most. GSPMD is indifferent to the dim choice."""
    from .auto_parallel import TensorDistAttr

    size = mesh.get_dim_size(axis)
    ax_idx = mesh.dim_names.index(axis)
    for _, p in model.named_parameters():
        if p._dist_attr is not None:
            taken = any(
                isinstance(pl, Shard) and i == ax_idx
                for i, pl in enumerate(p._dist_attr.placements)
            )
            if taken:
                continue
            placements = list(p._dist_attr.placements)
        else:
            placements = [Replicate() for _ in mesh.dim_names]
        shard_dims = {pl.dim for pl in placements if isinstance(pl, Shard)}
        ndim = p._data.ndim
        order = (list(range(1, ndim)) + [0]) if ndim >= 2 else range(ndim)
        for d in order:
            if d not in shard_dims and p._data.shape[d] % size == 0:
                placements[ax_idx] = Shard(d)
                break
        p._dist_attr = TensorDistAttr(mesh, placements)
    return model


def group_sharded_parallel(model, optimizer, level, scaler=None, group=None,
                           offload=False, sync_buffers=False, **kwargs):
    """Parity: paddle.distributed.sharding.group_sharded_parallel.

    level: "os" (stage1) | "os_g" (stage2) | "p_g_os" (stage3).
    Returns (model, optimizer, scaler) with sharding marks applied; the
    actual partitioning happens when ShardedTrainStep places state on
    the mesh — stage1 shards optimizer slots (shard_opt_states), stage
    2/3 engage the ZeRO execution mode (reduce-scattered grads,
    dp-sharded update, stage-3 just-in-time param gathers) when the
    mesh qualifies, else fall back to GSPMD placements (docs/ZERO.md).
    """
    if level not in ("os", "os_g", "p_g_os"):
        raise ValueError(
            f"group_sharded_parallel level={level!r}: expected 'os' "
            "(stage 1), 'os_g' (stage 2) or 'p_g_os' (stage 3)")
    if offload:
        # the kwarg used to be silently ignored — pretending CPU offload
        # happened is worse than refusing it (a planner sized for
        # offloaded slots would OOM the chip)
        raise NotImplementedError(
            "group_sharded_parallel(offload=True): CPU offload of "
            "sharded state is not implemented on this runtime. Sharded "
            "state stays in HBM, divided by the sharding degree "
            "(docs/ZERO.md); pass offload=False.")
    if kwargs:
        import warnings

        warnings.warn(
            "group_sharded_parallel: ignoring unknown kwargs "
            f"{sorted(kwargs)} — accepted for reference-API "
            "compatibility, but none of them alter this runtime's "
            "sharding behavior", stacklevel=2)
    from .auto_parallel import get_mesh

    mesh = get_mesh()
    if mesh is None:
        from .fleet import get_fleet_mesh

        mesh = get_fleet_mesh()
    if mesh is None:
        raise RuntimeError("call fleet.init or set_mesh before group_sharded_parallel")
    if level == "p_g_os":
        shard_model_parameters(model, mesh)
    optimizer._group_sharded_level = level
    return model, optimizer, scaler
