"""Mesh/schedule layout autotuner over the compose lattice.

Every bench config used to hand-pick its parallelism layout (dp/mp/pp/
sep degrees, ZeRO stage, pipeline schedule, microbatch count, comm
buckets) even though the pieces to derive it already existed:
``plan_train_step`` AOT-prices batch x remat candidates without
executing them, ``COMPAT_LATTICE`` knows which plan combinations
compose, and ``compiled_cost_summary`` + ``memory_analysis()`` price
any lowered program. This module closes the loop (the
arXiv:2004.13336 / GC3 exemplars: derive placement from a cost model
instead of per-config folklore):

1. :class:`LayoutCandidate` extends the planner grid with the layout
   axes — (dp, sharding, mp, pp, sep) degrees factoring the device
   count, ZeRO stage, pipeline schedule x microbatch count, comm
   bucket MB — on top of batch/remat/head_chunk/quant.
2. A pruning pass consults the compose lattice BEFORE lowering: each
   hybrid (mp/pp-live) layout shell resolves ``build_composed_plan``
   once (cheap — no trace); a declined shell prunes every candidate on
   it with the structured :class:`~..distributed.collectives.compose.
   Reason`. Only composable candidates pay a lower+compile.
3. Survivors are scored lowering-only (``TrainStep.aot_report``: one
   AOT compile yields XLA ``memory_analysis`` peak AND the roofline
   ``compiled_cost_summary``) by a predicted tokens/sec:
   ``tokens / (compute_s / (1 - pipeline_idle) + wire_bytes / link)``
   with the HBM-budget fit as a hard constraint.
4. The winning :class:`LayoutDecision` caches on disk next to the
   planner's PlanDecision, keyed by (config, chip, device count,
   budget, grids, every engagement-affecting env knob).

Entry point :func:`autotune_train_step` returns the BUILT
``ShardedTrainStep`` for the winning layout plus the decision
(docs/AUTOTUNE.md).

Knobs:
- ``PTPU_LAYOUT_CACHE``: decision-cache path; ``0`` disables.
- ``PTPU_LINK_GBPS``: override the interconnect bandwidth the comm
  term prices against (GB/s).

Telemetry: ``autotune_candidates_total{verdict,reason}`` (verdict in
pruned | lowered | error; reason = compose Reason value for pruned,
owning lattice row for lowered, "lowering_error" for error) and the
``autotune_search_seconds`` gauge (docs/TELEMETRY.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import time

from .. import telemetry as _telemetry
from .planner import (MemoryPlanError, PlanDecision, _cache_load,
                      _cache_store, chip_kind, hbm_budget_bytes,
                      is_hbm_oom)

_CANDS = _telemetry.counter(
    "autotune_candidates_total",
    "layout candidates examined by the mesh/schedule autotuner, by "
    "verdict (pruned | lowered | error) and structured reason "
    "(compose Reason for pruned, owning lattice row for lowered)",
    labelnames=("verdict", "reason"))
_SEARCH_SECONDS = _telemetry.gauge(
    "autotune_search_seconds",
    "wall seconds the last layout search spent (pruning + lowering + "
    "scoring; 0 on a decision-cache hit)")

#: mesh axes in the fleet topology order the degrees factor over
LAYOUT_AXES = ("dp", "sharding", "mp", "pp", "sep")

#: env knobs that change which plans ENGAGE for a layout — every one
#: rides the decision cache key so a stale decision can't replay across
#: a knob flip (the PR 2 staleness class; docs/AUTOTUNE.md contract)
LAYOUT_ENV_KNOBS = (
    "PTPU_QUANT_COLLECTIVES", "PTPU_COMPOSED", "PTPU_PIPELINE_SCHEDULE",
    "PTPU_ZERO_MODE", "PTPU_ZERO_JIT_GATHER", "PTPU_RING_ATTN",
    "PTPU_SHARDED_HEAD", "PTPU_TP_SEAM", "PTPU_COMM_BUCKET_MB",
    "PTPU_QUANT_PARAM_GATHER", "PTPU_LINK_GBPS", "PTPU_CE_VCHUNK",
)


class LayoutSearchError(MemoryPlanError):
    """No layout candidate is composable, lowerable and within budget."""


@dataclasses.dataclass(frozen=True)
class LayoutCandidate:
    """One point of the layout search space: the mesh degrees (must
    multiply to the searched device count), the ZeRO stage, the
    pipeline schedule axes, the comm bucket cap, and the planner's
    existing batch/remat/head_chunk/quant axes. ``batch`` is rows PER
    DATA SHARD — the global batch is ``batch * data_parallel``, so
    every layout's batch divides its data axes by construction."""

    dp: int = 1
    sharding: int = 1
    mp: int = 1
    pp: int = 1
    sep: int = 1
    zero_stage: int = 0
    pp_schedule: str = "1f1b"
    pp_microbatches: int | None = None
    bucket_mb: int | None = None
    batch: int = 1
    policy: str = "none"
    head_chunk: int | None = None
    quant: str | None = None

    @property
    def device_count(self):
        n = 1
        for a in LAYOUT_AXES:
            n *= int(getattr(self, a))
        return n

    @property
    def data_parallel(self):
        """Product of the batch-sharding axes (dim-0 of the batch)."""
        return self.dp * self.sharding * self.sep

    @property
    def n_micro(self):
        return int(self.pp_microbatches or self.pp)

    @property
    def hybrid(self):
        return self.mp > 1 or self.pp > 1

    def live_axes(self):
        return frozenset(a for a in LAYOUT_AXES
                         if int(getattr(self, a)) > 1)

    def degrees(self):
        return {a: int(getattr(self, a)) for a in LAYOUT_AXES}

    def shell(self):
        """The composability-deciding slice: two candidates on the same
        shell share the compose verdict (batch/remat/head_chunk/bucket
        never change whether a plan engages), so the pruning oracle
        runs once per shell."""
        return (self.dp, self.sharding, self.mp, self.pp, self.sep,
                self.zero_stage,
                self.pp_schedule if self.pp > 1 else None,
                self.n_micro if self.pp > 1 else None)

    def label(self):
        axes = "x".join(f"{a}{getattr(self, a)}" for a in LAYOUT_AXES
                        if int(getattr(self, a)) > 1) or "single"
        parts = [axes, f"z{self.zero_stage}"]
        if self.pp > 1:
            parts.append(f"{self.pp_schedule}@{self.n_micro}")
        if self.bucket_mb:
            parts.append(f"bk{self.bucket_mb}")
        parts.append(f"b{self.batch}")
        if self.head_chunk:
            parts.append(f"hc{self.head_chunk}")
        if self.quant:
            parts.append(f"q-{self.quant}")
        pol = str(self.policy)
        parts.append("r-" + (pol.split(":", 1)[0] if ":" in pol else pol))
        return "/".join(parts)

    def as_json(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class LayoutDecision:
    """The search outcome — the bench JSON ``"layout"`` block
    (docs/AUTOTUNE.md contract). ``memory`` embeds a genuine
    :class:`~.planner.PlanDecision` record for the winner (source
    "autotune", batch = GLOBAL rows) so hbm_report / the bench
    ``"memory"`` block work unchanged."""

    layout: dict
    label: str
    predicted_score: float          # predicted tokens/sec
    predicted_step_seconds: float
    peak_bytes: int
    budget_bytes: int
    fits: bool
    source: str                     # "search" | "cache" | "fallback"
    chip: str
    device_count: int
    key: str
    searched: int                   # candidates lowered (incl. baseline)
    pruned_total: int
    pruned_by_reason: dict = dataclasses.field(default_factory=dict)
    search_seconds: float = 0.0
    fallback_reason: str | None = None
    candidates: list = dataclasses.field(default_factory=list)  # top-3
    pruned: list = dataclasses.field(default_factory=list)
    errors: list = dataclasses.field(default_factory=list)
    baseline: dict | None = None
    link: dict | None = None
    memory: dict | None = None

    def as_json(self):
        return dataclasses.asdict(self)

    def fingerprint(self):
        """sha1 over the decision MINUS the volatile fields (wall
        seconds, cache provenance) — two searches of the same config
        must agree on this bitwise (tests/test_autotune.py)."""
        d = self.as_json()
        d.pop("search_seconds", None)
        d.pop("source", None)
        return hashlib.sha1(
            repr(sorted(d.items(), key=lambda kv: kv[0])).encode()
        ).hexdigest()


# -- link model --------------------------------------------------------------
def link_bytes_per_sec():
    """(bytes_per_sec, placeholder?) of the inter-chip link:
    ``PTPU_LINK_GBPS`` override > the chip table's published ICI figure
    (``paddle_tpu.device.CHIP_PEAKS``; CPU runs get the flagged
    placeholder, an unknown TPU kind raises)."""
    env = os.environ.get("PTPU_LINK_GBPS")
    if env:
        return float(env) * 1e9, False
    from ..device import chip_peaks

    peaks, placeholder = chip_peaks()
    return float(peaks["ici_bytes_per_sec"]), placeholder


def plan_wire_bytes(step):
    """Per-step collective payload bytes of the step's RESOLVED plans:
    the active grad-reduce plan's exact + quantized wire bytes
    (GradReducePlan / ZeroPlan / ComposedPlan / ring reduce all share
    the accounting surface) plus the zero plan's param-gather traffic
    (gathers move params OUT of collectives — disjoint from the grad
    bytes the reduce accounting counts)."""
    total = 0
    plan = step.comms_plan() if hasattr(step, "comms_plan") else None
    if plan is not None:
        total += int(plan.exact_bytes) + int(plan.quantized_wire_bytes)
    zp = step.zero_plan() if hasattr(step, "zero_plan") else None
    if zp is not None:
        total += int(getattr(zp, "param_gather_bytes", 0))
    return total


def pipeline_idle_fraction(layout):
    """The schedule's analytic idle fraction — ``pipeline.
    bubble_fraction_model`` with unit phase costs (the measured-cost
    ``bubble_report`` compiles probe programs per call, far too
    expensive per candidate; the analytic budget ranks schedules and
    microbatch counts the same way)."""
    if layout.pp <= 1:
        return 0.0
    from ..distributed.pipeline import bubble_fraction_model

    return float(bubble_fraction_model(layout.n_micro, layout.pp,
                                       schedule=layout.pp_schedule))


# -- search space ------------------------------------------------------------
def default_zero_stage(dp, sharding, mp, pp, sep):
    """The stage the hand-tuned configs converged on per mesh family:
    stage 3 on pure sharding-live data meshes (the config-5 lineage),
    stage 2 under a hybrid with a live data axis (the 10b lineage),
    stage 0 everywhere else (sep-live meshes: the zero mode declines
    them; no data axis: nothing to shard over)."""
    if mp > 1 or pp > 1:
        return 2 if (dp > 1 or sharding > 1) else 0
    if sep > 1:
        return 0
    return 3 if sharding > 1 else 0


def enumerate_layouts(device_count, *, mp_max=2, pp_max=2, sep_max=2,
                      zero_stage_fn=None, schedules=None,
                      microbatches=(None,), bucket_mbs=(None,),
                      batches=(1,), policies=("none",),
                      head_chunks=(None,), quants=(None,)):
    """The default search space: every (dp, sharding, mp, pp, sep)
    factorization of ``device_count`` under the axis caps, each with
    the stage :func:`default_zero_stage` picks (``zero_stage_fn``
    overrides), crossed with the schedule/microbatch grid on pp-live
    shells and the planner's batch/remat/head_chunk/quant grids.
    Off-lattice hybrid shells (e.g. sep live under mp/pp) ARE
    generated — the pruning pass records them with their structured
    decline Reason instead of silently skipping them. Deterministic
    order (the decision must reproduce bitwise across runs)."""
    n = int(device_count)
    stage_fn = zero_stage_fn or default_zero_stage
    divisors = [d for d in range(1, n + 1) if n % d == 0]
    shells = []
    for mp in divisors:
        if mp > mp_max:
            continue
        for pp in (d for d in divisors if (n // mp) % d == 0):
            if pp > pp_max:
                continue
            for sep in (d for d in divisors if (n // (mp * pp)) % d == 0):
                if sep > sep_max:
                    continue
                rem = n // (mp * pp * sep)
                for dp in (d for d in divisors if rem % d == 0):
                    shells.append((dp, rem // dp, mp, pp, sep))
    out = []
    for dp, sharding, mp, pp, sep in sorted(shells):
        stage = int(stage_fn(dp, sharding, mp, pp, sep))
        scheds = (schedules if schedules is not None
                  else (("1f1b",) if pp > 1 else (None,)))
        if pp <= 1:
            scheds, micros = (None,), (None,)
        else:
            micros = microbatches
        for sched in scheds:
            for nm in micros:
                nm_eff = int(nm or pp)
                for bk in bucket_mbs:
                    for b in batches:
                        # the pipeline splits the per-shard batch into
                        # microbatches — round the grid batch up to the
                        # nearest multiple so every pp-live candidate
                        # lowers (score normalizes by tokens, so a
                        # bigger batch doesn't bias the ranking)
                        b_eff = (b if pp <= 1 or b % nm_eff == 0
                                 else b + nm_eff - b % nm_eff)
                        for pol in policies:
                            for hc in head_chunks:
                                for q in quants:
                                    out.append(LayoutCandidate(
                                        dp=dp, sharding=sharding, mp=mp,
                                        pp=pp, sep=sep, zero_stage=stage,
                                        pp_schedule=sched or "1f1b",
                                        pp_microbatches=nm, bucket_mb=bk,
                                        batch=b_eff, policy=pol,
                                        head_chunk=hc, quant=q))
    return out


# -- candidate build ---------------------------------------------------------
@contextlib.contextmanager
def _layout_env(layout):
    """Apply the layout's env-carried knobs around a candidate build
    (knobs are read at BUILD time — bucket_bytes_cap)."""
    saved = {}
    if layout.bucket_mb is not None:
        saved["PTPU_COMM_BUCKET_MB"] = os.environ.get("PTPU_COMM_BUCKET_MB")
        os.environ["PTPU_COMM_BUCKET_MB"] = str(int(layout.bucket_mb))
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _pin_layout_env(layout):
    """Pin the winner's env-carried knobs for the process: the returned
    step (and any program bench builds after it) must honor the decided
    bucket cap — the knob IS part of the layout now."""
    if layout.bucket_mb is not None:
        os.environ["PTPU_COMM_BUCKET_MB"] = str(int(layout.bucket_mb))


def _build_mesh(layout):
    from ..distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": layout.dp, "mp_degree": layout.mp,
        "pp_degree": layout.pp, "sharding_degree": layout.sharding,
        "sep_degree": layout.sep,
    }
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_fleet_mesh()


def _make_step(layout, model, train_fn, optimizer, mesh):
    from ..distributed.parallel_step import ShardedTrainStep

    return ShardedTrainStep(
        model, train_fn, optimizer, mesh,
        shard_opt_states=(layout.zero_stage == 1),
        sharding_stage=(layout.zero_stage or None))


def _build_candidate(layout, model_factory):
    """mesh + factory model + ShardedTrainStep for one candidate (no
    trace, no compile — plan resolution only happens when the caller
    asks)."""
    mesh = _build_mesh(layout)
    model, train_fn, optimizer = model_factory(layout, mesh)
    return _make_step(layout, model, train_fn, optimizer, mesh)


def flagship_gpt_factory(cfg_factory, *, lr=1e-3, seed=0,
                         optimizer_factory=None, amp_bf16=False):
    """``model_factory`` for GPTForCausalLMPipe flagships — the
    MULTICHIP dryrun's shape. ``cfg_factory()`` returns
    a fresh GPTConfig per call; the factory applies the layout's remat/
    head-chunk/schedule axes to it, the layout's placements to the
    decoder (pipeline placements when pp > 1, tp placements when only
    mp > 1), and the ``group_sharded_parallel`` level matching the
    ZeRO stage. ``amp_bf16=True`` mirrors ``bench.build_model``'s TPU
    build: the model constructs under O2 autocast and its params cast
    to bf16 — without it a searched program would be priced in f32
    while the measured run executes bf16."""
    def factory(layout, mesh):
        import paddle_tpu as paddle
        from ..distributed.parallel_step import group_sharded_parallel
        from ..models.gpt import GPTForCausalLMPipe

        paddle.seed(seed)
        cfg = cfg_factory()
        pol = layout.policy
        if layout.quant and str(pol).startswith("names:"):
            pol = f"{pol},quant:{layout.quant}"
        cfg.recompute = pol != "none"
        cfg.recompute_policy = pol
        cfg.head_chunk = layout.head_chunk
        if layout.pp > 1:
            cfg.pp_schedule = layout.pp_schedule
            # plain attribute — compose reads getattr(cfg,
            # "pp_microbatches", None) or pp
            cfg.pp_microbatches = layout.n_micro
        if amp_bf16:
            import jax.numpy as jnp

            with paddle.amp.auto_cast(enable=True, dtype="bfloat16",
                                      level="O2"):
                model = GPTForCausalLMPipe(cfg)
            for _, p in model.named_parameters():
                p._data = p._data.astype(jnp.bfloat16)
        else:
            model = GPTForCausalLMPipe(cfg)
        if layout.pp > 1:
            model.decoder.apply_pipeline_placements(
                mesh, tp_axis="mp" if layout.mp > 1 else None)
        elif layout.mp > 1:
            model.decoder.apply_tp_placements(mesh, tp_axis="mp")
        if optimizer_factory is not None:
            opt = optimizer_factory(model)
        else:
            opt = paddle.optimizer.AdamW(learning_rate=lr,
                                         parameters=model.parameters())
        if layout.zero_stage:
            level = {1: "os", 2: "os_g", 3: "p_g_os"}[layout.zero_stage]
            model, opt, _ = group_sharded_parallel(model, opt, level)
        return model, (lambda a, b: model.loss(a, b)), opt

    return factory


# -- decision cache ----------------------------------------------------------
def _layout_cache_path(path=None):
    if path is not None:
        return path or None
    env = os.environ.get("PTPU_LAYOUT_CACHE")
    if env == "0":
        return None
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                        "layout_plan.json")


def _layout_key(chip, ndev, budget, cache_extra, layouts, baseline,
                require_fit):
    from ..models.gpt import scan_layers_enabled
    from ..quant import cache_key_knobs as _quant_knobs

    grid = tuple(tuple(sorted(l.as_json().items())) for l in layouts)
    base = (tuple(sorted(baseline.as_json().items()))
            if baseline is not None else None)
    knobs = tuple((k, os.environ.get(k, "")) for k in LAYOUT_ENV_KNOBS)
    scan_mode = "scan" if scan_layers_enabled() else "unrolled"
    return hashlib.sha1(repr(
        (chip, ndev, budget, tuple(cache_extra), grid, base, require_fit,
         scan_mode, knobs, _quant_knobs())
    ).encode()).hexdigest()[:16]


# -- scoring -----------------------------------------------------------------
def _score(layout, mem, cost, step, seq_len, link_bps):
    """Predicted tokens/sec for a lowered candidate (docs/AUTOTUNE.md
    cost model): roofline compute seconds inflated by the schedule's
    analytic idle fraction, plus the resolved plans' collective bytes
    over the link bandwidth. The HBM fit is checked by the caller —
    this only prices time."""
    tokens = layout.batch * layout.data_parallel * seq_len
    if cost is not None:
        compute_s = float(cost["device_seconds_est"])
    else:
        # no cost analysis from this executable: fall back to a pure
        # bandwidth proxy over the program's working set so ranking
        # still has a compute term (flagged via cost_placeholder)
        from ..jit import _device_peaks

        _, pb, _ = _device_peaks()
        compute_s = float(mem["temp_bytes"] + mem["output_bytes"]) / pb
    idle = pipeline_idle_fraction(layout)
    wire = plan_wire_bytes(step)
    comm_s = wire / link_bps if link_bps > 0 else 0.0
    step_s = compute_s / max(1e-9, 1.0 - idle) + comm_s
    return {
        "label": layout.label(),
        "layout": layout.as_json(),
        "predicted_tokens_per_sec": tokens / max(step_s, 1e-12),
        "predicted_step_seconds": step_s,
        "compute_seconds_est": compute_s,
        "comm_seconds_est": comm_s,
        "idle_fraction": idle,
        "wire_bytes_per_step": int(wire),
        "tokens_per_step": int(tokens),
        "peak_bytes": int(mem["peak_bytes"]),
        "cost_placeholder": cost is None or bool(
            cost.get("peak_model_placeholder")),
    }


# -- the autotuner -----------------------------------------------------------
def autotune_train_step(model_factory, *, seq_len, layouts=None,
                        baseline=None, batch_avals_fn=None,
                        budget_bytes=None, require_fit=True,
                        cache_path=None, cache_extra=(),
                        device_count=None):
    """Search the layout lattice and return ``(step, decision)`` — the
    BUILT :class:`~..distributed.parallel_step.ShardedTrainStep` for
    the winning layout (plans resolved, nothing executed) and the
    :class:`LayoutDecision` record.

    ``model_factory(layout, mesh) -> (model, train_fn, optimizer)``
    builds the model for one candidate with the layout's placements
    and sharding level applied (:func:`flagship_gpt_factory` makes one
    for flagship GPT configs). The search NEVER executes a step: hybrid
    shells resolve ``build_composed_plan`` first (no trace) and only
    composable candidates are lowered (``aot_report`` — one AOT compile
    per survivor, pricing memory and roofline cost together).

    ``baseline`` (a LayoutCandidate) is the hand-picked reference: it
    is always scored through the same cost model (and may legitimately
    win), lands in ``decision.baseline`` for the bench_gate LAYOUT
    gate, and is the fallback layout when no searched candidate fits —
    recorded as ``source="fallback"`` with a structured
    ``fallback_reason``, never silently.

    Decisions cache at ``~/.cache/paddle_tpu/layout_plan.json``
    (``PTPU_LAYOUT_CACHE``; ``0`` disables), keyed by (config, chip,
    device count, budget, grids, every engagement-affecting env knob —
    :data:`LAYOUT_ENV_KNOBS`). A hit rebuilds the winning step without
    searching.
    """
    import jax

    ndev = int(device_count
               or len(jax.devices()))
    budget = hbm_budget_bytes(budget_bytes)
    chip = chip_kind()
    if layouts is None:
        layouts = enumerate_layouts(ndev)
    layouts = list(layouts)
    for l in layouts:
        if l.device_count != ndev:
            raise ValueError(
                f"layout {l.label()} factors {l.device_count} devices, "
                f"searching {ndev}")
        if not l.hybrid and _lattice_owner_for(l) is None:
            raise ValueError(
                f"layout {l.label()} is off every compose-lattice row "
                f"(live axes {sorted(l.live_axes())}, stage "
                f"{l.zero_stage}) — not searchable (docs/AUTOTUNE.md)")
    if baseline is not None and baseline.device_count > ndev:
        raise ValueError(
            f"baseline {baseline.label()} needs {baseline.device_count} "
            f"devices, have {ndev}")
    key = _layout_key(chip, ndev, budget, cache_extra, layouts, baseline,
                      require_fit)
    avals_fn = batch_avals_fn or (
        lambda l: _default_batch_avals(l, seq_len))

    cpath = _layout_cache_path(cache_path)
    if cpath:
        hit = _cache_load(cpath).get(key)
        if hit:
            decision = LayoutDecision(**dict(hit, source="cache"))
            _SEARCH_SECONDS.set(0.0)
            winner = LayoutCandidate(**decision.layout)
            step = _finalize_winner(winner, model_factory)
            return step, decision

    t0 = time.perf_counter()
    link_bps, link_placeholder = link_bytes_per_sec()
    scored = []
    pruned = []
    errors = []
    shell_declines = {}

    def _examine(layout, *, is_baseline=False):
        shell = layout.shell()
        if layout.hybrid and shell in shell_declines:
            reason = shell_declines[shell]
            pruned.append({"label": layout.label(), "reason": reason,
                           "layout": layout.as_json()})
            _CANDS.inc(labels=("pruned", reason))
            return None
        with _layout_env(layout):
            step = _build_candidate(layout, model_factory)
            if layout.hybrid:
                plan = step._ensure_composed_plan()
                if plan is None:
                    from ..distributed.collectives import compose

                    v = compose.last_verdicts().get("composed")
                    reason = (v[1] if v
                              else compose.Reason.UNSPECIFIED.value)
                    shell_declines[shell] = reason
                    pruned.append({"label": layout.label(),
                                   "reason": reason,
                                   "layout": layout.as_json()})
                    _CANDS.inc(labels=("pruned", reason))
                    return None
            # lowering-only pricing: one AOT compile, zero execution
            step._planning = True
            try:
                mem, cost = step.aot_report(*avals_fn(layout))
            except Exception as e:
                if not is_hbm_oom(e):
                    # a refused kernel or a tracing bug is not a
                    # property of the layout's size — surface it
                    e.add_note(f"while pricing layout {layout.label()} "
                               "(memory.autotune_train_step)")
                    raise
                # the compiler ran out of HBM: the one lowering failure
                # that means "this layout does not fit"
                errors.append({"label": layout.label(),
                               "error": str(e)[:200]})
                _CANDS.inc(labels=("error", "compile_oom"))
                return None
            _CANDS.inc(labels=("lowered",
                               _lattice_owner_for(layout) or "composed"))
            rec = _score(layout, mem, cost, step, seq_len, link_bps)
            rec["fits"] = mem["peak_bytes"] <= budget
            rec["is_baseline"] = bool(is_baseline)
            scored.append(rec)
            return rec

    seen = set()
    for layout in layouts:
        seen.add(layout.label())
        _examine(layout)
    baseline_rec = None
    if baseline is not None:
        if baseline.label() in seen:
            baseline_rec = next(r for r in scored
                                if r["label"] == baseline.label())
            baseline_rec["is_baseline"] = True
        else:
            baseline_rec = _examine(baseline, is_baseline=True)

    ranked = sorted(scored,
                    key=lambda r: (-r["predicted_tokens_per_sec"],
                                   r["label"]))
    fitting = [r for r in ranked if r["fits"]]
    source, fallback_reason = "search", None
    if fitting:
        win_rec = fitting[0]
    elif not require_fit and ranked:
        win_rec = ranked[0]
        source, fallback_reason = "search", "no_candidate_fit_unenforced"
    elif baseline_rec is not None:
        win_rec = baseline_rec
        source = "fallback"
        fallback_reason = ("no_candidate_lowered" if not ranked
                           else "no_candidate_fit")
    else:
        raise LayoutSearchError(
            f"no layout candidate is composable and within the HBM "
            f"budget ({budget} bytes on {chip}); pruned={len(pruned)} "
            f"errors={errors}")
    winner = LayoutCandidate(**win_rec["layout"])

    by_reason = {}
    for p in pruned:
        by_reason[p["reason"]] = by_reason.get(p["reason"], 0) + 1
    search_seconds = time.perf_counter() - t0
    _SEARCH_SECONDS.set(search_seconds)

    mem_record = PlanDecision(
        batch=winner.batch * winner.data_parallel, policy=winner.policy,
        peak_bytes=int(win_rec["peak_bytes"]), budget_bytes=int(budget),
        fits=bool(win_rec["fits"]),
        score=float(win_rec["predicted_tokens_per_sec"]),
        source="autotune", chip=chip, key=key,
        head_chunk=winner.head_chunk, quant=winner.quant,
        candidates=[{k: r[k] for k in ("label", "peak_bytes", "fits",
                                       "predicted_tokens_per_sec")}
                    for r in ranked[:3]],
        zero=({"stage": winner.zero_stage,
               "degree": winner.data_parallel, "param_bytes": 0,
               "slot_bytes": 0, "grad_bytes": 0, "hbm_savings_bytes": 0}
              if winner.zero_stage else None))
    decision = LayoutDecision(
        layout=winner.as_json(), label=winner.label(),
        predicted_score=float(win_rec["predicted_tokens_per_sec"]),
        predicted_step_seconds=float(win_rec["predicted_step_seconds"]),
        peak_bytes=int(win_rec["peak_bytes"]), budget_bytes=int(budget),
        fits=bool(win_rec["fits"]), source=source, chip=chip,
        device_count=ndev, key=key, searched=len(scored),
        pruned_total=len(pruned), pruned_by_reason=by_reason,
        search_seconds=round(search_seconds, 3),
        fallback_reason=fallback_reason,
        candidates=ranked[:3], pruned=pruned, errors=errors,
        baseline=(dict(baseline_rec) if baseline_rec is not None
                  else None),
        link={"bytes_per_sec": link_bps, "placeholder": link_placeholder},
        memory=mem_record.as_json())
    if cpath:
        _cache_store(cpath, key, decision)
    step = _finalize_winner(winner, model_factory)
    return step, decision


def _lattice_owner_for(layout):
    from ..distributed.collectives import compose

    return compose.lattice_owner(layout.live_axes(),
                                 stage=layout.zero_stage)


def _default_batch_avals(layout, seq_len):
    import jax
    import jax.numpy as jnp

    rows = layout.batch * layout.data_parallel
    return (jax.ShapeDtypeStruct((rows, int(seq_len)), jnp.int32),
            jax.ShapeDtypeStruct((rows, int(seq_len)), jnp.int64))


def _finalize_winner(layout, model_factory):
    """Build the winning step for real: pin the layout's env knobs for
    the process (the decided bucket cap must govern every later build),
    re-init the fleet mesh, and resolve the step's plans (``_build`` —
    trace-free) so the returned object is ready to compile on first
    call."""
    _pin_layout_env(layout)
    step = _build_candidate(layout, model_factory)
    step._build()
    return step
