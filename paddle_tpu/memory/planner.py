"""XLA-memory-driven batch/remat auto-planner.

Every round since r3 hand-tuned the bench batch and remat name list
against OOMs. But
``jit(...).lower().compile().memory_analysis()`` tells us the exact HBM
budget of any candidate (batch, remat-policy) TrainStep WITHOUT executing
it — the same buffer-assignment numbers the XLA weight-update-sharding
work (arXiv:2004.13336) converts into throughput. The planner lowers the
candidate grid ahead of time, rejects configs whose peak exceeds the chip
budget, and picks the best fit by a throughput estimate — so no caller
carries hand-set caps and a chip upgrade re-plans itself.

Planning cost is compile time (one AOT compile per candidate evaluated,
highest-score first, stopping at the first fit); decisions are cached on
disk keyed by (config hash, chip, device count, budget, grid), so only
the first run per configuration pays.

Knobs (docs/MEMORY.md):
- ``PTPU_HBM_BUDGET``: override the per-chip budget (GB when < 1024,
  bytes otherwise).
- ``PTPU_PLAN_CACHE``: decision-cache path; ``0`` disables caching.

Telemetry gauges set on every decision: ``hbm_peak_bytes``,
``act_saved_bytes``, ``act_int8_bytes``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os

from .. import telemetry as _telemetry
from .int8_ckpt import int8_saved_nbytes, parse_save_names

_HBM_PEAK = _telemetry.gauge(
    "hbm_peak_bytes",
    "planner-chosen train-step peak HBM (XLA buffer assignment: "
    "argument + temp bytes)")
_ACT_SAVED = _telemetry.gauge(
    "act_saved_bytes",
    "estimated bytes of remat-saved activations per step under the "
    "chosen policy (all layers)")
_ACT_INT8 = _telemetry.gauge(
    "act_int8_bytes",
    "estimated bytes of int8-saved activations (+fp32 scales) within "
    "act_saved_bytes")
_PLAN_EVALS = _telemetry.counter(
    "memory_plan_lowerings_total",
    "candidate TrainStep programs lowered+compiled by the planner",
    # fit | over_budget | cache_hit | memoized ("memoized" = a build
    # SAVED because an earlier candidate already lowered the same
    # traced program; fit+over_budget = actual lowerings)
    labelnames=("outcome",))


class MemoryPlanError(RuntimeError):
    """No candidate fits the HBM budget."""


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the batch x remat x head-chunk x depth grid.
    ``score`` overrides the default throughput estimate (higher =
    preferred). ``head_chunk`` is the fused-CE vocab-chunk size (None =
    the kernel default) — larger chunks mean fewer serialized LSE scan
    steps but a bigger resident [tokens, chunk] fp32 block, so it trades
    against batch/remat inside the same HBM budget. ``depth`` is a
    num_layers override for callers whose step_factory rebuilds the
    model per candidate — with scan-over-layers compilation flat in
    depth (docs/SCAN.md), depth sweeps cost one cheap AOT compile per
    point instead of a depth-linear trace."""
    batch: int
    policy: str
    score: float | None = None
    head_chunk: int | None = None
    depth: int | None = None
    #: quant-compute site spec ("all"/"attn"/"ffn"/comma-joined sites,
    #: None = wide GEMMs): the step_factory appends the matching
    #: ``quant:`` entries to the candidate's names: policy, making
    #: narrow-vs-wide compute a planner axis like batch x remat
    #: (docs/QUANT.md)
    quant: str | None = None


@dataclasses.dataclass
class PlanDecision:
    batch: int
    policy: str
    peak_bytes: int
    budget_bytes: int
    fits: bool
    score: float
    source: str          # "planner" | "cache" | "env-override"
    chip: str
    key: str
    act_saved_bytes: int | None = None
    act_int8_bytes: int | None = None
    opt_state_bytes: int | None = None
    candidates: list = dataclasses.field(default_factory=list)
    head_chunk: int | None = None
    depth: int | None = None
    #: winning candidate's quant-compute site spec (Candidate.quant) —
    #: the caller re-applies it to the policy it builds with
    quant: str | None = None
    #: ZeRO pricing record (docs/ZERO.md): {"stage", "degree", analytic
    #: byte pools, "hbm_savings_bytes"} — None when no zero info passed
    zero: dict | None = None

    def as_json(self):
        """The bench JSON ``"memory"`` block (docs/MEMORY.md contract)."""
        return dataclasses.asdict(self)


# -- budget -----------------------------------------------------------------
def chip_kind():
    import jax

    return jax.devices()[0].device_kind


def hbm_budget_bytes(budget=None):
    """Resolve the HBM budget: PTPU_HBM_BUDGET env (GB if < 1024, bytes
    otherwise) > explicit arg > backend bytes_limit > the chip table
    (``paddle_tpu.device.CHIP_PEAKS``; an unknown TPU kind raises)."""
    env = os.environ.get("PTPU_HBM_BUDGET")
    if env:
        v = float(env)
        return int(v * 2**30) if v < 1024 else int(v)
    if budget is not None:
        return int(budget)
    import jax

    from ..device import chip_peaks

    stats = jax.devices()[0].memory_stats() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return int(chip_peaks()[0]["hbm_bytes"])


def is_hbm_oom(exc):
    """True when ``exc`` is XLA refusing a program because it does not
    fit device memory — the ONLY compile failure the planners may file
    as "over budget". Anything else (a Mosaic refusal, a scoped-VMEM
    overflow inside a kernel, a tracing error) is a bug in the program,
    not a property of the candidate, and must surface."""
    import jax

    msg = str(exc)
    return (isinstance(exc, jax.errors.JaxRuntimeError)
            and "RESOURCE_EXHAUSTED" in msg and "hbm" in msg.lower())


# -- throughput estimate ----------------------------------------------------
# Fraction of one decoder block's forward FLOPs the backward replay SKIPS
# when the anchor is saved (models/gpt.py _block_pure tags). Heuristic
# weights fit to the r3-r5 sweeps (attention kernel ~ a fifth of the
# block, gate+up ~ a third); they only need to rank policies, not predict
# absolute MFU.
_ANCHOR_COVERAGE = {
    "attn_res": 0.18, "attn_lse": 0.02, "attn_out": 0.20,
    "attn_q": 0.07, "attn_k": 0.055, "attn_v": 0.055,
    "resid_mid": 0.09, "ln2_out": 0.01, "rms_rstd": 0.01,
    "ffn_gate": 0.17, "ffn_up": 0.17, "ffn_out": 0.04,
}
#: int8 saves skip the same recompute but pay quant/dequant bandwidth
_INT8_DISCOUNT = 0.9
_POLICY_COVERAGE = {"none": 1.0, "full": 0.0, "dots": 0.6,
                    "attn": 0.22, "attn_ffn": 0.26}


def policy_coverage(policy):
    """~fraction of forward FLOPs the backward replay skips under
    ``policy`` (a recompute_policy string)."""
    pol = str(policy)
    if pol in _POLICY_COVERAGE:
        return _POLICY_COVERAGE[pol]
    if pol.startswith("names:"):
        _, int8_names = parse_save_names(pol[len("names:"):])
        cov = 0.0
        for raw in pol[len("names:"):].split(","):
            nm = raw.strip()
            base = nm[len("int8:"):] if nm.startswith("int8:") else nm
            w = _ANCHOR_COVERAGE.get(base, 0.0)
            cov += w * (_INT8_DISCOUNT if base in int8_names else 1.0)
        return min(cov, 0.95)
    return 0.0


def throughput_score(batch, policy, head_chunk=None):
    """MFU-shaped estimate: useful FLOPs per token are 3F (fwd+bwd), the
    replay re-runs (1 - coverage)F of them, and larger batches buy mildly
    better MXU efficiency. Calibrated on r4/r5: b3 + full ffn saves must
    outrank b4 without them (measured 0.5629 vs 0.5468). A larger CE
    head chunk nudges the score up (fewer serialized LSE scan steps —
    only a ranking tiebreak, the HBM cost is what memory_analysis
    prices)."""
    import math

    cov = policy_coverage(policy)
    score = 3.0 / (4.0 - cov) * (1.0 + 0.03 * int(batch))
    if head_chunk:
        score *= 1.0 + 0.004 * math.log2(max(int(head_chunk), 1) / 1024.0)
    return score


# -- activation-byte estimate (telemetry + bench JSON) ----------------------
def estimate_stacked_activation_bytes(policy, *, num_layers, batch, seq,
                                      hidden, num_heads, num_kv_heads,
                                      intermediate, act_bytes=2,
                                      block=None):
    """(saved_bytes, int8_bytes) the stacked decoder's remat policy pins
    in HBM across all layers — the analytic counterpart of
    ``memory_analysis`` that attributes bytes to NAMES. Unknown anchors
    count 0 (custom-kernel residual shapes vary); non-``names:`` policies
    return (0, 0)."""
    from .int8_ckpt import INT8_BLOCK

    block = block or INT8_BLOCK
    pol = str(policy)
    if not pol.startswith("names:"):
        return 0, 0
    _, int8_names = parse_save_names(pol[len("names:"):])
    hd = hidden // num_heads
    kv = num_kv_heads * hd
    tok = batch * seq
    # elements per layer, with the dtype each anchor is saved in
    elems = {
        "attn_q": (tok * hidden, act_bytes),
        "attn_k": (tok * kv, act_bytes),
        "attn_v": (tok * kv, act_bytes),
        "attn_out": (tok * hidden, act_bytes),
        "attn_res": (tok * hidden, act_bytes),
        "attn_lse": (tok * num_heads, 4),
        "resid_mid": (tok * hidden, act_bytes),
        "ln2_out": (tok * hidden, act_bytes),
        "ffn_gate": (tok * intermediate, act_bytes),
        "ffn_up": (tok * intermediate, act_bytes),
        "ffn_out": (tok * intermediate, act_bytes),
        "rms_rstd": (tok * 2, 4),  # one rstd row-vector per rms (2/block)
    }
    saved = int8 = 0
    for raw in pol[len("names:"):].split(","):
        nm = raw.strip()
        base = nm[len("int8:"):] if nm.startswith("int8:") else nm
        if base not in elems:
            continue
        n, nbytes = elems[base]
        if base in int8_names:
            b = int8_saved_nbytes(n, block)
            int8 += b
            saved += b
        else:
            saved += n * nbytes
    return saved * num_layers, int8 * num_layers


# -- decision cache ---------------------------------------------------------
def _cache_path(path=None):
    if path is not None:
        return path or None
    env = os.environ.get("PTPU_PLAN_CACHE")
    if env == "0":
        return None
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                        "memory_plan.json")


def _cache_load(path):
    try:
        with open(path) as f:
            d = json.load(f)
        return d if isinstance(d, dict) else {}
    except (OSError, ValueError):
        return {}


def _cache_store(path, key, decision):
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        d = _cache_load(path)
        d[key] = decision.as_json()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(d, f)
        os.replace(tmp, path)
    except OSError:
        pass  # cache is an optimization; planning already succeeded


# -- ZeRO stage pricing (docs/ZERO.md) --------------------------------------
def zero_hbm_savings(zero):
    """Per-device bytes a ZeRO stage frees versus the unsharded program:
    slot state divides by the sharding degree from stage 1, gradient
    working set from stage 2, resident params from stage 3. ``zero`` is
    a dict {"stage", "degree", "slot_bytes", "grad_bytes",
    "param_bytes"} — the byte pools are the ANALYTIC sizes of the
    UNSHARDED program the planner measured; pass 0 pools when the
    candidate programs were already compiled on the live sharded mesh
    (their memory_analysis peak is per-device and already divided)."""
    if not zero:
        return 0
    degree = int(zero.get("degree") or 1)
    stage = int(zero.get("stage") or 0)
    if degree <= 1 or stage < 1:
        return 0
    frac = 1.0 - 1.0 / degree
    saved = int(zero.get("slot_bytes") or 0) * frac
    if stage >= 2:
        saved += int(zero.get("grad_bytes") or 0) * frac
    if stage >= 3:
        saved += int(zero.get("param_bytes") or 0) * frac
    return int(saved)


# -- the planner ------------------------------------------------------------
def default_program_key(cand):
    """The candidate axes that change the traced program, conservatively:
    every grid axis. Callers that KNOW two candidates lower to the same
    program pass a coarser ``program_key_fn`` — e.g. one that resolves
    the EFFECTIVE CE head chunk (fused_cross_entropy.resolve_vocab_chunk
    clamps to the vocab), so head_chunk values that clamp to the same
    chunk share one lowering instead of re-compiling per spelling."""
    return (cand.batch, cand.policy, getattr(cand, "head_chunk", None),
            getattr(cand, "depth", None), getattr(cand, "quant", None))


def plan_train_step(step_factory, candidates, *, budget_bytes=None,
                    cache_path=None, cache_extra=(), act_bytes_fn=None,
                    opt_state_bytes=None, require_fit=True, zero=None,
                    program_key_fn=None):
    """Pick the best (batch, policy) that fits the HBM budget.

    ``step_factory(candidate) -> (TrainStep, batch_avals)`` builds a step
    for the candidate; the planner lowers+compiles it WITHOUT executing
    (``TrainStep.memory_stats`` over abstract avals — no buffers are
    allocated) and reads the XLA buffer-assignment peak. Candidates are
    tried highest :func:`throughput_score` first; the first fit wins, so
    the common case compiles one program. ``require_fit=False`` accepts
    the top candidate even over budget (the env-override path — trust the
    human, but still record ``fits``).

    ``act_bytes_fn(candidate) -> (saved, int8)`` optionally attributes
    saved-activation bytes for telemetry/the bench JSON.

    ``program_key_fn(candidate)`` names the axes that actually change
    the TRACED program (default :func:`default_program_key` — every grid
    axis). When two candidates map to the same key, the second reuses
    the first's measured memory instead of re-lowering — the saved
    build is counted as ``memory_plan_lowerings_total{outcome=
    "memoized"}`` and the evaluated record carries ``"memoized": true``.

    ``zero`` (docs/ZERO.md): ZeRO stage pricing — slot (stage>=1), grad
    (stage>=2) and param (stage>=3) HBM divide by the sharding degree,
    so a candidate whose raw single-chip peak busts the budget can
    still be ACCEPTED at stage 3 (:func:`zero_hbm_savings` is
    subtracted from every measured peak before the fit check, and the
    record lands in ``PlanDecision.zero``). The cache key carries the
    stage/degree: a decision priced at stage 3 is never replayed for a
    stage-0 build.

    Decisions are cached at ``cache_path`` (default
    ``~/.cache/paddle_tpu/memory_plan.json``, env ``PTPU_PLAN_CACHE``,
    ``0`` disables) keyed by (chip, device count, budget, grid,
    ``cache_extra``); a hit returns without lowering anything.
    """
    import jax

    budget = hbm_budget_bytes(budget_bytes)
    chip = chip_kind()
    ndev = len(jax.devices())
    order = sorted(
        candidates,
        key=lambda c: (c.score if c.score is not None
                       else throughput_score(c.batch, c.policy,
                                             getattr(c, "head_chunk", None))),
        reverse=True)
    grid = [(c.batch, c.policy, getattr(c, "head_chunk", None),
             getattr(c, "depth", None), getattr(c, "quant", None))
            for c in order]
    # the key must carry the scan/unroll mode: a decision priced under
    # the depth-flat scanned program replayed for an unrolled build (or
    # vice versa) would hand back a config priced against the WRONG
    # program — the same staleness class the mem_envs hardening closed
    # in PR 2 (docs/SCAN.md). Depth rides in per-candidate via `grid`.
    # The mode comes from the ONE resolver the model dispatch uses
    # (lazy import: no cycle — models.gpt pulls memory only in-function)
    from ..models.gpt import scan_layers_enabled

    scan_mode = "scan" if scan_layers_enabled() else "unrolled"
    savings = zero_hbm_savings(zero)
    zero_key = (tuple(sorted((k, int(v or 0)) for k, v in zero.items()))
                if zero else None)
    # every quant-compute knob rides in the key: a cached decision priced
    # with wide GEMMs must not replay across a PTPU_QUANT_COMPUTE flip
    # (the same staleness class as scan_mode above — docs/QUANT.md)
    from ..quant import cache_key_knobs as _quant_knobs

    key = hashlib.sha1(repr(
        (chip, ndev, budget, tuple(cache_extra), grid, require_fit,
         scan_mode, zero_key, _quant_knobs())
    ).encode()).hexdigest()[:16]

    cpath = _cache_path(cache_path)
    if cpath:
        hit = _cache_load(cpath).get(key)
        if hit:
            hit = dict(hit, source="cache")
            decision = PlanDecision(**hit)
            _PLAN_EVALS.inc(labels=("cache_hit",))
            _set_gauges(decision)
            return decision

    evaluated = []
    chosen = None
    key_fn = program_key_fn or default_program_key
    lowered = {}  # program key -> measured memory (the memoization seam)
    for cand in order:
        score = (cand.score if cand.score is not None
                 else throughput_score(cand.batch, cand.policy,
                                       getattr(cand, "head_chunk", None)))
        pkey = key_fn(cand)
        memoized = pkey in lowered
        if memoized:
            # an earlier candidate already lowered this exact traced
            # program (e.g. head_chunk spellings clamping to the same
            # effective CE chunk) — reuse its measured bytes, count the
            # saved build
            mem = lowered[pkey]
            _PLAN_EVALS.inc(labels=("memoized",))
        else:
            step, batch_avals = step_factory(cand)
            # label this step's build as a planning compile so the
            # recompile watchdog's per-function counts stay meaningful
            # (jit._build)
            step._planning = True
            try:
                mem = step.memory_stats(*batch_avals)
            except Exception as e:
                if not is_hbm_oom(e):
                    # not a property of the candidate's size: a kernel
                    # the compiler refused, a tracing bug — surface it
                    # with the candidate named, never as "does not fit"
                    e.add_note(f"while pricing candidate {pkey!r} "
                               "(memory.plan_train_step)")
                    raise
                # the compiler itself ran out of HBM: over budget, with
                # no peak to report
                _PLAN_EVALS.inc(labels=("over_budget",))
                evaluated.append(
                    {"batch": cand.batch, "policy": cand.policy,
                     "head_chunk": getattr(cand, "head_chunk", None),
                     "depth": getattr(cand, "depth", None),
                     "quant": getattr(cand, "quant", None),
                     "score": score, "fits": False,
                     "compile_oom": str(e)[:200]})
                continue
            lowered[pkey] = mem
        # zero pricing: the sharded stages free (1 - 1/degree) of the
        # slot/grad/param pools versus the measured unsharded program
        fits = mem["peak_bytes"] - savings <= budget
        if not memoized:
            _PLAN_EVALS.inc(labels=("fit" if fits else "over_budget",))
        evaluated.append({"batch": cand.batch, "policy": cand.policy,
                          "head_chunk": getattr(cand, "head_chunk", None),
                          "depth": getattr(cand, "depth", None),
                          "quant": getattr(cand, "quant", None),
                          "score": score, "peak_bytes": mem["peak_bytes"],
                          "fits": fits, "memoized": memoized})
        if fits or not require_fit:
            chosen = (cand, mem, score, fits)
            break
    if chosen is None:
        raise MemoryPlanError(
            f"no candidate fits the HBM budget ({budget} bytes on {chip}); "
            f"evaluated: {evaluated}")

    cand, mem, score, fits = chosen
    decision = PlanDecision(
        batch=cand.batch, policy=cand.policy,
        head_chunk=getattr(cand, "head_chunk", None),
        depth=getattr(cand, "depth", None),
        quant=getattr(cand, "quant", None),
        peak_bytes=int(mem["peak_bytes"]), budget_bytes=int(budget),
        fits=bool(fits), score=float(score),
        source="planner" if require_fit else "env-override",
        chip=chip, key=key, opt_state_bytes=opt_state_bytes,
        candidates=evaluated,
        zero=(dict(zero, hbm_savings_bytes=int(savings))
              if zero else None))
    if act_bytes_fn is not None:
        saved, i8 = act_bytes_fn(cand)
        decision.act_saved_bytes = int(saved)
        decision.act_int8_bytes = int(i8)
    _set_gauges(decision)
    if cpath:
        _cache_store(cpath, key, decision)
    return decision


def _set_gauges(decision):
    _HBM_PEAK.set(decision.peak_bytes)
    if decision.act_saved_bytes is not None:
        _ACT_SAVED.set(decision.act_saved_bytes)
    if decision.act_int8_bytes is not None:
        _ACT_INT8.set(decision.act_int8_bytes)
