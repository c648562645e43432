"""bench_gate: fail CI on a tokens/sec regression between bench rounds.

Usage::

    python tools/bench_gate.py                 # newest BENCH_r*.json vs
                                               # the previous round
    python tools/bench_gate.py NEW.json        # explicit candidate
    python tools/bench_gate.py NEW.json --against OLD.json [OLD2.json ...]
    python tools/bench_gate.py --threshold 0.08   # allow 8%

Accepts every bench artifact shape this repo produces:

- raw ``bench.py`` stdout (one JSON object per line, log lines ignored),
- driver round files ``BENCH_r*.json`` (``{"tail": "...", "parsed":
  ...}`` — metric lines are re-parsed out of ``tail``),
- a bare ``{"metric": ..., "value": ...}`` object or a list of them.

For every metric name shared between the candidate and a reference file,
the gate compares ``value`` (tokens/sec/chip) and **exits 1 if the
candidate is more than ``--threshold`` (default 5%) below the
reference**. Metrics present on only one side are reported but don't
gate (a new bench line must not fail the round that introduces it).
``mfu`` is printed alongside when present. BASELINE.json carries no
absolute numbers (the reference publishes none) — it is accepted and
skipped with a note, so ``--against BASELINE.json BENCH_rNN.json`` works
as a documented CI line.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys


def _records_from_obj(obj):
    if isinstance(obj, list):
        out = []
        for o in obj:
            out.extend(_records_from_obj(o))
        return out
    if not isinstance(obj, dict):
        return []
    recs = []
    if "tail" in obj and isinstance(obj["tail"], str):
        recs.extend(_records_from_text(obj["tail"]))
    if not recs and isinstance(obj.get("parsed"), dict):
        recs.extend(_records_from_obj(obj["parsed"]))
    if "metric" in obj and "value" in obj:
        recs.append(obj)
    return recs


def _records_from_text(text):
    recs = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj and "value" in obj:
            recs.append(obj)
    return recs


def load_metrics(path):
    """path -> {metric: record} (last line per metric wins, like the
    driver's parse). Records without a numeric value are dropped."""
    with open(path) as f:
        text = f.read()
    try:
        recs = _records_from_obj(json.loads(text))
    except ValueError:
        recs = _records_from_text(text)
    out = {}
    for r in recs:
        try:
            float(r["value"])
        except (TypeError, ValueError):
            continue
        out[str(r["metric"])] = r
    return out


def _round_files(root):
    files = glob.glob(os.path.join(root, "BENCH_r*.json"))

    def key(p):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    return sorted((p for p in files if key(p) >= 0), key=key)


def resilience_violations(rec):
    """Violation strings from one bench record's "resilience" block and
    its telemetry guard counters. A CLEAN bench run (no chaos injection)
    must report zero anomalies and zero rollbacks — any other value
    means the hardware/numerics misbehaved during the measurement or the
    guard false-positived; either way the round must not land silently
    (docs/RESILIENCE.md)."""
    out = []
    res = rec.get("resilience")
    if isinstance(res, dict) and res.get("enabled"):
        anomalies = res.get("anomalies") or {}
        total = res.get("anomalies_total")
        if total is None:
            total = sum(int(v) for v in anomalies.values())
        if int(total) > 0:
            out.append(f"guard_anomalies_total={total} ({anomalies})")
        if int(res.get("rollbacks") or 0) > 0:
            out.append(f"guard rollbacks={res['rollbacks']}")
        if res.get("aborted"):
            out.append("guard ABORTED the run")
        # the per-guard block is authoritative for this record; the
        # process-global telemetry counters describe the SAME events
        # (shared across every metric line) — reporting both would
        # print one anomaly up to once per source per line
        return out
    counters = (rec.get("telemetry") or {}).get("counters") or {}
    for name in ("guard_anomalies_total", "guard_rollbacks_total"):
        series = counters.get(name) or {}
        total = (sum(series.values()) if isinstance(series, dict)
                 else int(series))
        if total:
            out.append(f"telemetry {name}={total}")
    return out


def comms_violations(rec):
    """Violation strings from one bench record's "comms" block: a
    quantized run whose loss-parity probe drifted past its threshold
    must not land silently — either the quantizer regressed or the
    gradients stopped being block-quantizable (docs/COMMS.md)."""
    out = []
    comms = rec.get("comms")
    if not isinstance(comms, dict):
        return out
    parity = comms.get("parity")
    if isinstance(parity, dict) and parity.get("enabled"):
        err = parity.get("max_rel_err")
        thr = parity.get("threshold")
        if err is not None and thr is not None and float(err) > float(thr):
            out.append(
                f"quantized-collective parity drift {float(err):.4f} > "
                f"threshold {float(thr):.4f}")
        elif parity.get("ok") is False:
            out.append("quantized-collective parity probe reported ok=false")
    return out


def ring_violations(rec):
    """Violation strings from one bench record's "ring" block
    (docs/ATTENTION.md; the ``*_seq32k`` long-context lines): a run
    whose ring-vs-dense parity probe drifted past its embedded
    threshold must not land silently — a hop mask or merge regression
    is a numerics bug, not noise. Reference-free, like the comms parity
    gate; a sep-mesh run whose plan unexpectedly declined (enabled but
    never engaged) also fails — the line would silently measure the
    batch-axis fallback instead of the ring."""
    block = rec.get("ring") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or not block.get("enabled"):
        return []
    out = []
    parity = block.get("parity")
    if isinstance(parity, dict) and parity.get("enabled"):
        err = parity.get("max_rel_err")
        thr = parity.get("threshold")
        if err is not None and thr is not None and float(err) > float(thr):
            out.append(f"ring-attention parity drift {float(err):.2e} > "
                       f"threshold {float(thr):.2e}")
        elif parity.get("ok") is False:
            out.append("ring-attention parity probe reported ok=false")
    if block.get("engaged") is False:
        out.append("ring plan built but never engaged — the long-context "
                   "line measured the batch-axis fallback")
    return out


#: decline reasons that describe the CONFIG's shape, not a silent
#: downgrade — documented fallbacks (docs/PIPELINE.md) a user may run on
#: purpose, so the never-engaged check reports them in the JSON but does
#: not hard-fail the round. Everything else (checkify, frozen shards,
#: optimizer stats, missing reason...) still fails: the line claims a
#: pipeline it silently is not measuring.
PIPE_CONFIG_DECLINES = frozenset({
    "no_stage_placements",          # pp axis live, decoder not staged
    "interleave_not_composed",      # VPP layout (Reason.INTERLEAVE)
    "layers_indivisible_by_pp",     # Reason.LAYERS_INDIVISIBLE
})


def pipe_violations(rec):
    """Reference-free violation strings from one record's "pipe" block
    (docs/PIPELINE.md): the engaged schedule's measured-cost bubble
    fraction must stay within the plain-1F1B budget
    (pp−1)/(n_micro+pp−1) — a fraction past it means the schedule
    arithmetic or the per-phase cost split regressed, not noise (5%
    relative + 0.02 absolute headroom for timing jitter). A pp-live
    mesh whose composition never engaged also fails — the line would
    silently measure the GSPMD fallback while claiming a pipeline —
    unless the recorded decline reason is one of the documented
    config-shape fallbacks (:data:`PIPE_CONFIG_DECLINES`) or an
    explicit escape-hatch knob."""
    block = rec.get("pipe") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or "bubble_fraction" not in block:
        return []
    out = []
    frac = block.get("bubble_fraction")
    budget = block.get("bubble_budget_1f1b")
    if frac is not None and budget is not None:
        if float(frac) > float(budget) * 1.05 + 0.02:
            out.append(
                f"pipeline bubble fraction {float(frac):.3f} over the "
                f"1F1B budget {float(budget):.3f} "
                f"(schedule={block.get('schedule')}, "
                f"pp={block.get('pp')}, n_micro={block.get('n_micro')})")
    if (block.get("pp_axis_live") and not block.get("engaged")
            and not block.get("disabled_by_knob")
            and block.get("decline_reason") not in PIPE_CONFIG_DECLINES):
        # an explicit escape-hatch knob (disabled_by_knob) is an
        # intended A/B baseline, and a config-shape decline is a
        # documented fallback — only a silent decline fails
        out.append("pp axis live but the composed pipeline never "
                   "engaged — the line measured the GSPMD fallback "
                   f"(decline_reason={block.get('decline_reason')!r}; "
                   "see the plan_engagement telemetry)")
    if (block.get("schedule") == "zb"
            and block.get("zb_beats_1f1b") is False):
        out.append("zero-bubble schedule engaged but its measured-cost "
                   "bubble fraction does not beat plain 1F1B")
    return out


def layout_violations(rec):
    """Reference-free violation strings from one record's "layout" block
    (docs/AUTOTUNE.md): an --autotune round must not ship a layout whose
    PREDICTED score loses to the hand-picked baseline's predicted score
    at equal chips — the baseline is searched through the same cost
    model, so by construction the winner can only lose to it when the
    search silently misranked or fell back. A fallback (no searched
    candidate fit) is a legitimate outcome ONLY when it carries its
    structured reason; a silent one would measure the hand config while
    claiming a search."""
    block = rec.get("layout") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or not block.get("label"):
        return []  # {"enabled": false} or absent: not an autotuned line
    out = []
    base = block.get("baseline")
    score = block.get("predicted_score")
    if (isinstance(base, dict) and base.get("fits")
            and score is not None
            and base.get("predicted_tokens_per_sec") is not None
            and float(score)
            < float(base["predicted_tokens_per_sec"]) * (1 - 1e-9)):
        out.append(
            f"autotuned layout {block.get('label')!r} predicted "
            f"{float(score):.1f} tokens/sec loses to the hand-picked "
            f"baseline {base.get('label')!r} at "
            f"{float(base['predicted_tokens_per_sec']):.1f} on the same "
            f"{block.get('device_count')} chips — the searched winner "
            "must beat (or be) every scored candidate")
    if block.get("source") == "fallback" and not block.get(
            "fallback_reason"):
        out.append(
            "layout search fell back to the hand-picked config without "
            "a structured fallback_reason — silent fallbacks would "
            "measure the baseline while claiming a search")
    return out


#: quant decline reasons that describe a DOCUMENTED fallback
#: (docs/QUANT.md): the parity gate / CPU default-off (loud, warned), or
#: a precedence rule ceding the GEMM to an owner kernel/region. A
#: requested run declining for any other (or no) recorded reason fails —
#: the line would silently measure wide GEMMs while claiming quant.
QUANT_CONFIG_DECLINES = frozenset({
    "quant_parity_gate",        # gate red / CPU default-off
    "tp_seam_owns_gemm",        # fused tp seams own the projections
    "fused_kernel_owns_gemm",   # swiglu_down megakernel owns wd
    "pipeline_stage_fn",        # pipeline stage fns: no amax threading
    "composed_region",          # manual composed region owns the math
})


def quant_violations(rec):
    """Reference-free violation strings from one record's "quant" block
    (docs/QUANT.md): the numeric parity-gate report must be green (a red
    gate that still ENGAGED means someone forced past drifted numerics),
    the embedded exact-vs-scaled loss-drift A/B must stay inside its
    0.5% budget, and a requested mode that never engaged must carry a
    documented decline reason — the int8-head gate discipline applied to
    the scaled-GEMM compute mode."""
    block = rec.get("quant") if isinstance(rec, dict) else None
    if not isinstance(block, dict):
        return []
    out = []
    gate = block.get("gate")
    if isinstance(gate, dict) and gate.get("ok") is False:
        out.append(
            "quant parity gate red (loss_rel_err="
            f"{gate.get('loss_rel_err')}, tol={gate.get('tol')}, "
            f"grad_rel_err={gate.get('grad_rel_err')}, "
            f"grad_tol={gate.get('grad_tol')})"
            + (" yet the run ENGAGED scaled GEMMs — forced past a "
               "failing probe" if block.get("engaged") else ""))
    drift = block.get("loss_drift_rel")
    budget = block.get("loss_drift_budget")
    if drift is not None and budget is not None \
            and float(drift) > float(budget):
        out.append(
            f"quant loss drift {float(drift):.4f} > budget "
            f"{float(budget):.4f} vs the embedded exact A/B "
            "(quant.loss_drift_probe)")
    if (block.get("requested") and not block.get("engaged")
            and block.get("reason") not in QUANT_CONFIG_DECLINES):
        out.append(
            "quant compute requested but never engaged "
            f"(decline_reason={block.get('reason')!r}; see the "
            "plan_engagement telemetry)")
    return out


def serving_violations(rec):
    """Reference-free violation strings from one record's "serving"
    block (docs/SERVING.md; emitted by tools/serve_bench.py and
    ``bench.py --serve``): the p99-TTFT bound and the goodput-scaling
    target gate only when the block carries their bound — the soak run
    embeds what it was asked to guarantee, like the comms parity block
    embeds its threshold. A soak that lost requests (completed +
    cancelled < submitted) also fails: silent drops are not goodput."""
    block = rec.get("serving") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or not block.get("enabled"):
        return []
    out = []
    p99 = (block.get("ttft") or {}).get("p99")
    if p99 is None:
        p99 = block.get("p99_ttft_seconds")
    budget = block.get("p99_ttft_budget")
    if p99 is not None and budget is not None and float(p99) > float(budget):
        out.append(f"p99 TTFT {float(p99):.4f}s > budget "
                   f"{float(budget):.4f}s")
    x = block.get("goodput_x_single")
    target = block.get("scaling_target")
    if x is not None and target is not None and float(x) < float(target):
        out.append(
            f"goodput scaling {float(x):.2f}x single < target "
            f"{float(target):.2f}x at {block.get('replicas')} replicas")
    reqs = block.get("requests")
    done = block.get("completed")
    cancelled = block.get("cancelled") or 0
    shed = block.get("shed") or 0
    rejected = block.get("rejected") or 0
    if reqs is not None and done is not None and (
            int(done) + int(cancelled) + int(shed) + int(rejected)
            < int(reqs)):
        out.append(f"soak lost requests: {done} completed + {cancelled} "
                   f"cancelled + {shed} shed + {rejected} rejected "
                   f"< {reqs} submitted")
    return out


def slo_violations(rec):
    """Reference-free SLO gate over a CLEAN soak's embedded ``"slo"``
    block (the "serving" block only — an "overload" block runs past
    capacity by design and its alerts are the scenario working): a
    clean soak whose live SLO engine fired any fast-burn alert fails
    the round, same discipline as the guard/OVERLOAD gates. An alert
    still ACTIVE at the end of the run (it never cleared during the
    cool-down) fails at any severity — the condition outlived its
    cause."""
    block = rec.get("serving") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or not block.get("enabled"):
        return []
    slo = block.get("slo")
    if not isinstance(slo, dict) or not slo.get("enabled"):
        return []
    out = []
    fast = int(slo.get("fast_burn_alerts") or 0)
    if fast > 0:
        names = sorted({e.get("objective") for e in slo.get("events") or []
                        if e.get("severity") == "fast_burn"
                        and e.get("event") == "fire"})
        out.append(f"{fast} fast-burn SLO alert(s) fired during a clean "
                   f"soak ({', '.join(n for n in names if n) or '?'})")
    active = slo.get("active") or []
    if active:
        out.append("SLO alert(s) still active at soak end: "
                   + ", ".join(str(a) for a in active))
    return out


def overload_violations(rec):
    """Reference-free violation strings from one record's "overload"
    block (docs/SERVING.md "Overload & degradation"; emitted by
    ``tools/serve_bench.py --overload``). The block embeds every budget
    it was asked to guarantee, like the serving/comms blocks:

    - ``conserved`` false = a submitted request reached no terminal
      outcome (served | cancelled | shed | rejected) — a lost or hung
      request, the hard floor;
    - p99 TTFT of ADMITTED requests over ``p99_ttft_budget`` — admission
      control exists precisely so admitted requests keep their SLO
      under 2x-capacity pressure;
    - ``shed_fraction`` over ``shed_ceiling`` — refusing a bounded
      slice of overload traffic is the design, refusing most of it is a
      regression;
    - ``breaker_opens`` over ``breaker_flap_bound`` — a flapping
      replica must cost a bounded number of breaker flaps;
    - a brownout ladder that did not restore (``restored`` false) —
      degradation must be reversible once pressure clears."""
    block = rec.get("overload") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or not block.get("enabled"):
        return []
    out = []
    if block.get("conserved") is False:
        n = (int(block.get("submitted") or 0)
             - int(block.get("served") or 0)
             - int(block.get("cancelled") or 0)
             - int(block.get("shed") or 0)
             - int(block.get("rejected") or 0))
        out.append(f"outcome conservation broken: {n} of "
                   f"{block.get('submitted')} requests reached no "
                   "terminal outcome (lost or hung)")
    p99 = block.get("p99_ttft_seconds")
    budget = block.get("p99_ttft_budget")
    if p99 is not None and budget is not None and float(p99) > float(budget):
        out.append(f"admitted p99 TTFT {float(p99):.4f}s > budget "
                   f"{float(budget):.4f}s under overload")
    frac = block.get("shed_fraction")
    ceil = block.get("shed_ceiling")
    if frac is not None and ceil is not None and float(frac) > float(ceil):
        out.append(f"shed+rejected fraction {float(frac):.2f} > ceiling "
                   f"{float(ceil):.2f}")
    opens = block.get("breaker_opens")
    bound = block.get("breaker_flap_bound")
    if opens is not None and bound is not None and int(opens) > int(bound):
        out.append(f"breaker flap count {int(opens)} > bound "
                   f"{int(bound)}")
    brown = block.get("brownout") or {}
    if brown and brown.get("restored") is False:
        out.append(f"brownout ladder not restored after the run "
                   f"(level still {brown.get('level')})")
    return out


def upgrade_violations(rec):
    """Reference-free violation strings from one record's "upgrade"
    block (docs/SERVING.md "Process topology"; emitted by
    ``tools/serve_bench.py --procs N``): the multi-process fleet soak
    with a SIGKILLed replica, chaos-injected link faults, and a rolling
    weight upgrade mid-traffic. The invariants are absolute:

    - ``conserved`` false / ``lost_requests`` > 0 — a request lost or
      hung across kills, migrations, and reloads is the hard floor;
    - ``duplicate_stream_tokens`` / ``lost_stream_tokens`` > 0 — every
      generated token must reach its stream callback exactly once,
      counted at an independent seam from the router's suppression;
    - an upgrade that never completed (``upgrade.complete`` false) —
      the rollout must finish while the fleet keeps serving;
    - inside the upgrade *window* (both gates engage only when their
      budget is embedded in the block): goodput fraction under
      ``goodput_floor_fraction`` while work was actually outstanding,
      or worst recent-p99 TTFT over ``p99_ttft_budget``."""
    block = rec.get("upgrade") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or not block.get("enabled"):
        return []
    out = []
    if block.get("conserved") is False:
        out.append(f"outcome conservation broken across the fleet "
                   f"scenario ({block.get('submitted')} submitted, "
                   f"{block.get('served')} served)")
    lost = int(block.get("lost_requests") or 0)
    if lost > 0:
        out.append(f"{lost} request(s) lost (no terminal outcome) "
                   "through kill/migration/upgrade")
    dup = int(block.get("duplicate_stream_tokens") or 0)
    if dup > 0:
        out.append(f"{dup} stream token(s) delivered more than once "
                   "(exactly-once replay broken)")
    missing = int(block.get("lost_stream_tokens") or 0)
    if missing > 0:
        out.append(f"{missing} generated token(s) never delivered to "
                   "their stream callback")
    up = block.get("upgrade") or {}
    if up and not up.get("complete"):
        out.append(f"rolling upgrade to version {up.get('version')} "
                   f"did not complete (stalled after "
                   f"{up.get('upgraded_replicas')})")
    win = block.get("window") or {}
    frac = win.get("goodput_fraction")
    floor = win.get("goodput_floor_fraction")
    if (frac is not None and floor is not None
            and int(win.get("peak_outstanding") or 0) > 0
            and float(frac) < float(floor)):
        out.append(f"goodput inside the upgrade window fell to "
                   f"{float(frac):.3f}x of the whole run "
                   f"(< floor {float(floor):.3f}x) with "
                   f"{win.get('peak_outstanding')} requests outstanding")
    p99 = win.get("p99_ttft_seconds")
    budget = win.get("p99_ttft_budget")
    if p99 is not None and budget is not None \
            and float(p99) > float(budget):
        out.append(f"p99 TTFT {float(p99):.4f}s inside the upgrade "
                   f"window > budget {float(budget):.4f}s")
    return out


def partition_violations(rec):
    """Reference-free violation strings from one record's "partition"
    block (docs/SERVING.md "Cross-host topology"; emitted by
    ``tools/serve_bench.py --hosts N``): the cross-host fleet soak with
    a whole host partitioned away mid-traffic, its replicas fenced and
    their work replayed, then the partition healed. The invariants are
    absolute:

    - ``conserved`` false / ``lost_requests`` > 0 — a severed host must
      not lose or hang a single request;
    - ``duplicate_stream_tokens`` > 0 — the fencing epochs guarantee no
      rid is ever served by two replicas, so the independent callback
      seam must count zero duplicate deliveries (a duplicate here means
      a stale lease's tokens leaked past the fence);
    - ``lost_stream_tokens`` > 0 — exactly-once is not at-most-once;
    - ``fleet_live_at_drain`` false — replay + respawn must reconverge
      the fleet to target size;
    - ``partition.healed`` false with a surviving agent — a healed
      network must return the host to service (with ``agent_killed``
      the host legitimately stays severed and is not gated);
    - an overlapped rolling upgrade that never completed."""
    block = rec.get("partition") if isinstance(rec, dict) else None
    if not isinstance(block, dict) or not block.get("enabled"):
        return []
    out = []
    if block.get("conserved") is False:
        out.append(f"outcome conservation broken across the host "
                   f"partition ({block.get('submitted')} submitted, "
                   f"{block.get('served')} served)")
    lost = int(block.get("lost_requests") or 0)
    if lost > 0:
        out.append(f"{lost} request(s) lost (no terminal outcome) "
                   "through the host partition")
    dup = int(block.get("duplicate_stream_tokens") or 0)
    if dup > 0:
        out.append(f"{dup} stream token(s) delivered more than once "
                   "(a stale lease leaked past the fencing epoch)")
    missing = int(block.get("lost_stream_tokens") or 0)
    if missing > 0:
        out.append(f"{missing} generated token(s) never delivered to "
                   "their stream callback")
    if block.get("fleet_live_at_drain") is False:
        out.append("fleet below target size after the run settled "
                   "(replay/respawn did not reconverge)")
    part = block.get("partition") or {}
    if part.get("healed") is False and not part.get("agent_killed"):
        out.append(f"host {part.get('host')} never returned to service "
                   "after the partition healed")
    up = block.get("upgrade") or {}
    if up and not up.get("complete"):
        out.append(f"rolling upgrade to version {up.get('version')} "
                   "overlapping the partition did not complete")
    return out


def cold_start_violations(rec, ref_rec, threshold=0.25):
    """Referenced gate on the serving block's replica cold start
    (engine construction + program compile, ``warmup()``): must not
    regress more than ``threshold`` vs the reference round at the SAME
    scan-over-layers mode — the depth-flat serving compile guarantee
    (docs/SERVING.md). Sub-second references are noise and skipped."""
    new_b = rec.get("serving") if isinstance(rec, dict) else None
    old_b = ref_rec.get("serving") if isinstance(ref_rec, dict) else None
    if not isinstance(new_b, dict) or not isinstance(old_b, dict):
        return []
    if bool(new_b.get("scan_layers")) != bool(old_b.get("scan_layers")):
        return []
    try:
        old = float(old_b.get("cold_start_seconds"))
        new = float(new_b.get("cold_start_seconds"))
    except (TypeError, ValueError):
        return []
    if old < 1.0:
        return []
    out = []
    if new > old * (1.0 + threshold):
        out.append(
            f"replica cold start {new:.1f}s > {1.0 + threshold:.2f}x "
            f"reference {old:.1f}s "
            f"(scan_layers={bool(new_b.get('scan_layers'))})")
    return out


def mfu_violations(rec, ref_rec, threshold):
    """Violation strings comparing one metric's ``mfu`` field against the
    reference round's (docs/ZERO.md satellite: the stage-3 config-5 line
    is gated on MFU, not only tokens/sec — a sharding regression that
    trades tokens/sec for a quietly shrunken effective batch shows up
    here). Gated for every metric that carries mfu on both sides."""
    new = rec.get("mfu") if isinstance(rec, dict) else None
    old = ref_rec.get("mfu") if isinstance(ref_rec, dict) else None
    try:
        new, old = float(new), float(old)
    except (TypeError, ValueError):
        return []
    if old <= 0:
        return []
    out = []
    if new < old * (1.0 - threshold):
        out.append(f"mfu {new} < {1.0 - threshold:.2f}x reference {old} "
                   f"({(new / old - 1) * 100:+.1f}%)")
    return out


def compile_violations(rec, ref_rec, threshold=0.25):
    """Violation strings comparing one metric's "compile" block against
    the reference round's (docs/SCAN.md): total build wall time
    (trace + lower + compile) must not regress more than ``threshold``
    at the SAME depth and scan mode — the scan-over-layers flat-compile
    guarantee, gated instead of eyeballed. Blocks are only comparable
    when depth/mode match (a depth change legitimately changes compile
    cost); sub-second references are noise-dominated and skipped."""
    new_c = rec.get("compile") if isinstance(rec, dict) else None
    old_c = ref_rec.get("compile") if isinstance(ref_rec, dict) else None
    if not isinstance(new_c, dict) or not isinstance(old_c, dict):
        return []
    if new_c.get("num_layers") != old_c.get("num_layers"):
        return []
    if bool(new_c.get("scan_layers")) != bool(old_c.get("scan_layers")):
        return []

    def total(c):
        return sum(float(c.get(k) or 0.0)
                   for k in ("trace_seconds", "lower_seconds",
                             "compile_seconds"))

    old_t, new_t = total(old_c), total(new_c)
    if old_t < 1.0:
        return []
    out = []
    if new_t > old_t * (1.0 + threshold):
        out.append(
            f"compile time {new_t:.1f}s > {1.0 + threshold:.2f}x reference "
            f"{old_t:.1f}s at depth {new_c.get('num_layers')} "
            f"(scan_layers={bool(new_c.get('scan_layers'))})")
    return out


def compare(new_metrics, ref_metrics, threshold):
    """-> (rows, regressions). Each row: (metric, old, new, ratio|None)."""
    rows, regressions = [], []
    for metric, rec in sorted(new_metrics.items()):
        ref = ref_metrics.get(metric)
        if ref is None:
            rows.append((metric, None, float(rec["value"]), None))
            continue
        old, new = float(ref["value"]), float(rec["value"])
        ratio = new / old if old else float("inf")
        rows.append((metric, old, new, ratio))
        if old > 0 and ratio < 1.0 - threshold:
            regressions.append((metric, old, new, ratio))
    for metric in sorted(set(ref_metrics) - set(new_metrics)):
        rows.append((metric, float(ref_metrics[metric]["value"]), None,
                     None))
    return rows, regressions


def _fmt(metric, old, new, ratio, rec):
    mfu = rec.get("mfu") if rec else None
    mfu_s = f"  mfu={mfu}" if mfu is not None else ""
    if old is None:
        return f"  NEW   {metric}: {new}{mfu_s} (no reference — not gated)"
    if new is None:
        return f"  GONE  {metric}: was {old} (missing from candidate)"
    arrow = f"{old} -> {new} ({(ratio - 1) * 100:+.1f}%)"
    return f"  {'OK  ' if ratio >= 1.0 else 'DOWN'}  {metric}: {arrow}{mfu_s}"


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="exit non-zero on a >threshold tokens/sec regression "
                    "between bench JSON artifacts (docs/PERF.md)")
    ap.add_argument("candidate", nargs="?", default=None,
                    help="bench JSON to gate (default: newest BENCH_r*.json)")
    ap.add_argument("--against", nargs="+", default=None,
                    help="reference artifacts (default: the previous "
                    "BENCH_r*.json round)")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="allowed fractional drop (default 0.05)")
    ap.add_argument("--compile-threshold", type=float, default=0.25,
                    help="allowed fractional compile-time increase at "
                    "the same depth/scan mode (default 0.25; docs/SCAN.md)")
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="repo root for BENCH_r*.json discovery")
    args = ap.parse_args(argv)

    candidate = args.candidate
    refs = args.against
    if candidate is None or refs is None:
        rounds = _round_files(args.root)
        if candidate is None:
            if not rounds:
                print("bench_gate: no BENCH_r*.json rounds found", flush=True)
                return 2
            candidate = rounds[-1]
            rounds = rounds[:-1]
        else:
            rounds = [r for r in rounds
                      if os.path.abspath(r) != os.path.abspath(candidate)]
        if refs is None:
            if not rounds:
                print(f"bench_gate: {candidate}: no earlier round to gate "
                      "against — tokens/sec not gated", flush=True)
                refs = []  # the resilience gate below still applies
            else:
                refs = [rounds[-1]]
                # metric continuity: a gap round recorded on different
                # hardware (e.g. a CPU-only container, BENCH_r06) lacks
                # the tracked metrics — walking back to the NEWEST
                # earlier round carrying each candidate metric keeps
                # the next real round gated instead of every tracked
                # metric reporting "NEW (not gated)" across the gap
                covered = set(load_metrics(rounds[-1]))
                want = set(load_metrics(candidate))
                for r in reversed(rounds[:-1]):
                    missing = want - covered
                    if not missing:
                        break
                    have = set(load_metrics(r))
                    if have & missing:
                        refs.append(r)
                        covered |= have

    new_metrics = load_metrics(candidate)
    if not new_metrics:
        print(f"bench_gate: no metric lines in {candidate}", flush=True)
        return 2

    failed = False
    # resilience gate: independent of any reference round — a clean bench
    # run reporting guard anomalies or rollbacks fails outright
    for metric, rec in sorted(new_metrics.items()):
        for v in resilience_violations(rec):
            print(f"  GUARD {metric}: {v} — clean bench runs must report "
                  "zero anomalies/rollbacks", flush=True)
            failed = True
        # comms gate: also reference-free — parity is a property of the
        # candidate run alone
        for v in comms_violations(rec):
            print(f"  COMMS {metric}: {v}", flush=True)
            failed = True
        # ring gate (docs/ATTENTION.md): the *_seq32k long-context
        # lines embed a ring-vs-dense parity probe — reference-free
        for v in ring_violations(rec):
            print(f"  RING  {metric}: {v}", flush=True)
            failed = True
        # quant gate (docs/QUANT.md): parity-gate report + embedded
        # loss-drift A/B + no silent request-without-engagement
        for v in quant_violations(rec):
            print(f"  QUANT {metric}: {v}", flush=True)
            failed = True
        # serving gate (reference-free): p99-TTFT bound + goodput
        # scaling target + no lost requests (docs/SERVING.md)
        for v in serving_violations(rec):
            print(f"  SERVE {metric}: {v}", flush=True)
            failed = True
        # SLO gate (reference-free): a clean soak's embedded slo block
        # reporting any fast-burn alert fails the round
        # (docs/TELEMETRY.md "Time series, SLOs...")
        for v in slo_violations(rec):
            print(f"  SLO   {metric}: {v}", flush=True)
            failed = True
        # overload gate (reference-free): outcome conservation at 2x
        # capacity, admitted-p99 budget, shed ceiling, breaker flap
        # bound, brownout restoration (docs/SERVING.md)
        for v in overload_violations(rec):
            print(f"  OVERLOAD {metric}: {v}", flush=True)
            failed = True
        # upgrade gate (reference-free): zero lost / duplicated requests
        # and tokens through SIGKILL + chaos + rolling weight upgrade,
        # plus embedded window budgets (docs/SERVING.md)
        for v in upgrade_violations(rec):
            print(f"  UPGRADE {metric}: {v}", flush=True)
            failed = True
        # partition gate (reference-free): zero lost / duplicated
        # requests and tokens through a whole-host partition — fencing
        # epochs, fleet-wide replay, heal + adoption (docs/SERVING.md)
        for v in partition_violations(rec):
            print(f"  PARTITION {metric}: {v}", flush=True)
            failed = True
        # pipeline gate (docs/PIPELINE.md): measured-cost bubble over
        # budget, or a pp-live mesh whose composition never engaged
        for v in pipe_violations(rec):
            print(f"  PIPE  {metric}: {v}", flush=True)
            failed = True
        # layout gate (docs/AUTOTUNE.md): the autotuned winner's
        # predicted score must not lose to the hand-picked baseline,
        # and a fallback must carry its structured reason
        for v in layout_violations(rec):
            print(f"  LAYOUT {metric}: {v}", flush=True)
            failed = True
    for ref_path in refs:
        ref_metrics = load_metrics(ref_path)
        print(f"bench_gate: {os.path.basename(candidate)} vs "
              f"{os.path.basename(ref_path)} "
              f"(threshold {args.threshold:.0%})")
        if not ref_metrics:
            print("  (no metric lines — reference skipped; BASELINE.json "
                  "publishes no absolute numbers)")
            continue
        rows, regressions = compare(new_metrics, ref_metrics,
                                    args.threshold)
        for metric, old, new, ratio in rows:
            print(_fmt(metric, old, new, ratio, new_metrics.get(metric)))
        for metric, old, new, ratio in regressions:
            print(f"  REGRESSION {metric}: {old} -> {new} "
                  f"({(ratio - 1) * 100:+.1f}% < -{args.threshold:.0%})")
            failed = True
        # compile gate (docs/SCAN.md): same-depth build time must not
        # regress past --compile-threshold vs this reference round
        for metric, rec in sorted(new_metrics.items()):
            for v in compile_violations(rec, ref_metrics.get(metric),
                                        args.compile_threshold):
                print(f"  COMPILE {metric}: {v}", flush=True)
                failed = True
            # mfu gate (docs/ZERO.md): hardware-normalised throughput
            # must hold alongside raw tokens/sec
            for v in mfu_violations(rec, ref_metrics.get(metric),
                                    args.threshold):
                print(f"  MFU {metric}: {v}", flush=True)
                failed = True
            # serving cold-start gate (docs/SERVING.md): replica
            # spin-up compile must stay depth-flat round over round
            for v in cold_start_violations(rec, ref_metrics.get(metric),
                                           args.compile_threshold):
                print(f"  COLD  {metric}: {v}", flush=True)
                failed = True
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
