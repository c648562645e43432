#!/usr/bin/env python
"""Pretty-print a telemetry snapshot, or diff two bench telemetry blocks.

Usage:
    python tools/telemetry_report.py RUN.json
    python tools/telemetry_report.py OLD.json NEW.json [--top N]

Accepts either a raw ``paddle_tpu.telemetry.snapshot()`` dict or a bench
JSON record carrying the snapshot under its ``"telemetry"`` key
(BENCH_r*.json rounds). The diff mode ranks the top-N regressed metrics —
histogram series by mean-time increase, counters by relative growth — so
"why is this round slower" starts from data instead of a re-profile.
"""
from __future__ import annotations

import argparse
import json
import sys


def _is_snapshot(d):
    return isinstance(d, dict) and any(
        k in d for k in ("counters", "gauges", "histograms"))


def _scan_lines(text):
    """LAST JSON-object line carrying telemetry (bench stdout prints log
    lines and, on TPU, TWO metric lines — the headline one is last)."""
    best = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(d, dict) and ("telemetry" in d or _is_snapshot(d)):
            best = d
    return best


def _extract(data):
    """Pull the snapshot out of any of the shapes we meet in the wild:
    a raw snapshot, a bench JSON line ({"metric", ..., "telemetry"}), or
    a BENCH_r*.json round record ({"n", "cmd", "tail", "parsed"})."""
    if not isinstance(data, dict):
        return None
    if _is_snapshot(data):
        return data
    if _is_snapshot(data.get("telemetry")):
        return data["telemetry"]
    parsed = data.get("parsed")
    if isinstance(parsed, dict) and _is_snapshot(parsed.get("telemetry")):
        return parsed["telemetry"]
    tail = data.get("tail")
    if isinstance(tail, str):
        return _extract(_scan_lines(tail))
    return None


def load_snapshot(path):
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except json.JSONDecodeError:
        # stdout capture: log lines + one JSON record per bench model
        data = _scan_lines(text)
        if data is None:
            raise ValueError(f"{path}: no JSON object found")
    snap = _extract(data)
    if snap is None:
        raise ValueError(
            f"{path}: no telemetry snapshot found (expected 'counters'/"
            "'gauges'/'histograms' keys, a bench JSON line with a "
            "'telemetry' block, or a BENCH_r*.json round record)")
    return snap


def _hist_line(name, labels, h):
    lbl = f"{{{labels}}}" if labels else ""
    return (f"  {name}{lbl}: n={h['count']} mean={h['mean']:.6f}s "
            f"p50={h['p50']:.6f} p95={h['p95']:.6f} p99={h['p99']:.6f} "
            f"max={h['max']:.6f}")


def _comms_rows(snap):
    """Aggregate the collective_* families into per-(op, axis) rows with
    the exact-vs-int8 traffic split (docs/COMMS.md). Standalone
    reimplementation of collectives.comms_summary so this tool keeps
    working on a bare snapshot file without importing paddle_tpu."""
    counters = snap.get("counters") or {}
    hists = snap.get("histograms") or {}

    def _parse(labels):
        d = dict(p.split("=", 1) for p in labels.split(",") if "=" in p)
        return f"{d.get('op', '?')}@{d.get('axis', '?')}"

    rows = {}
    for name, field in (("collective_bytes_total", "bytes"),
                        ("collective_calls_total", "calls"),
                        ("collective_quantized_bytes_total", "q8_bytes")):
        for labels, v in (counters.get(name) or {}).items():
            key = _parse(labels)
            rows.setdefault(key, {})[field] = (
                rows.get(key, {}).get(field, 0) + int(v))
    for labels, h in (hists.get("collective_seconds") or {}).items():
        rows.setdefault(_parse(labels), {})["seconds"] = float(
            h.get("sum", 0.0))
    return rows


def print_comms(snap, out=None):
    rows = _comms_rows(snap)
    if not rows:
        return
    w = (out or sys.stdout).write
    w("-- comms (exact vs int8 traffic split) --\n")
    total = sum(r.get("bytes", 0) for r in rows.values())
    qtotal = sum(r.get("q8_bytes", 0) for r in rows.values())
    for key in sorted(rows):
        r = rows[key]
        secs = (f" seconds={r['seconds']:.4f}" if "seconds" in r else "")
        q8 = (f" q8_bytes={r['q8_bytes']}" if r.get("q8_bytes") else "")
        w(f"  {key}: calls={r.get('calls', 0)} bytes={r.get('bytes', 0)}"
          f"{q8}{secs}\n")
    if total:
        w(f"  TOTAL: bytes={total} quantized={qtotal} "
          f"({qtotal / total:.1%} int8, exact={total - qtotal})\n")


def print_zero(snap, out=None):
    """ZeRO traffic section (docs/ZERO.md): gathered-param bytes and
    reduce-scattered grad bytes by (axis, int8-vs-exact)."""
    counters = snap.get("counters") or {}
    rows = []
    for name, label in (("zero3_param_gather_bytes_total", "param_gather"),
                        ("zero3_grad_rs_bytes_total", "grad_rs")):
        for labels, v in sorted((counters.get(name) or {}).items()):
            d = dict(p.split("=", 1) for p in labels.split(",") if "=" in p)
            wire = "int8" if d.get("quantized") == "1" else "exact"
            rows.append(f"  {label}@{d.get('axis', '?')} [{wire}]: "
                        f"bytes={int(v)}")
    if not rows:
        return
    w = (out or sys.stdout).write
    w("-- zero (sharded-state traffic) --\n")
    for r in rows:
        w(r + "\n")


def print_ring(snap, out=None):
    """Ring-attention traffic section (docs/ATTENTION.md): KV block
    bytes rotated around the sep ring per phase (fwd = k+v hops, bwd =
    k+v plus the traveling dk/dv accumulators)."""
    counters = snap.get("counters") or {}
    series = counters.get("ring_attn_kv_bytes_total") or {}
    if not series:
        return
    w = (out or sys.stdout).write
    w("-- ring (sep kv rotation traffic) --\n")
    for labels, v in sorted(series.items()):
        d = dict(p.split("=", 1) for p in labels.split(",") if "=" in p)
        w(f"  ppermute@{d.get('axis', '?')} [{d.get('phase', '?')}]: "
          f"bytes={int(v)}\n")


def print_plans(snap, out=None):
    """Plan-engagement section (docs/COMMS.md lattice): one row per
    (plan, verdict, reason) resolution at step build — a hybrid config
    whose quantized/zero/ring machinery silently declined shows up here
    with its structured reason instead of just running slower."""
    counters = snap.get("counters") or {}
    series = counters.get("plan_engagement_total") or {}
    if not series:
        return
    w = (out or sys.stdout).write
    w("-- plans (engagement verdicts at step build) --\n")
    for labels, v in sorted(series.items()):
        d = dict(p.split("=", 1) for p in labels.split(",") if "=" in p)
        w(f"  {d.get('plan', '?')}: {d.get('verdict', '?')} "
          f"[{d.get('reason', '?')}] x{int(v)}\n")


def print_quant(snap, out=None):
    """Low-precision compute section (docs/QUANT.md): the per-site GEMM
    dtype mode (0=wide, 1=int8, 2=fp8) recorded at trace time, the
    cumulative narrow-GEMM forward FLOPs by dtype, and the serving
    resident-weight footprint by storage dtype."""
    counters = snap.get("counters") or {}
    gauges = snap.get("gauges") or {}
    mode = gauges.get("gemm_dtype_mode") or {}
    flops = counters.get("quant_gemm_flops_total") or {}
    wbytes = gauges.get("serving_weight_bytes") or {}
    if not (mode or flops or wbytes):
        return
    w = (out or sys.stdout).write
    w("-- quant (scaled-GEMM compute) --\n")
    names = {0.0: "wide", 1.0: "int8", 2.0: "fp8"}

    def _d(labels):
        return dict(p.split("=", 1) for p in labels.split(",") if "=" in p)

    for labels, v in sorted(mode.items()):
        d = _d(labels)
        w(f"  gemm[{d.get('site', '?')}]@{d.get('path', '?')}: "
          f"{names.get(float(v), v)}\n")
    for labels, v in sorted(flops.items()):
        d = _d(labels)
        w(f"  narrow_flops[{d.get('dtype', '?')}]: {int(v)}\n")
    for labels, v in sorted(wbytes.items()):
        d = _d(labels)
        w(f"  serving_weight_bytes[{d.get('dtype', '?')}]: {int(v)}\n")


def print_overload(snap, out=None):
    """Overload section (docs/SERVING.md "Overload & degradation"):
    admission rejects by reason/priority, shed counts by reason, breaker
    states/transitions per replica, and the brownout ladder level."""
    counters = snap.get("counters") or {}
    gauges = snap.get("gauges") or {}
    rows = []

    def _d(labels):
        return dict(p.split("=", 1) for p in labels.split(",")
                    if "=" in p)

    for labels, v in sorted((counters.get(
            "serving_admission_rejects_total") or {}).items()):
        d = _d(labels)
        rows.append(f"  reject[{d.get('reason', '?')}] "
                    f"({d.get('priority', '?')}): {int(v)}")
    for labels, v in sorted((counters.get("serving_shed_total")
                             or {}).items()):
        rows.append(f"  shed[{_d(labels).get('reason', '?')}]: {int(v)}")
    for labels, v in sorted((counters.get(
            "serving_breaker_transitions_total") or {}).items()):
        d = _d(labels)
        rows.append(f"  breaker r{d.get('replica', '?')} -> "
                    f"{d.get('to', '?')}: x{int(v)}")
    state_names = {0: "closed", 1: "half_open", 2: "open"}
    for labels, v in sorted((gauges.get("serving_breaker_state")
                             or {}).items()):
        d = _d(labels)
        rows.append(f"  breaker r{d.get('replica', '?')} state: "
                    f"{state_names.get(int(float(v)), v)}")
    for labels, v in sorted((counters.get(
            "serving_brownout_transitions_total") or {}).items()):
        rows.append(f"  brownout step {_d(labels).get('direction', '?')}:"
                    f" x{int(v)}")
    lvl = (gauges.get("serving_brownout_level") or {}).get("")
    if lvl is not None:
        rows.append(f"  brownout level: {int(float(lvl))}")
    if not rows:
        return
    w = (out or sys.stdout).write
    w("-- overload (admission / shedding / breakers / brownout) --\n")
    for r in rows:
        w(r + "\n")


def print_layout(snap, out=None):
    """Layout-autotuner section (docs/AUTOTUNE.md): one row per
    (verdict, reason) over the candidate lattice — ``pruned`` rows never
    paid a lowering (the compose probe declined their mesh shell),
    ``lowered`` rows were AOT-compiled and priced, ``error`` rows failed
    to lower — plus the wall seconds the search spent."""
    counters = snap.get("counters") or {}
    gauges = snap.get("gauges") or {}
    series = counters.get("autotune_candidates_total") or {}
    secs = (gauges.get("autotune_search_seconds") or {}).get("")
    if not series and secs is None:
        return
    w = (out or sys.stdout).write
    w("-- layout (autotune candidate verdicts) --\n")
    for labels, v in sorted(series.items()):
        d = dict(p.split("=", 1) for p in labels.split(",") if "=" in p)
        w(f"  {d.get('verdict', '?')} [{d.get('reason', '?')}]: "
          f"x{int(v)}\n")
    if secs is not None:
        w(f"  search_seconds: {float(secs):.3f}\n")


def print_trace(snap, out=None):
    """Span-tracer section (docs/TELEMETRY.md Tracing): the
    ``trace_span_seconds`` histogram family mirrors every completed
    span's wall time by name while both the tracer and the registry are
    enabled — this is the aggregate view; the timeline lives in the
    trace files (tools/trace_report.py)."""
    series = (snap.get("histograms") or {}).get("trace_span_seconds") or {}
    if not series:
        return
    w = (out or sys.stdout).write
    w("-- trace (span wall seconds by name) --\n")

    def _span_name(labels):
        d = dict(p.split("=", 1) for p in labels.split(",") if "=" in p)
        return d.get("span", labels or "?")

    rows = sorted(series.items(), key=lambda kv: -float(kv[1].get("sum",
                                                                  0.0)))
    for labels, h in rows:
        w(f"  {_span_name(labels)}: n={h['count']} "
          f"total={h.get('sum', 0.0):.6f}s mean={h['mean']:.6f}s "
          f"p99={h['p99']:.6f}\n")


def print_snapshot(snap, out=None):
    out = out or sys.stdout
    w = out.write
    print_trace(snap, out)
    print_layout(snap, out)
    print_plans(snap, out)
    print_comms(snap, out)
    print_zero(snap, out)
    print_ring(snap, out)
    print_quant(snap, out)
    print_overload(snap, out)
    for kind in ("counters", "gauges"):
        group = snap.get(kind) or {}
        if group:
            w(f"-- {kind} --\n")
            for name in sorted(group):
                series = group[name]
                for labels, v in sorted(series.items(),
                                        key=lambda kv: -_num(kv[1])):
                    lbl = f"{{{labels}}}" if labels else ""
                    w(f"  {name}{lbl}: {v}\n")
    hists = snap.get("histograms") or {}
    if hists:
        w("-- histograms --\n")
        for name in sorted(hists):
            for labels, h in sorted(hists[name].items()):
                w(_hist_line(name, labels, h) + "\n")
    dropped = snap.get("dropped_series")
    if dropped:
        w(f"-- dropped series (label-cardinality cap) --\n")
        for name, n in sorted(dropped.items()):
            w(f"  {name}: {n}\n")


def _num(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return 0.0


def diff_snapshots(old, new, top=15, out=None):
    """Rank series by regression: histogram relative mean growth and
    counter relative growth. Series absent from the old snapshot rank at
    0 (flagged "new series") so they cannot crowd real regressions out
    of the top-N window."""
    out = out or sys.stdout
    rows = []
    old_h = old.get("histograms") or {}
    for name, series in (new.get("histograms") or {}).items():
        for labels, h in series.items():
            prev = (old_h.get(name) or {}).get(labels)
            if not prev or not prev["count"] or not h["count"]:
                continue
            delta = h["mean"] - prev["mean"]
            rel = delta / prev["mean"] if prev["mean"] else 0.0
            rows.append((rel, "hist", name, labels,
                         f"mean {prev['mean']:.6f}s -> {h['mean']:.6f}s "
                         f"({rel:+.1%}), p99 {prev['p99']:.6f} -> "
                         f"{h['p99']:.6f}"))
    old_c = old.get("counters") or {}
    for name, series in (new.get("counters") or {}).items():
        for labels, v in series.items():
            pv = _num((old_c.get(name) or {}).get(labels, 0))
            nv = _num(v)
            if pv == 0 and nv == 0:
                continue
            rel = (nv - pv) / pv if pv else 0.0
            tag = "new series" if pv == 0 else format(rel, "+.1%")
            rows.append((rel, "counter", name, labels,
                         f"{pv:g} -> {nv:g} ({tag})"))
    rows.sort(key=lambda r: -r[0])
    out.write(f"top {top} regressed metrics (new vs old):\n")
    for rel, kind, name, labels, desc in rows[:top]:
        lbl = f"{{{labels}}}" if labels else ""
        out.write(f"  [{kind}] {name}{lbl}: {desc}\n")
    if not rows:
        out.write("  (no comparable series)\n")
    return rows


def _timeseries_mod():
    """The shared timeline JSONL reader, via tools/flight_report.py's
    by-path loader (no paddle_tpu/jax import — same discipline as the
    rest of this tool)."""
    try:
        from tools import flight_report
    except ImportError:
        import flight_report
    return flight_report.load_timeseries()


def print_timeline(path, top=15):
    """Per-metric delta/rate table between consecutive timeline samples:
    for every counter, the total delta across the file and the mean/max
    per-second rate; for every values/gauges signal, min/mean/max/last.
    """
    ts_mod = _timeseries_mod()
    samples = ts_mod.read_timeline(path)
    print(f"timeline {path}: {len(samples)} samples"
          + (f", ts {samples[0]['ts']:.3f} .. {samples[-1]['ts']:.3f}"
             if samples else ""))
    if not samples:
        return
    counter_keys = ts_mod.timeline_keys(samples, group="counters")
    rows = []
    for k in counter_keys:
        deltas = ts_mod.series_from(samples, f"counters:{k}:delta")
        rates = ts_mod.series_from(samples, f"counters:{k}:rate")
        if not deltas:
            continue
        total = sum(v for _, v in deltas)
        rvals = [v for _, v in rates]
        rows.append((k, total, sum(rvals) / len(rvals) if rvals else 0.0,
                     max(rvals) if rvals else 0.0))
    rows.sort(key=lambda r: -abs(r[1]))
    if rows:
        print(f"\n  {'counter':44s} {'delta':>12s} {'rate/s mean':>12s}"
              f" {'rate/s max':>12s}")
        for k, total, mean_r, max_r in rows[:top]:
            print(f"  {k[:44]:44s} {total:12.6g} {mean_r:12.6g}"
                  f" {max_r:12.6g}")
    for group in ("values", "gauges"):
        keys = ts_mod.timeline_keys(samples, group=group)
        rows = []
        for k in keys:
            vals = [v for _, v in ts_mod.series_from(samples,
                                                     f"{group}:{k}")]
            if vals:
                rows.append((k, min(vals), sum(vals) / len(vals),
                             max(vals), vals[-1]))
        if rows:
            print(f"\n  {group + ':':44s} {'min':>10s} {'mean':>10s}"
                  f" {'max':>10s} {'last':>10s}")
            for k, lo, mean, hi, last in rows[:top * 2]:
                print(f"  {k[:44]:44s} {lo:10.4g} {mean:10.4g}"
                      f" {hi:10.4g} {last:10.4g}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("snapshot", help="telemetry snapshot or bench JSON "
                    "(a timeline JSONL with --timeline)")
    ap.add_argument("other", nargs="?",
                    help="second snapshot: diff mode (old=first, new=second)")
    ap.add_argument("--top", type=int, default=15,
                    help="diff mode: how many regressed metrics to show")
    ap.add_argument("--timeline", action="store_true",
                    help="the input is a timeline JSONL (recorded by "
                    "TimeSeriesRecorder / a soak): "
                    "print per-metric delta/rate columns between "
                    "consecutive samples")
    args = ap.parse_args(argv)
    if args.timeline:
        print_timeline(args.snapshot, top=args.top)
    elif args.other is None:
        print_snapshot(load_snapshot(args.snapshot))
    else:
        diff_snapshots(load_snapshot(args.snapshot),
                       load_snapshot(args.other), top=args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
