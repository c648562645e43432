#!/usr/bin/env python
"""Summarize a span trace, or diff two traces by phase.

Usage:
    python tools/trace_report.py TRACE            # summary
    python tools/trace_report.py OLD NEW [--top N]  # phase diff

Accepts both formats the tracer exports (docs/TELEMETRY.md Tracing):

- Perfetto/Chrome trace-event JSON (``trace.to_perfetto``):
  ``{"traceEvents": [...]}`` with ``ts``/``dur`` in microseconds,
- the compact JSONL (``trace.dump_jsonl``): one event per line with
  ``ts``/``dur`` in seconds and a leading ``{"ph": "meta", ...}`` line.

The summary prints per-phase totals (count / total / mean seconds) for
complete spans, instant counts (the plan-collective events), and async
request stats (count, mean duration, unclosed). Diff mode ranks phases
by total-seconds growth — "which phase ate the regression".

A malformed trace (unparseable JSON, missing required event fields,
negative durations) **exits 1** so CI can gate trace integrity on the
same artifact Perfetto loads.
"""
from __future__ import annotations

import argparse
import json
import sys


class MalformedTrace(ValueError):
    pass


_REQUIRED = {"ph", "name"}


def _validate_event(e, scale):
    if not isinstance(e, dict):
        raise MalformedTrace(f"event is not an object: {e!r}")
    ph = e.get("ph")
    if ph == "meta":
        return None
    missing = _REQUIRED - set(e)
    if missing:
        raise MalformedTrace(f"event missing {sorted(missing)}: {e!r}")
    if ph == "M":   # perfetto metadata (thread names)
        return None
    if ph not in ("X", "i", "I", "b", "e", "n"):
        raise MalformedTrace(f"unknown event phase {ph!r}: {e!r}")
    if "ts" not in e:
        raise MalformedTrace(f"event missing 'ts': {e!r}")
    try:
        ts = float(e["ts"]) * scale
    except (TypeError, ValueError):
        raise MalformedTrace(f"non-numeric ts: {e!r}")
    dur = None
    if ph == "X":
        if "dur" not in e:
            raise MalformedTrace(f"complete span missing 'dur': {e!r}")
        try:
            dur = float(e["dur"]) * scale
        except (TypeError, ValueError):
            raise MalformedTrace(f"non-numeric dur: {e!r}")
        if dur < 0:
            raise MalformedTrace(f"negative span duration: {e!r}")
    return {"ph": ph, "name": str(e["name"]), "ts": ts, "dur": dur,
            "id": e.get("id"),
            "attrs": e.get("attrs") or e.get("args"),
            "cat": e.get("cat", "")}


def load_trace(path):
    """-> normalized event list (seconds). Raises MalformedTrace."""
    with open(path) as f:
        text = f.read()
    if not text.strip():
        raise MalformedTrace(f"{path}: empty file")
    events, raw = [], None
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and isinstance(doc.get("traceEvents"), list):
        raw, scale = doc["traceEvents"], 1e-6   # perfetto: microseconds
    elif isinstance(doc, list):
        raw, scale = doc, 1e-6                  # bare chrome event array
    elif doc is None:
        raw, scale = [], 1.0                    # JSONL: seconds
        for i, line in enumerate(text.splitlines()):
            line = line.strip()
            if not line:
                continue
            try:
                raw.append(json.loads(line))
            except json.JSONDecodeError:
                raise MalformedTrace(f"{path}:{i + 1}: not JSON: "
                                     f"{line[:80]!r}")
    else:
        raise MalformedTrace(
            f"{path}: neither a traceEvents JSON nor JSONL")
    for e in raw:
        ev = _validate_event(e, scale)
        if ev is not None:
            events.append(ev)
    if not events:
        raise MalformedTrace(f"{path}: no trace events")
    return events


def phase_totals(events):
    """{name: {"count", "seconds"}} over complete spans."""
    out = {}
    for e in events:
        if e["ph"] != "X":
            continue
        row = out.setdefault(e["name"], {"count": 0, "seconds": 0.0})
        row["count"] += 1
        row["seconds"] += e["dur"]
    return out


def instant_counts(events):
    out = {}
    for e in events:
        if e["ph"] in ("i", "I", "n"):
            out[e["name"]] = out.get(e["name"], 0) + 1
    return out


def request_stats(events):
    """Async b/e pairing per (name, id): count, mean seconds, unclosed."""
    open_, durs, unclosed = {}, {}, 0
    for e in events:
        if e["ph"] == "b":
            open_.setdefault((e["name"], e["id"]), []).append(e["ts"])
        elif e["ph"] == "e":
            stack = open_.get((e["name"], e["id"]))
            if stack:
                t0 = stack.pop()
                durs.setdefault(e["name"], []).append(e["ts"] - t0)
    unclosed = sum(len(v) for v in open_.values())
    return {name: {"count": len(ds),
                   "mean_seconds": sum(ds) / len(ds)}
            for name, ds in durs.items()}, unclosed


_SERVE_SPANS = ("engine_step", "retire", "admission", "prefill_group",
                "prefill_tick", "prefill_build", "prefill_launch",
                "first_token_fetch", "grow_pages", "decode_build",
                "decode_upload", "decode_tick", "decode_launch",
                "decode_fetch", "emit", "spec_draft", "spec_verify")
_SERVE_ASYNC = ("request", "route", "queue", "prefill")


def serving_stats(events):
    """Aggregate the serving span contract (docs/TELEMETRY.md Tracing,
    docs/SERVING.md): engine tick phases, per-request async spans
    (route/queue/prefill/request), handoff transfers, and speculative-
    decode acceptance from the ``spec_accept`` instants. None when the
    trace carries no serving activity."""
    ticks = {}
    for e in events:
        if e["ph"] == "X" and e["name"] in _SERVE_SPANS:
            row = ticks.setdefault(e["name"], {"count": 0, "seconds": 0.0})
            row["count"] += 1
            row["seconds"] += e["dur"]
    reqs, _unclosed = request_stats(events)
    async_rows = {n: reqs[n] for n in _SERVE_ASYNC if n in reqs}
    handoffs = {"count": 0, "bytes": 0}
    spec = {"accepted": 0, "drafted": 0}
    for e in events:
        attrs = e.get("attrs") or {}
        if e["ph"] == "n" and e["name"] == "handoff":
            handoffs["count"] += 1
            handoffs["bytes"] += int(attrs.get("bytes") or 0)
        elif e["ph"] in ("i", "I") and e["name"] == "spec_accept":
            spec["accepted"] += int(attrs.get("accepted") or 0)
            spec["drafted"] += int(attrs.get("drafted") or 0)
    if not ticks and not async_rows and not handoffs["count"]:
        return None
    out = {"ticks": ticks, "requests": async_rows}
    if handoffs["count"]:
        out["handoffs"] = handoffs
    if spec["drafted"]:
        spec["acceptance_rate"] = round(spec["accepted"]
                                        / spec["drafted"], 4)
        out["spec"] = spec
    return out


def print_summary(path, events, out=None):
    w = (out or sys.stdout).write
    w(f"{path}: {len(events)} events\n")
    phases = phase_totals(events)
    if phases:
        w("-- phases (complete spans) --\n")
        for name in sorted(phases, key=lambda n: -phases[n]["seconds"]):
            p = phases[name]
            w(f"  {name}: n={p['count']} total={p['seconds']:.6f}s "
              f"mean={p['seconds'] / p['count']:.6f}s\n")
    inst = instant_counts(events)
    if inst:
        w("-- instants --\n")
        for name in sorted(inst, key=lambda n: -inst[n]):
            w(f"  {name}: n={inst[name]}\n")
    reqs, unclosed = request_stats(events)
    if reqs or unclosed:
        w("-- async (request spans) --\n")
        for name in sorted(reqs):
            r = reqs[name]
            w(f"  {name}: n={r['count']} "
              f"mean={r['mean_seconds']:.6f}s\n")
        if unclosed:
            w(f"  (unclosed spans: {unclosed})\n")
    serve = serving_stats(events)
    if serve:
        w("-- serving --\n")
        for name, row in sorted(serve["ticks"].items(),
                                key=lambda kv: -kv[1]["seconds"]):
            w(f"  {name}: n={row['count']} "
              f"total={row['seconds']:.6f}s\n")
        for name, row in sorted(serve["requests"].items()):
            w(f"  {name} (async): n={row['count']} "
              f"mean={row['mean_seconds']:.6f}s\n")
        if "handoffs" in serve:
            h = serve["handoffs"]
            w(f"  handoffs: n={h['count']} bytes={h['bytes']}\n")
        if "spec" in serve:
            s = serve["spec"]
            w(f"  spec: accepted {s['accepted']}/{s['drafted']} "
              f"(rate {s['acceptance_rate']})\n")


def diff(old_events, new_events, top=15, out=None):
    out = out or sys.stdout
    old_p, new_p = phase_totals(old_events), phase_totals(new_events)
    rows = []
    for name in set(old_p) | set(new_p):
        o = old_p.get(name, {}).get("seconds", 0.0)
        n = new_p.get(name, {}).get("seconds", 0.0)
        rel = (n - o) / o if o else (float("inf") if n else 0.0)
        rows.append((n - o, rel, name, o, n))
    rows.sort(key=lambda r: -r[0])
    out.write(f"top {top} phases by total-seconds growth (new vs old):\n")
    for delta, rel, name, o, n in rows[:top]:
        tag = ("new phase" if o == 0.0 and n > 0.0
               else f"{rel:+.1%}")
        out.write(f"  {name}: {o:.6f}s -> {n:.6f}s "
                  f"({delta:+.6f}s, {tag})\n")
    if not rows:
        out.write("  (no comparable phases)\n")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace file (perfetto JSON or JSONL)")
    ap.add_argument("other", nargs="?",
                    help="second trace: diff mode (old=first, new=second)")
    ap.add_argument("--top", type=int, default=15,
                    help="diff mode: phases to show")
    args = ap.parse_args(argv)
    try:
        events = load_trace(args.trace)
        other = load_trace(args.other) if args.other else None
    except (MalformedTrace, OSError) as e:
        print(f"trace_report: malformed trace: {e}", file=sys.stderr)
        return 1
    if other is None:
        print_summary(args.trace, events)
    else:
        diff(events, other, top=args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
