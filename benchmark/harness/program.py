"""Readers over what the program measured of itself inside the window: the
serving tick's phases (spans of ``ContinuousBatchingEngine.step``), a
request's wait split into queue and prefill (attrs of its ``first_token``
mark), how full the prefill program's rows were (attrs of ``prefill_tick``)
and the tracer's heartbeat. Spans come from ``ctx["spans"]`` (run.py has put
the program's ``X`` events there, on ``time.perf_counter()``); marks, attrs
and beats come from ``paddle_tpu.telemetry.trace`` itself, placed on the same
clock by its ``epoch()``. The reductions take plain lists, so they are
checked on synthetic ones; a program without these spans, attrs or beats
gives None and the metric is left out."""
from __future__ import annotations

WAITS = ("decode_fetch", "first_token_fetch")


def recorded(ctx):
    """{"events", "epoch", "beats"} of the program's tracer (``ctx`` may
    carry its own under "program"); None where the tracer has no epoch()."""
    if "program" in ctx:
        return ctx["program"]
    from paddle_tpu.telemetry import trace

    if not hasattr(trace, "epoch"):
        return None
    return {"events": trace.events(), "epoch": trace.epoch(),
            "beats": trace.beats()}


def in_window(ctx, ph, name):
    """The attrs of the window's events of one kind and name, their ``ts``
    put on perf_counter by the tracer's epoch: an ``X`` span counts when
    it lies wholly inside the window, an instant or mark when lo <= t < hi
    (as the harness counts a first token)."""
    rec = recorded(ctx)
    if rec is None:
        return []
    lo, hi, out = ctx["t0"], ctx["t1"], []
    for e in rec["events"]:
        if e.get("ph") != ph or e.get("name") != name:
            continue
        t = rec["epoch"] + e["ts"]
        inside = (lo <= t and t + e["dur"] <= hi) if ph == "X" \
            else lo <= t < hi
        if inside:
            out.append(e.get("attrs") or {})
    return out


# --------------------------------------------------------------- reductions
def host_seconds(items, lo, hi):
    """Per ``engine_step`` span wholly inside [lo, hi]: its duration less
    the ``WAITS`` spans inside it, the host's serial work of that tick."""
    steps = sorted((a, b) for n, a, b in items
                   if n == "engine_step" and a >= lo and b <= hi)
    held = sorted((a, b) for n, a, b in items if n in WAITS)
    out, j = [], 0
    for a, b in steps:
        while j < len(held) and held[j][0] < a:
            j += 1
        k, waited = j, 0.0
        while k < len(held) and held[k][1] <= b:
            waited += held[k][1] - held[k][0]
            k += 1
        j = k
        out.append(b - a - waited)
    return out


def mean_attr(rows, key):
    vals = [r[key] for r in rows if r.get(key) is not None]
    return sum(vals) / len(vals) if vals else None


def ratio_of_sums(rows, num, den):
    rows = [r for r in rows if r.get(den)]
    total = sum(r[den] for r in rows)
    return 100.0 * sum(r[num] for r in rows) / total if total else None


def latest_beat(beats, epoch, lo, hi):
    """Seconds by which the latest heartbeat due in [lo, hi) woke late."""
    late = [l for ts, l in beats if lo <= epoch + ts < hi]
    return max(late) if late else None


# ------------------------------------------------------------------ readers
def host_ms(ctx):
    """Mean host milliseconds a tick: ``engine_step`` less the fetches that
    wait for the device inside it."""
    host = host_seconds(ctx["spans"].items, ctx["t0"], ctx["t1"])
    return 1e3 * sum(host) / len(host) if host else None


def mark_mean(ctx, mark, attr):
    """Mean of one attr over the window's marks of one name."""
    return mean_attr(in_window(ctx, "n", mark), attr)


def span_attr_share(ctx, span, num, den):
    """Sum of one attr over the sum of another, over the window's spans of
    one name that carry them, in percent."""
    return ratio_of_sums(in_window(ctx, "X", span), num, den)


def beat_late_max_ms(ctx):
    rec = recorded(ctx)
    if rec is None:
        return None
    late = latest_beat(rec["beats"], rec["epoch"], ctx["t0"], ctx["t1"])
    return None if late is None else 1e3 * late
