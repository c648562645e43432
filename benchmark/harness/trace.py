"""Device-trace capture and its reduction to metrics (the reduction of
paddle_tpu/profiler/xplane.py and profile_bench.py, copied and extended so
that later PRs cannot move the yardstick): device busy and idle time, self
time per named operation, collective time not covered by compute, and idle
gaps attributed to the host span that covered them. The reduction works on
plain tuples, so it is checked on synthetic traces without a profiler."""
from __future__ import annotations

import contextlib
import glob
import os
import re
import time

COLLECTIVE = r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all"
SYNC = "bench.sync"


# ------------------------------------------------------------------ capture
class Spans:
    """Host spans in memory: (name, t0, t1) on time.perf_counter()."""

    def __init__(self):
        self.items = []

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.items.append((name, t0, time.perf_counter()))

    def add_program_spans(self, events, epoch):
        """paddle_tpu.telemetry.trace events (ts relative to ``epoch``)."""
        for e in events:
            if e.get("ph") == "X":
                t0 = epoch + e["ts"]
                self.items.append((e["name"], t0, t0 + e["dur"]))

    def mean_ms(self, name, lo, hi):
        d = [t1 - t0 for n, t0, t1 in self.items
             if n == name and t0 >= lo and t1 <= hi]
        return 1e3 * sum(d) / len(d) if d else None

    def total_s(self, name, lo, hi):
        return sum(t1 - t0 for n, t0, t1 in self.items
                   if n == name and t0 >= lo and t1 <= hi)


class Capture:
    """jax.profiler around a window. ``sync`` pairs one annotated instant
    with perf_counter so host spans land on the trace's clock."""

    def __init__(self, logdir):
        self.logdir = logdir
        self.sync_pc = None

    def start(self):
        import jax

        jax.profiler.start_trace(self.logdir)
        self.sync_pc = time.perf_counter()
        with jax.profiler.TraceAnnotation(SYNC):
            time.sleep(0.001)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def read(self):
        """-> (device_lines, sync_ns): device_lines is one list of
        (name, start_ns, dur_ns) per device, from its "XLA Ops" line."""
        from jax.profiler import ProfileData

        runs = sorted(glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*")))
        paths = glob.glob(os.path.join(runs[-1], "*.xplane.pb"))
        devices, sync_ns = [], None
        for path in paths:
            for plane in ProfileData.from_file(path).planes:
                if plane.name.startswith("/device:TPU:"):
                    for line in plane.lines:
                        if line.name == "XLA Ops":
                            devices.append([
                                (e.name, e.start_ns, e.duration_ns)
                                for e in line.events])
                elif plane.name.startswith("/host:"):
                    for line in plane.lines:
                        for e in line.events:
                            if e.name == SYNC and sync_ns is None:
                                sync_ns = e.start_ns
        return devices, sync_ns


# ---------------------------------------------------------------- reduction
def op_name(name):
    """'%fusion.123 = ...' -> 'fusion': the op's stable name."""
    name = name.split(" ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name) or name


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(events):
    """[(name, start, self_dur, is_leaf)] of one line's events, where an
    event that encloses others (a while loop and its body) keeps only the
    time its children do not cover."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [index, end]
    for name, start, dur in evs:
        while stack and stack[-1][1] < start + dur:
            stack.pop()  # over, or only overlapping: not this one's parent
        if stack:
            parent = out[stack[-1][0]]
            parent[2] -= dur
            parent[3] = False
        out.append([name, start, dur, True])
        stack.append([len(out) - 1, start + dur])
    return [(n, s, max(d, 0), leaf) for n, s, d, leaf in out]


def reduce_trace(devices, lo_ns, hi_ns):
    """Per-chip averages over the window [lo, hi): busy seconds, self
    seconds and calls per op name, seconds of collectives not covered by a
    compute op, and the idle gaps of the first device as (start, end)."""
    n = len(devices)
    ops, busy, exposed, gaps = {}, 0.0, 0.0, []
    for d, events in enumerate(devices):
        evs = [(nm, max(s, lo_ns), min(s + du, hi_ns) - max(s, lo_ns))
               for nm, s, du in events if s < hi_ns and s + du > lo_ns]
        merged = union([(s, s + du) for _, s, du in evs])
        busy += measure(merged) / 1e9 / n
        coll, comp = [], []
        for nm, s, du, leaf in self_times(evs):
            key = op_name(nm)
            c = ops.setdefault(key, [0, 0.0])
            c[0] += 1 / n
            c[1] += du / 1e9 / n
            if leaf:
                (coll if re.search(COLLECTIVE, key) else comp).append(
                    (s, s + du))
        exposed += measure(subtract(union(coll), union(comp))) / 1e9 / n
        if d == 0:
            gaps = subtract([[lo_ns, hi_ns]], merged)
    return {"busy_s": busy, "window_s": (hi_ns - lo_ns) / 1e9, "ops": ops,
            "exposed_collective_s": exposed, "gaps": gaps}


def op_calls_seconds(tr, patterns):
    calls = seconds = 0.0
    for name, (c, s) in tr["ops"].items():
        if any(re.search(p, name) for p in patterns):
            calls, seconds = calls + c, seconds + s
    return calls, seconds


def attribute_gaps(gaps_ns, spans_ns):
    """Seconds of idle gap by the innermost host span covering each gap's
    middle; ``spans_ns`` is [(name, start, end)] on the trace's clock."""
    out = {}
    for s, e in gaps_ns:
        mid = (s + e) / 2
        cover = [(t1 - t0, nm) for nm, t0, t1 in spans_ns if t0 <= mid < t1]
        name = min(cover)[1] if cover else "_no_span_"
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def breakdown(tr, gap_seconds, top=10):
    ops = sorted(((n, s) for n, (_, s) in tr["ops"].items()),
                 key=lambda x: -x[1])[:top]
    gaps = sorted(gap_seconds.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


# ----------------------------------------------------------------- readers
def op_share(ctx, patterns):
    """Self time of the matching ops as a share of device busy time."""
    tr = ctx.get("trace")
    if tr is None or not tr["busy_s"]:
        return None
    calls, seconds = op_calls_seconds(tr, patterns)
    return 100.0 * seconds / tr["busy_s"] if calls else None


def idle_share(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def exposed_share(ctx):
    """Collective time no compute op covers, as a share of the window."""
    tr = ctx.get("trace")
    if tr is None or not any(re.search(COLLECTIVE, n) for n in tr["ops"]):
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["window_s"]


def span_ms(ctx, span):
    return ctx["spans"].mean_ms(span, ctx["t0"], ctx["t1"])


def span_share(ctx, span, of):
    whole = ctx["spans"].total_s(of, ctx["t0"], ctx["t1"])
    if not whole:
        return None
    return 100.0 * ctx["spans"].total_s(span, ctx["t0"], ctx["t1"]) / whole


def counter(ctx, key, scale=1.0):
    v = ctx["counters"].get(key)
    return None if v is None else v * scale
