"""The benchmark's one command: runs one cell once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: BENCHMARK.json names the cell's
configuration (benchmark/configs/<config>.json), its traffic mix
(benchmark/traffic/<mix>.json) and the per-layer metrics
(benchmark/layer_metrics/<name>.json, each naming a reader and its
arguments). The last line of standard output is the result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_cell(root, workload):
    """The cell's entry, its configuration and its mix, from the files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = dict(cells[workload])
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf["file"])) as f:
        cell["config"] = json.load(f)
    cell["dir"] = os.path.join(root, os.path.dirname(
        os.path.dirname(conf["file"])))
    for key, sub, name in (("mix", "traffic", cell["traffic"]),
                           ("limits", "limits", workload)):
        with open(os.path.join(cell["dir"], sub, f"{name}.json")) as f:
            cell[key] = json.load(f)
    return bench, cell


def prepare(cell, on_tpu):
    """The configuration's environment for the program, and on the chip
    JAX's persistent compile cache inside the checkout (eager programs
    compile in well under a second: cache them too)."""
    for key, value in cell["config"].get("program", {}).get("env",
                                                           {}).items():
        os.environ[key] = value
    if on_tpu:
        import jax

        from paddle_tpu.device import compile_cache_dir

        compile_cache_dir()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def metrics_of(bench, group, workload):
    """The metrics of a group that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def read_layer_metrics(bench, cell, ctx):
    """Each per-layer metric through the reader its file names; a reader
    that finds nothing to read leaves its metric out."""
    out = {}
    for m in metrics_of(bench, "per_layer", cell["name"]):
        with open(os.path.join(cell["dir"], "layer_metrics",
                               f"{m['name']}.json")) as f:
            spec = json.load(f)
        mod, fn = spec["reader"].split(".")
        reader = getattr(importlib.import_module(f"harness.{mod}"), fn)
        value = reader(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class CompileClock:
    """Seconds JAX spent obtaining executables and the persistent cache's
    hits and misses, from jax.monitoring (copied from chip_smoke.py)."""

    def __init__(self):
        import jax

        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def judge(numbers, limits):
    """Each number compared beside its limit ({"max": x} or {"min": x});
    correct when every one holds. A number that is missing fails."""
    rows, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        rows[name] = {"value": value, "limit": limit}
        if value is None or value > limit.get("max", float("inf")) \
                or value < limit.get("min", float("-inf")):
            ok = False
    return ok, rows


def main(argv=None, require_chip=True):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=REPO,
                    help="the tree that holds BENCHMARK.json and benchmark/")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, REPO]
    bench, cell = load_cell(args.root, args.workload)

    import jax

    from harness import roofline, trace

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_chip and (device["platform"] != "tpu"
                         or device["count"] < cell["chips"]):
        raise SystemExit(f"benchmark: cell {args.workload} needs "
                         f"{cell['chips']} TPU chip(s); JAX found {device}")
    prepare(cell, device["platform"] == "tpu")
    clock = CompileClock()
    spans = trace.Spans()
    capture = trace.Capture(os.path.join(REPO, ".bench_trace",
                                         args.workload))
    state = {}

    def start_window():
        state["compile_s"] = clock.seconds
        state["compiles"] = (clock.hits, clock.misses)
        if args.trace:
            from paddle_tpu.telemetry import trace as ptrace

            ptrace.enable()
            ptrace.reset()
            state["ptrace_epoch"] = time.perf_counter()
            capture.start()

    def stop_window(t0, t1):
        if args.trace:
            capture.stop()
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        state["memory_peak_bytes"] = max(
            s.get("peak_bytes_in_use", 0) for s in stats)
        print(f"memory_stats: {stats[0]}", file=sys.stderr, flush=True)
        state["window_compile_s"] = clock.seconds - state["compile_s"]

    env = {"spans": spans, "start_window": start_window,
           "stop_window": stop_window}
    kind = cell["mix"]["kind"]
    harness = importlib.import_module(
        "harness.train" if kind == "train" else "harness.serve")
    res = harness.run(cell, args, env)

    t0, t1 = res["t0"], res["t1"]
    correct, rows = judge(res["numbers"], cell["limits"])
    values = dict(res["e2e"])
    values["setup_s"] = t0 - T_PROCESS
    device["memory_peak_bytes"] = state["memory_peak_bytes"]
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        devices, sync_ns = capture.read()
        shutil.rmtree(capture.logdir, ignore_errors=True)
        off = sync_ns - capture.sync_pc * 1e9  # perf_counter -> trace ns
        tr = trace.reduce_trace(devices, t0 * 1e9 + off, t1 * 1e9 + off)
        from paddle_tpu.telemetry import trace as ptrace

        spans.add_program_spans(ptrace.events(), state["ptrace_epoch"])
        gaps = trace.attribute_gaps(tr["gaps"], [
            (n, a * 1e9 + off, b * 1e9 + off) for n, a, b in spans.items
            if n != "bench.window"])
        counters = dict(res["counters"])
        counters["compile_s"] = state["compile_s"]
        ctx = {"trace": tr, "spans": spans, "counters": counters,
               "config": cell["config"], "chips": cell["chips"],
               "peaks": roofline.peaks(device["kind"]),
               "window_s": t1 - t0, "t0": t0, "t1": t1}
        line["metrics"] = read_layer_metrics(bench, cell, ctx)
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        line["device"] = device
        line["breakdown"] = trace.breakdown(tr, gaps)
        print("ops by self time: " + json.dumps(
            trace.breakdown(tr, gaps, top=40)), file=sys.stderr, flush=True)
    else:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(bench, "end_to_end", args.workload)}
        line["device"] = device
    line["checks"] = rows
    print(f"setup {values['setup_s']:.2f} s (compile {state['compile_s']:.2f}"
          f" s, cache hits/misses {state['compiles']}; compile inside the "
          f"window {state['window_compile_s']:.3f} s); e2e {values}; all "
          f"numbers {res['numbers']}", file=sys.stderr, flush=True)
    for name, row in rows.items():
        print(f"check {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
