"""The readers of benchmark/harness/program.py and
benchmark/harness/startup.py on synthetic spans, marks, beats and
instants: what the per-layer metrics engine.host_ms.*,
engine.queue_wait_ms.chat, engine.prefill_ms.chat,
engine.prefill_fill_share.chat, host.beat_late_max_ms.train and the
setup.* metrics of the program's set-up record compute, events on the
window's edge included."""
import importlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import program, startup  # noqa: E402
from harness.trace import Spans  # noqa: E402

NEW = ("engine.host_ms.decode", "engine.host_ms.chat",
       "engine.queue_wait_ms.chat", "engine.prefill_ms.chat",
       "engine.prefill_fill_share.chat", "host.beat_late_max_ms.train")
SETUP = ("setup.trace_s", "setup.lower_s", "setup.load_s",
         "setup.first_run_s", "setup.after_warm_s")
EPOCH = 1000.0   # the tracer's epoch on perf_counter; the window is 10 s
T0, T1 = 1010.0, 1020.0


def X(name, t, dur, **attrs):
    return {"ph": "X", "name": name, "ts": t - EPOCH, "dur": dur,
            "attrs": attrs or None}


def mark(name, t, **attrs):
    return {"ph": "n", "name": name, "ts": t - EPOCH, "id": 1,
            "attrs": attrs or None}


def instant(name, t, **attrs):
    return {"ph": "i", "name": name, "ts": t - EPOCH, "attrs": attrs or None}


def ctx_of(events=(), beats=(), items=()):
    spans = Spans()
    spans.items = list(items)
    spans.add_program_spans(list(events), EPOCH)
    return {"t0": T0, "t1": T1, "spans": spans,
            "program": {"events": list(events), "epoch": EPOCH,
                        "beats": list(beats)}}


def test_host_seconds_subtracts_only_the_fetches_inside_each_tick():
    items = [
        ("engine_step", 1011.0, 1011.100),
        ("decode_fetch", 1011.010, 1011.090),      # 80 ms waiting
        ("decode_launch", 1011.005, 1011.010),     # host work: stays
        ("engine_step", 1012.0, 1012.400),
        ("first_token_fetch", 1012.050, 1012.150),
        ("decode_fetch", 1012.200, 1012.390),
        ("decode_fetch", 1012.500, 1012.600),      # under no tick: ignored
        ("engine_step", 1013.0, 1013.004),         # an idle tick
    ]
    host = program.host_seconds(items, T0, T1)
    assert [round(h, 6) for h in host] == [0.020, 0.110, 0.004]
    assert program.host_ms(ctx_of(items=items)) == pytest.approx(
        1e3 * (0.020 + 0.110 + 0.004) / 3)


def test_host_ms_leaves_out_a_tick_across_the_windows_edge():
    items = [
        ("engine_step", T0 - 0.05, T0 + 0.05),     # began before the window
        ("decode_fetch", T0 - 0.04, T0 + 0.04),
        ("engine_step", T0, T0 + 0.1),             # begins ON the edge: in
        ("decode_fetch", T0 + 0.01, T0 + 0.07),
        ("engine_step", T1 - 0.1, T1),             # ends ON the edge: in
        ("engine_step", T1 - 0.05, T1 + 0.05),     # ends after it
    ]
    assert [round(h, 6) for h in program.host_seconds(items, T0, T1)] == [
        0.04, 0.1]
    assert program.host_ms(ctx_of(items=items)) == pytest.approx(70.0)


def test_host_ms_reads_the_programs_own_spans_through_run_pys_list():
    events = [X("engine_step", 1011.0, 0.1, tick=7),
              X("decode_fetch", 1011.02, 0.06),
              X("bench.tick", 1011.0, 0.2)]
    assert program.host_ms(ctx_of(events)) == pytest.approx(40.0)


def test_queue_and_prefill_means_over_the_windows_first_tokens():
    events = [
        mark("first_token", T0 - 0.001, queue_ms=9e9, prefill_ms=9e9),
        mark("first_token", T0, queue_ms=100.0, prefill_ms=900.0),  # edge in
        mark("first_token", 1015.0, queue_ms=300.0, prefill_ms=1100.0),
        mark("admitted", 1015.0, kind="prefill", tick=3),
        mark("first_token", T1, queue_ms=9e9, prefill_ms=9e9),  # edge: out
    ]
    ctx = ctx_of(events)
    assert program.mark_mean(ctx, "first_token", "queue_ms") == 200.0
    assert program.mark_mean(ctx, "first_token", "prefill_ms") == 1000.0


def test_marks_without_the_split_give_no_reading():
    """The parent's first_token marks carry no attrs: nothing to read."""
    ctx = ctx_of([mark("first_token", 1015.0)])
    assert program.mark_mean(ctx, "first_token", "queue_ms") is None
    assert program.mark_mean(ctx_of(), "first_token", "queue_ms") is None


def test_fill_share_is_a_ratio_of_sums_over_ticks_that_launched():
    events = [
        X("prefill_tick", 1011.0, 0.3, rows=2, valid_tokens=256,
          computed_tokens=4096),
        X("prefill_tick", 1012.0, 0.3, rows=1, valid_tokens=64,
          computed_tokens=4096),
        X("prefill_tick", 1013.0, 0.00001),        # nothing to prefill
        X("prefill_tick", T0 - 0.1, 0.3, rows=9, valid_tokens=4096,
          computed_tokens=4096),                   # across the edge: out
        X("decode_tick", 1014.0, 0.1, live=32),
    ]
    share = program.span_attr_share(ctx_of(events), "prefill_tick",
                                    "valid_tokens", "computed_tokens")
    assert share == pytest.approx(100.0 * 320 / 8192)
    assert program.span_attr_share(
        ctx_of([X("prefill_tick", 1011.0, 0.3)]), "prefill_tick",
        "valid_tokens", "computed_tokens") is None


def test_latest_beat_of_the_window():
    beats = [(T0 - EPOCH - 0.02, 3.0),             # due before the window
             (T0 - EPOCH, 0.0004),                 # due on its edge: in
             (12.0, 0.0021), (14.0, 1.75), (16.0, 0.0003),
             (T1 - EPOCH, 2.5)]                    # due at its end: out
    assert program.latest_beat(beats, EPOCH, T0, T1) == 1.75
    assert program.beat_late_max_ms(ctx_of(beats=beats)) == 1750.0
    quiet = [(10.0 + 0.02 * i, 0.0002 + 1e-6 * i) for i in range(500)]
    assert program.beat_late_max_ms(ctx_of(beats=quiet)) == pytest.approx(
        0.699)
    assert program.beat_late_max_ms(ctx_of()) is None


def test_a_program_without_the_tracers_epoch_reads_as_nothing(monkeypatch):
    """Laid over the parent commit, the readers return None: no raise."""
    from paddle_tpu.telemetry import trace

    monkeypatch.delattr(trace, "epoch")
    ctx = {"t0": T0, "t1": T1, "spans": Spans()}
    assert program.recorded(ctx) is None
    assert program.host_ms(ctx) is None
    assert program.mark_mean(ctx, "first_token", "queue_ms") is None
    assert program.span_attr_share(ctx, "prefill_tick", "valid_tokens",
                                   "computed_tokens") is None
    assert program.beat_late_max_ms(ctx) is None


def test_readers_read_the_live_tracer():
    """No "program" in the context, as run.py calls them: events, epoch
    and beats come from paddle_tpu.telemetry.trace."""
    import time

    from paddle_tpu.telemetry import trace

    trace.enable()
    trace.reset()
    try:
        t0 = time.perf_counter()
        with trace.span("prefill_tick") as sp:
            sp.annotate(rows=1, valid_tokens=3, computed_tokens=12)
        trace.async_instant("first_token", 5, {"queue_ms": 2.0,
                                               "prefill_ms": 8.0})
        while not trace.beats():
            time.sleep(0.005)
        ctx = {"t0": t0, "t1": time.perf_counter(), "spans": Spans()}
        assert program.span_attr_share(ctx, "prefill_tick", "valid_tokens",
                                       "computed_tokens") == 25.0
        assert program.mark_mean(ctx, "first_token", "prefill_ms") == 8.0
        assert program.beat_late_max_ms(ctx) > -1.0
    finally:
        trace.disable()
        trace.reset()


def test_setup_sum_adds_the_windows_setup_instants_only():
    engine = dict(owner="engine", programs=10, trace_s=3.0, lower_s=2.0,
                  load_s=4.0, run_s=1.5, after_warm_s=6.0)
    train = dict(owner="train_step", programs=1, trace_s=0.5, lower_s=0.25,
                 load_s=1.0, after_warm_s=4.0)
    events = [
        instant("setup", T0 - 0.001, **dict(engine, trace_s=9e9)),  # before
        instant("setup", T0, **engine),                        # edge: in
        instant("setup", 1015.0, **train),
        mark("setup", 1016.0, trace_s=9e9),            # not an instant
        instant("program_memory", 1016.0, trace_s=9e9),
        instant("setup", T1, **dict(engine, trace_s=9e9)),     # edge: out
    ]
    ctx = ctx_of(events)
    assert startup.setup_sum(ctx, "trace_s") == 3.5
    assert startup.setup_sum(ctx, "load_s") == 5.0
    assert startup.setup_sum(ctx, "after_warm_s") == 10.0
    # the train step keeps no run_s: the engine's alone
    assert startup.setup_sum(ctx, "run_s") == 1.5
    assert startup.setup_sum(ctx_of(events[2:3]), "run_s") is None
    assert startup.setup_sum(ctx_of(), "trace_s") is None


def test_setup_sum_reads_what_an_engine_publishes():
    """The instant a SetupRecord publishes in the window, read back as
    run.py reads it: from the live tracer."""
    import time

    from paddle_tpu.jit import SetupRecord
    from paddle_tpu.telemetry import trace

    rec = SetupRecord("engine")
    rec.add("decode", 0.5, 0.25, 1.0)
    rec.programs["decode"].append(0.125)
    rec.add("prefill", 0.25, 0.125, 0.5)
    rec.programs["prefill"].append(0.0625)
    rec.warm_end = time.perf_counter()
    trace.enable()
    trace.reset()
    try:
        t0 = time.perf_counter()
        rec.publish()
        ctx = {"t0": t0, "t1": time.perf_counter() + 1e-6, "spans": Spans()}
        assert startup.setup_sum(ctx, "trace_s") == 0.75
        assert startup.setup_sum(ctx, "lower_s") == 0.375
        assert startup.setup_sum(ctx, "load_s") == 1.5
        assert startup.setup_sum(ctx, "run_s") == 0.1875
        assert 0.0 <= startup.setup_sum(ctx, "after_warm_s") < 60.0
    finally:
        trace.disable()
        trace.reset()


@pytest.mark.parametrize("name", NEW + SETUP)
def test_each_new_metric_names_a_reader_and_its_cell(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for cell in entry.get("workloads", ()):
        assert cell in e2e[entry["moves"]].get("workloads", cells)
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           f"{name}.json")) as f:
        spec = json.load(f)
    mod, fn = spec["reader"].split(".")
    reader = getattr(importlib.import_module(f"harness.{mod}"), fn)
    # its arguments fit the reader, and an empty window reads as nothing
    assert reader(ctx_of(), **spec["args"]) is None
    # appended together, in this order (later PRs append after them)
    group = NEW if name in NEW else SETUP
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(group[0])
    assert names[at:at + len(group)] == list(group)
