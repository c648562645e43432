"""Span tracer: nesting, thread safety, disabled-path cost, Perfetto
export, serving request trees, watchdog debris and the trace_report
tool."""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.telemetry as telemetry
from paddle_tpu.telemetry import trace
from paddle_tpu.telemetry.trace import SpanTracer


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Each test starts with an empty, disabled tracer and registry."""
    trace.disable()
    trace.reset()
    telemetry.reset()
    yield
    trace.disable()
    trace.reset()
    telemetry.disable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------
def test_disabled_span_is_shared_noop_singleton():
    """The acceptance contract: PTPU_TRACE unset adds no measurable
    overhead — span() while disabled returns ONE shared object (no
    allocation per call) and records nothing."""
    s1 = trace.span("a", attrs=None)
    s2 = trace.span("b", attrs={"x": 1})
    assert s1 is s2
    with s1:
        pass
    trace.instant("i")
    trace.async_begin("r", 1)
    trace.async_end("r", 1)
    trace.complete("c", 0.0, 1.0)
    assert trace.events() == []


def test_disabled_calls_touch_no_thread_buffers():
    """No per-thread ring buffer is even created while disabled — the
    disabled path is one attribute check."""
    t = SpanTracer()
    for _ in range(100):
        with t.span("x"):
            pass
        t.instant("y")
    assert t._bufs == []


def test_enable_disable_roundtrip():
    assert not trace.enabled()
    trace.enable()
    assert trace.enabled()
    with trace.span("only"):
        pass
    trace.disable()
    with trace.span("after"):
        pass
    names = [e["name"] for e in trace.events()]
    assert names == ["only"]


# ---------------------------------------------------------------------------
# spans: nesting, attrs, threads, ring bound
# ---------------------------------------------------------------------------
def test_span_nesting_records_depth_and_duration():
    trace.enable()
    with trace.span("outer", attrs={"k": "v"}):
        time.sleep(0.002)
        with trace.span("inner"):
            time.sleep(0.001)
    evs = {e["name"]: e for e in trace.events()}
    assert evs["outer"]["depth"] == 0
    assert evs["inner"]["depth"] == 1
    assert evs["outer"]["dur"] >= evs["inner"]["dur"] > 0
    # time containment: inner inside outer
    assert evs["inner"]["ts"] >= evs["outer"]["ts"]
    assert (evs["inner"]["ts"] + evs["inner"]["dur"]
            <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-9)
    assert evs["outer"]["attrs"] == {"k": "v"}


def test_span_annotate_merges_attrs():
    trace.enable()
    with trace.span("s", attrs={"a": 1}) as sp:
        sp.annotate(b=2)
    (ev,) = trace.events()
    assert ev["attrs"] == {"a": 1, "b": 2}


def test_traced_decorator_checks_enabled_at_call_time():
    @trace.traced("deco:fn")
    def fn(x):
        return x + 1

    assert fn(1) == 2          # disabled: plain call, nothing recorded
    assert trace.events() == []
    trace.enable()
    assert fn(2) == 3
    assert [e["name"] for e in trace.events()] == ["deco:fn"]


def test_thread_safety_each_thread_owns_its_buffer():
    trace.enable()
    n, workers = 200, 4

    def work(i):
        for _ in range(n):
            with trace.span(f"w{i}"):
                pass

    threads = [threading.Thread(target=work, args=(i,), name=f"tw{i}")
               for i in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = trace.events()
    per_name = {}
    for e in evs:
        per_name[e["name"]] = per_name.get(e["name"], 0) + 1
        # every w<i> span sits on thread tw<i> — no cross-thread bleed
        if e["name"].startswith("w"):
            assert e["thread"] == "tw" + e["name"][1:]
    assert all(per_name[f"w{i}"] == n for i in range(workers))


def test_ring_buffer_bounds_memory_and_counts_drops():
    t = SpanTracer(capacity=16)
    t.enable()
    for i in range(50):
        t.instant(f"e{i}")
    evs = t.events()
    assert len(evs) == 16
    assert t.dropped_events() == 34
    # the ring keeps the NEWEST events
    assert evs[-1]["name"] == "e49"


def test_live_spans_shows_open_stack():
    trace.enable()
    with trace.span("phase_a", attrs={"step": 3}):
        with trace.span("phase_b"):
            stacks = trace.live_spans()
            (stack,) = stacks.values()
            assert [s["name"] for s in stack] == ["phase_a", "phase_b"]
            assert stack[0]["attrs"] == {"step": 3}
            assert all(s["elapsed_seconds"] >= 0 for s in stack)
    assert trace.live_spans() == {}


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------
def test_perfetto_export_is_valid_and_loadable(tmp_path):
    trace.enable()
    with trace.span("step", attrs={"step": 1}, cat="step"):
        with trace.span("dispatch", cat="jit"):
            pass
    trace.instant("collective:grad_reduce",
                  {"bytes": 1024, "quantized": True}, cat="comms")
    trace.async_begin("request", 7, {"prompt_tokens": 3})
    trace.async_end("request", 7)
    path = tmp_path / "t.perfetto.json"
    doc = trace.to_perfetto(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] == json.loads(json.dumps(
        doc["traceEvents"], default=str))
    evs = loaded["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert {"X", "i", "b", "e", "M"} <= phs
    for e in evs:
        assert "name" in e and "ph" in e and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["ts"], float)
        if e["ph"] in ("b", "e"):
            assert e["id"] == "7"
    # thread metadata names the recording thread
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"]


def test_jsonl_roundtrips_through_trace_report(tmp_path):
    import tools.trace_report as tr

    trace.enable()
    for _ in range(3):
        with trace.span("step", cat="step"):
            with trace.span("train_step", cat="step"):
                time.sleep(0.001)
    p = tmp_path / "t.jsonl"
    n = trace.dump_jsonl(str(p))
    assert n == len(trace.events()) + 1  # + meta line
    events = tr.load_trace(str(p))
    totals = tr.phase_totals(events)
    assert totals["step"]["count"] == 3
    assert totals["train_step"]["count"] == 3
    # perfetto form parses to the same totals (µs -> s)
    p2 = tmp_path / "t.perfetto.json"
    trace.to_perfetto(str(p2))
    totals2 = tr.phase_totals(tr.load_trace(str(p2)))
    assert totals2["step"]["count"] == 3
    np.testing.assert_allclose(totals2["step"]["seconds"],
                               totals["step"]["seconds"], rtol=1e-3)


def test_trace_report_exits_1_on_malformed(tmp_path, capsys):
    import tools.trace_report as tr

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert tr.main([str(bad)]) == 1
    assert "malformed" in capsys.readouterr().err
    # an X event without dur is malformed too (CI trace-integrity gate)
    bad2 = tmp_path / "bad2.jsonl"
    bad2.write_text(json.dumps(
        {"ph": "X", "name": "s", "ts": 0.0}) + "\n")
    assert tr.main([str(bad2)]) == 1
    # a valid trace exits 0
    trace.enable()
    with trace.span("ok"):
        pass
    good = tmp_path / "good.jsonl"
    trace.dump_jsonl(str(good))
    assert tr.main([str(good)]) == 0


def test_trace_report_diff_ranks_phase_growth(tmp_path, capfd):
    import tools.trace_report as tr

    def mk(name, secs):
        p = tmp_path / name
        lines = [json.dumps({"ph": "meta"})]
        for phase, s in secs.items():
            lines.append(json.dumps(
                {"ph": "X", "name": phase, "ts": 0.0, "dur": s}))
        p.write_text("\n".join(lines) + "\n")
        return str(p)

    old = mk("old.jsonl", {"fwd": 1.0, "bwd": 2.0})
    new = mk("new.jsonl", {"fwd": 1.0, "bwd": 3.5, "extra": 0.5})
    assert tr.main([old, new]) == 0
    out = capfd.readouterr().out
    lines = [l for l in out.splitlines() if l.strip().startswith(("bwd",
                                                                  "fwd",
                                                                  "extra"))]
    assert lines[0].strip().startswith("bwd")   # biggest growth first
    assert "new phase" in out


def test_span_seconds_mirror_into_registry_and_report():
    """Completed spans mirror into trace_span_seconds{span} while the
    registry is enabled — the telemetry_report -- trace -- section."""
    import io

    import tools.telemetry_report as trep

    telemetry.enable()
    trace.enable()
    with trace.span("mirrored_phase"):
        pass
    snap = telemetry.snapshot()
    series = snap["histograms"]["trace_span_seconds"]
    assert any("mirrored_phase" in labels for labels in series)
    buf = io.StringIO()
    trep.print_snapshot(snap, out=buf)
    out = buf.getvalue()
    assert "-- trace (span wall seconds by name) --" in out
    assert "mirrored_phase" in out


# ---------------------------------------------------------------------------
# jit integration: build-phase + dispatch spans
# ---------------------------------------------------------------------------
def _tiny_step(seed=7):
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep

    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=model.parameters())

    def train_fn(x, y):
        return nn.functional.mse_loss(model(x), y)

    return model, opt, TrainStep(model, train_fn, opt)


def test_train_step_trace_has_build_phases_and_dispatch_cost():
    trace.enable()
    _, _, step = _tiny_step()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((16, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32))
    with trace.span("step", cat="step"):
        step(x, y)
    names = [e["name"] for e in trace.events()]
    for expect in ("jit:trace", "jit:lower", "jit:compile",
                   "train_step", "dispatch"):
        assert expect in names, (expect, names)
    disp = [e for e in trace.events() if e["name"] == "dispatch"][-1]
    assert disp["attrs"] == {
        "function": step._compile_label()}


# ---------------------------------------------------------------------------
# serving request trees
# ---------------------------------------------------------------------------
def test_serving_request_tree_shape():
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=96, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=128,
                      dropout=0.0)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    trace.enable()
    eng = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                   max_seq_len=64, max_new_tokens=4)
    rng = np.random.default_rng(0)
    r0 = eng.submit(rng.integers(1, 96, (5,)).tolist())
    r1 = eng.submit(rng.integers(1, 96, (3,)).tolist())
    done = eng.run_until_complete()
    assert sorted(done) == [r0, r1]
    trees = trace.request_trees()
    assert sorted(trees) == [r0, r1]
    for rid, root in trees.items():
        # the anatomy chain: request{queue, prefill} + admitted/
        # first_token marks — TTFT decomposes instead of being one
        # histogram sample
        assert root["name"] == "request"
        assert root["end"] is not None, "request span must close"
        children = {c["name"] for c in root["children"]}
        assert {"queue", "prefill"} <= children
        marks = {m["name"] for m in root["marks"]}
        assert {"admitted", "first_token"} <= marks
        q = next(c for c in root["children"] if c["name"] == "queue")
        p = next(c for c in root["children"] if c["name"] == "prefill")
        assert root["start"] <= q["start"] <= q["end"] <= p["end"]
        assert p["end"] <= root["end"]
        assert root["attrs"]["prompt_tokens"] in (5, 3)
        assert root["attrs"]["generated_tokens"] == 4
    # the tick's phases land as sync spans on the engine thread
    names = {e["name"] for e in trace.events()}
    assert {"decode_tick", "admission", "prefill_group"} <= names


# ---------------------------------------------------------------------------
# watchdog debris
# ---------------------------------------------------------------------------
def test_watchdog_debris_carries_live_span_stacks(tmp_path):
    from paddle_tpu.resilience import HangWatchdog

    trace.enable()
    wd = HangWatchdog(str(tmp_path), min_hang_seconds=9999)
    with trace.span("train_step", attrs={"model": "M"}, cat="step"):
        with trace.span("dispatch", cat="jit"):
            path = wd.dump_debris(step=5, elapsed=12.0, limit=6.0)
    payload = json.loads(open(path).read())
    stacks = payload["trace_spans"]
    (stack,) = stacks.values()
    assert [s["name"] for s in stack] == ["train_step", "dispatch"]
    assert stack[0]["attrs"] == {"model": "M"}
    # the pre-existing debris fields survive alongside
    assert payload["step"] == 5 and "threads" in payload


def test_watchdog_debris_empty_spans_when_tracer_off(tmp_path):
    from paddle_tpu.resilience import HangWatchdog

    wd = HangWatchdog(str(tmp_path), min_hang_seconds=9999)
    path = wd.dump_debris(step=1, elapsed=2.0, limit=1.0)
    assert json.loads(open(path).read())["trace_spans"] == {}


# ---------------------------------------------------------------------------
# collectives instants (plan-labeled spans)
# ---------------------------------------------------------------------------
def test_note_grad_reduce_emits_labeled_collective_instants():
    from paddle_tpu.distributed import collectives as coll
    from paddle_tpu.distributed.collectives.overlap import (GradBucket,
                                                            GradReducePlan)

    plan = GradReducePlan(
        axes=("dp",), nranks=4,
        buckets=(GradBucket(("w1", "w2"), (1024, 2048), "float32", True),
                 GradBucket(("norm",), (64,), "float32", False)))
    trace.enable()
    coll.note_grad_reduce(plan)
    evs = [e for e in trace.events()
           if e["name"] == "collective:grad_reduce"]
    assert len(evs) == 2
    by_bucket = {e["attrs"]["bucket"]: e["attrs"] for e in evs}
    assert by_bucket[0]["quantized"] is True
    assert by_bucket[0]["bytes"] == (1024 + 2048) * 4
    assert by_bucket[0]["axis"] == "dp"
    assert by_bucket[1]["quantized"] is False
    assert by_bucket[1]["bytes"] == 64 * 4


def test_note_zero_step_emits_gather_and_rs_instants():
    from paddle_tpu.distributed import collectives as coll
    from paddle_tpu.distributed.collectives.zero import ZeroParam, ZeroPlan

    plan = ZeroPlan(
        stage=3, axes=("sharding",), shard_axis="sharding",
        shard_degree=4, nranks=4,
        params=(ZeroParam("wq", "dim", (8, 64, 64), "float32",
                          8 * 64 * 64, shard_dim=1),
                ZeroParam("bias", "flat", (128,), "float32", 128,
                          quantized=False, padded=128),
                ZeroParam("scale", "replicated", (4,), "float32", 4)))
    trace.enable()
    coll.note_zero_step(plan)
    names = [e["name"] for e in trace.events()]
    assert names.count("collective:param_gather") == 2  # dim + flat
    assert names.count("collective:grad_rs") == 2       # dim AD + flat
    assert names.count("collective:grad_reduce") == 1   # replicated psum
    dim_g = next(e for e in trace.events()
                 if e["name"] == "collective:param_gather"
                 and e["attrs"]["param"] == "wq")
    assert dim_g["attrs"]["bytes"] == 8 * 64 * 64 * 4
    assert dim_g["attrs"]["axis"] == "sharding"


def test_sharded_step_emits_collective_instants_per_step():
    """End-to-end: a ShardedTrainStep with an engaged GradReducePlan
    emits one labeled collective instant per bucket per executed step —
    the acceptance's 'collectives visible as labeled spans'."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.parallel_step import ShardedTrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_fleet_mesh()
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=32, dropout=0.0,
                    recompute=True)
    m = GPTForCausalLMPipe(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=m.parameters())
    step = ShardedTrainStep(m, lambda a, b: m.loss(a, b), opt, mesh)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 256, (8, 16)).astype(np.int32))
    lab = paddle.to_tensor(rng.integers(0, 256, (8, 16)).astype(np.int64))
    trace.enable()
    step(ids, lab)
    plan = step.comms_plan()
    if plan is None:
        pytest.skip("reduce plan declined on this mesh/runtime")
    evs = [e for e in trace.events()
           if e["name"] == "collective:grad_reduce"]
    assert len(evs) == plan.calls
    assert all(e["attrs"]["axis"] == plan.axis_label for e in evs)
    assert {e["attrs"]["bucket"] for e in evs} == set(range(plan.calls))
    # a second step emits a second round of instants
    trace.reset()
    step(ids, lab)
    evs2 = [e for e in trace.events()
            if e["name"] == "collective:grad_reduce"]
    assert len(evs2) == plan.calls
    assert "train_step" in {e["name"] for e in trace.events()}


# ---------------------------------------------------------------------------
# ISSUE 27: heartbeat, epoch, compiles as spans, the serving tick's phases
# ---------------------------------------------------------------------------
HEARTBEAT = "ptpu-trace-heartbeat"


def _heartbeat_threads():
    return [t for t in threading.enumerate() if t.name == HEARTBEAT]


def test_heartbeat_runs_only_while_enabled_and_reset_clears_beats():
    assert _heartbeat_threads() == [] and trace.beats() == []
    trace.enable()
    trace.enable()   # idempotent: still one thread
    assert len(_heartbeat_threads()) == 1
    deadline = time.perf_counter() + 5.0
    while len(trace.beats()) < 3 and time.perf_counter() < deadline:
        time.sleep(0.01)
    beats = trace.beats()
    assert len(beats) >= 3
    # (seconds since the epoch a beat was due, seconds late), 20 ms apart
    assert all(ts > 0 and late > -1e-3 for ts, late in beats)
    assert beats[1][0] - beats[0][0] >= 0.02 - 1e-9
    # a heartbeat is never an X event: gap attribution does not see it
    assert [e for e in trace.events() if e["ph"] == "X"] == []
    trace.reset()
    assert len(trace.beats()) <= 1
    trace.disable()
    assert _heartbeat_threads() == []
    assert trace.span("x") is trace.span("y")   # the shared no-op again


def test_heartbeat_reports_a_planted_stall_with_its_deltas(monkeypatch):
    """Clock, sleep and the usage probe are injected: the third wait
    'returns' 1.5 s late, as if the process had stood still."""
    from paddle_tpu.telemetry.trace import _Heartbeat

    tr = SpanTracer()
    tr.enable()
    now = [100.0]
    waits = []

    def wait(timeout):
        waits.append(timeout)
        if len(waits) > 5:
            return True                       # stop
        now[0] += timeout + (1.5 if len(waits) == 3 else 0.0)
        return False

    usage = iter([(1.0, 2.0, 10, 0), (1.0, 2.0, 10, 0), (1.0, 2.0, 10, 0),
                  (1.25, 3.2, 17, 2), (1.25, 3.2, 17, 2),
                  (1.25, 3.2, 17, 2)])
    monkeypatch.setattr(_Heartbeat, "_usage",
                        staticmethod(lambda: next(usage)))
    t0 = time.perf_counter()
    _Heartbeat(tr, clock=lambda: now[0], wait=wait).run()
    assert time.perf_counter() - t0 < 1.0     # no real waiting
    beats = list(tr._beats)
    assert len(beats) == 5
    assert [round(late, 6) for _, late in beats] == [0, 0, 1.5, 0, 0]
    assert round(beats[2][0], 6) == 100.06    # when the late beat was due
    # the beats missed during the stall are not replayed
    assert round(beats[3][0] - beats[2][0], 6) == 1.52
    stalls = [e for e in tr.events() if e["name"] == "host_stall"]
    assert len(stalls) == 1 and stalls[0]["ph"] == "i"
    assert stalls[0]["attrs"] == {
        "late_ms": 1500.0, "cpu_ms": 250.0, "run_delay_ms": 1200.0,
        "invol_switches": 7, "major_faults": 2}


def test_heartbeat_usage_probe_reads_this_process():
    from paddle_tpu.telemetry.trace import _Heartbeat, _stall_attrs

    cpu, delay, switches, faults = _Heartbeat._usage()
    assert cpu > 0 and switches >= 0 and faults >= 0
    assert delay is None or delay >= 0       # None where /proc lacks it
    # a missing reading stays missing instead of raising
    attrs = _stall_attrs(0.1, (1.0, None, 1, 0), (1.5, None, 2, 0))
    assert attrs["run_delay_ms"] is None and attrs["cpu_ms"] == 500.0


def test_epoch_is_the_base_of_event_timestamps():
    trace.enable()
    trace.reset()
    t = time.perf_counter()
    trace.complete("known", t, 0.25)
    (ev,) = [e for e in trace.events() if e["name"] == "known"]
    assert abs(trace.epoch() + ev["ts"] - t) < 1e-9
    first = trace.epoch()
    trace.reset()
    assert trace.epoch() > first


def test_ptpu_trace_env_goes_through_enable():
    """PTPU_TRACE=1 at import starts the heartbeat too (it used to set
    the attribute only)."""
    import os
    import subprocess
    import sys

    code = ("import threading; from paddle_tpu.telemetry import trace; "
            "print(trace.enabled(), sum(t.name == '%s' for t in "
            "threading.enumerate()))" % HEARTBEAT)
    env = dict(os.environ, PTPU_TRACE="1", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=300)
    assert out.stdout.split()[-2:] == ["True", "1"], out.stderr[-2000:]


def _fresh_jit(k):
    """A program jax has not compiled before in this process."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x: jnp.tanh(x * k) + k)(jnp.ones((3, 5)))


def test_xla_compile_span_only_while_the_tracer_is_on():
    _fresh_jit(27.125)                        # tracer off: nothing recorded
    assert trace.events() == []
    trace.enable()
    trace.reset()
    t0 = time.perf_counter()
    _fresh_jit(27.25)
    t1 = time.perf_counter()
    spans = [e for e in trace.events() if e["name"] == "xla_compile"]
    assert spans, [e["name"] for e in trace.events()]
    for e in spans:
        assert e["ph"] == "X" and e["cat"] == "jit"
        assert e["attrs"]["event"].endswith("backend_compile_duration")
        assert abs(e["attrs"]["seconds"] - e["dur"]) < 1e-5
        # the span ends when jax reported it, inside the call that compiled
        assert t0 <= trace.epoch() + e["ts"] + e["dur"] <= t1
    trace.disable()
    n = len(trace.events())
    _fresh_jit(27.375)
    assert len(trace.events()) == n


# a step launches its tick and THEN fetches and emits the tick before it
# (docs/SERVING.md "The step's order"); one with nothing to launch fetches
# what is in flight and retires once more what that finished
TICK_PHASES = ["retire", "admission", "prefill_tick", "grow_pages",
               "decode_build", "decode_upload", "decode_tick",
               "decode_fetch", "emit"]
NESTED = {"prefill_tick": ["prefill_build", "prefill_launch",
                           "first_token_fetch"],
          "decode_tick": ["decode_launch"]}


def _chunked_engine():
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=96, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=128,
                      dropout=0.0)
    paddle.seed(0)
    return ContinuousBatchingEngine(
        LlamaForCausalLM(cfg), max_slots=2, page_size=16, max_seq_len=64,
        max_new_tokens=4, prefill_chunk=8)


def _run_three_requests(eng):
    """Three prompts through two slots, so one waits for a slot; returns
    the engine's own request objects."""
    rng = np.random.default_rng(0)
    for n in (19, 5, 11):
        eng.submit(rng.integers(1, 96, (n,)).tolist())
    reqs = list(eng._waiting)
    done = eng.run_until_complete()
    assert sorted(done) == [r.rid for r in reqs]
    return reqs


def _children(events, parent):
    """X events directly under ``parent``: same thread, one level deeper,
    inside its time; in start order."""
    lo, hi = parent["ts"], parent["ts"] + parent["dur"]
    kids = [e for e in events
            if e["ph"] == "X" and e["tid"] == parent["tid"]
            and e["depth"] == parent["depth"] + 1
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-9]
    return sorted(kids, key=lambda e: e["ts"])


def test_engine_step_spans_hold_the_named_phases_in_order():
    eng = _chunked_engine()
    trace.enable()
    trace.reset()
    _run_three_requests(eng)
    events = trace.events()
    steps = [e for e in events if e["name"] == "engine_step"]
    assert len(steps) == eng._tick
    assert [e["attrs"]["tick"] for e in steps] == list(
        range(1, eng._tick + 1))
    full = 0
    for step in steps:
        kids = _children(events, step)
        names = [k["name"] for k in kids]
        if "decode_tick" not in names and "decode_fetch" in names:
            # the pipeline drained: the last tokens' requests retire now
            assert names[-1] == "retire", names
            names = names[:-1]
        # the phases that ran this tick, in the tick's order, none twice
        assert names == [p for p in TICK_PHASES if p in names], names
        assert names[:2] == ["retire", "admission"]
        # a fetch is of a tick launched in an EARLIER step
        assert ("decode_fetch" in names) == ("emit" in names)
        full += names == TICK_PHASES
        # together they cover the tick but for a few statements between
        assert sum(k["dur"] for k in kids) <= step["dur"]
        for kid in kids:
            inner = [g["name"] for g in _children(events, kid)]
            want = NESTED.get(kid["name"], [])
            assert inner == [p for p in want if p in inner], (kid, inner)
            if kid["name"] == "decode_tick":
                assert inner == NESTED["decode_tick"]
                attrs = kid["attrs"]
                assert attrs["live"] >= 1 and attrs["ticks"] == 1
                assert attrs["discarded"] == 0
                # launched ahead exactly when this step also fetches
                assert attrs["ahead"] == ("decode_fetch" in names)
    assert full >= 1, "no tick both prefilled and decoded"
    launched = [e for e in events if e["name"] == "prefill_tick"
                and e["attrs"]]
    assert launched and all(
        [g["name"] for g in _children(events, e)][:2]
        == ["prefill_build", "prefill_launch"] for e in launched)
    assert "first_token_fetch" in {e["name"] for e in events}
    assert "detokenize" not in {e["name"] for e in events}


def test_first_token_marks_split_ttft_and_name_their_tick():
    eng = _chunked_engine()
    trace.enable()
    trace.reset()
    reqs = _run_three_requests(eng)
    events = trace.events()
    ticks = {e["attrs"]["tick"] for e in events
             if e["name"] == "engine_step"}
    marks = {e["id"]: e["attrs"] for e in events
             if e["ph"] == "n" and e["name"] == "first_token"}
    admitted = {e["id"]: e["attrs"] for e in events
                if e["ph"] == "n" and e["name"] == "admitted"}
    assert sorted(marks) == sorted(r.rid for r in reqs)
    for r in reqs:
        m = marks[r.rid]
        ttft = r.first_token_t - r.submit_t
        assert abs((m["queue_ms"] + m["prefill_ms"]) / 1e3 - ttft) < 1e-6
        assert abs(m["queue_ms"] / 1e3 - r.queue_s) < 1e-9
        assert r.submit_t <= r.admit_t <= r.first_token_t
        assert abs(r.queue_s - (r.admit_t - r.submit_t)) < 1e-9
        assert m["queue_ms"] >= 0 and m["prefill_ms"] > 0
        assert m["tick"] in ticks and admitted[r.rid]["tick"] in ticks
        assert admitted[r.rid]["tick"] <= m["tick"]
    # the third request waited for a slot: admitted ticks after the others
    waited = max(reqs, key=lambda r: r.queue_s)
    assert admitted[waited.rid]["tick"] > 1
    assert waited.queue_s > min(r.queue_s for r in reqs)


def test_prefill_tick_attrs_sum_to_the_prompts_tokens():
    eng = _chunked_engine()
    trace.enable()
    trace.reset()
    reqs = _run_three_requests(eng)
    spans = [e for e in trace.events() if e["name"] == "prefill_tick"]
    launched = [e["attrs"] for e in spans if e["attrs"]]
    assert len(launched) == eng.prefill_chunk_steps < len(spans)
    assert sum(a["valid_tokens"] for a in launched) == sum(
        len(r.prompt) for r in reqs) == 35
    # a pass is as wide as the ladder's step over its rows (1 or 2 of
    # two slots), and computes that many rows of a chunk
    assert all(a["pass_rows"] == a["rows"] for a in launched)
    assert all(a["computed_tokens"] == a["pass_rows"] * 8 for a in launched)
    assert {a["pass_rows"] for a in launched} == {1, 2}
    assert all(1 <= a["rows"] <= 2 for a in launched)
    assert all(a["valid_tokens"] <= a["rows"] * 8 for a in launched)


def test_queue_seconds_sum_over_a_requeue():
    """A preempted request waits twice; its queue seconds are the sum and
    its first_token mark still splits the TTFT exactly."""
    eng = _chunked_engine()
    eng.submit([1, 2, 3, 4, 5])
    (req,) = list(eng._waiting)
    eng._admit()
    first_wait = req.queue_s
    assert first_wait > 0 and req.admit_t is not None
    eng._preempt(eng._slots.index(req))
    assert req.queued_t >= req.admit_t
    trace.enable()
    trace.reset()
    eng.run_until_complete()
    assert req.queue_s > first_wait
    (mark,) = [e["attrs"] for e in trace.events()
               if e["name"] == "first_token"]
    ttft = req.first_token_t - req.submit_t
    assert abs((mark["queue_ms"] + mark["prefill_ms"]) / 1e3 - ttft) < 1e-6
    assert abs(mark["queue_ms"] / 1e3 - req.queue_s) < 1e-9


def test_engine_run_with_the_tracer_off_touches_no_buffer():
    eng = _chunked_engine()
    before = [(b, len(b.ring), b.dropped)
              for b in trace.get_tracer()._snapshot_bufs()]
    reqs = _run_three_requests(eng)
    after = [(b, len(b.ring), b.dropped)
             for b in trace.get_tracer()._snapshot_bufs()]
    assert after == before and trace.events() == []
    assert trace.beats() == [] and _heartbeat_threads() == []
    # the split is kept on the request all the same
    assert all(r.queue_s >= 0 and r.admit_t is not None for r in reqs)
    assert eng._tick >= 1
