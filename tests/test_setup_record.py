"""Where a warm set-up goes (docs/TELEMETRY.md "The set-up record"): the
serving engine and the train step keep each program's trace, lowering, load
and first-run seconds whether the tracer is on or off, and publish them once,
as one ``setup`` instant, at their first traced step after warming."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as pjit
from paddle_tpu.jit import SetupRecord
from paddle_tpu.telemetry import trace

PHASES = ("trace_s", "lower_s", "load_s", "run_s")


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def setups():
    return [e for e in trace.events() if e["name"] == "setup"]


@pytest.fixture(scope="module")
def warmed():
    """A chunked-prefill engine warmed with the tracer off, and with the
    HLO module's serialization refusing: the engine must not pay it."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    def refuse(lowered):
        raise AssertionError("the serving engine serialized an HLO module")

    cfg = LlamaConfig(vocab_size=96, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=128,
                      dropout=0.0)
    paddle.seed(0)
    trace.disable()
    eng = ContinuousBatchingEngine(
        LlamaForCausalLM(cfg), max_slots=4, page_size=16, num_pages=40,
        max_new_tokens=4, prefill_chunk=16)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pjit, "_serialized_hlo_bytes", refuse)
        eng.warmup()
    return eng


def test_engine_keeps_one_row_for_each_program_it_warmed(warmed):
    rec = warmed.setup_record
    want = ({"decode" if b == warmed.max_slots else f"decode_r{b}"
             for b in warmed._decode_rows}
            | {"prefill" if b == warmed.max_slots else f"prefill_r{b}"
               for b in warmed._pass_rows}
            | {"first_token"})
    assert set(rec.programs) == want
    for row in rec.programs.values():
        assert len(row) == 4 and all(s >= 0.0 for s in row)
    # first_token is timed like the others, yet holds no pool
    assert "first_token" not in warmed.program_bytes
    assert set(warmed.program_bytes) == want - {"first_token"}
    # the rows are inside warmup()'s wall time, which ends at warm_end
    total = sum(sum(row) for row in rec.programs.values())
    assert 0.0 < total <= warmed.build_seconds
    assert rec.warm_end <= time.perf_counter()
    assert not rec.published


def test_engine_builds_never_serialize_the_hlo_module(warmed):
    """The fixture warmed with _serialized_hlo_bytes refusing; what the
    jit layer keeps of each engine build has no program size."""
    for name in warmed.setup_record.programs:
        rec = pjit.compile_summary(name)
        assert rec is not None and rec["hlo_program_bytes"] is None


def test_engine_steps_untraced_publish_nothing(warmed):
    rid = warmed.submit([5, 6, 7])
    for _ in range(3):
        warmed.step()
    warmed.cancel(rid)
    warmed.step()
    assert trace.events() == []
    assert not warmed.setup_record.published


def test_engine_publishes_one_setup_instant_at_its_first_traced_step(
        warmed):
    rec = warmed.setup_record
    assert not rec.published
    trace.enable()
    warmed.step()
    (ev,) = setups()
    assert ev["ph"] == "i" and ev["cat"] == "setup"
    attrs = ev["attrs"]
    assert attrs["owner"] == "engine"
    assert attrs["programs"] == len(rec.programs)
    assert attrs["by_program"] == rec.programs
    for i, key in enumerate(PHASES):
        assert attrs[key] == pytest.approx(
            sum(row[i] for row in attrs["by_program"].values()))
    assert attrs["after_warm_s"] >= 0.0
    # ... before the step it was published by
    step = [e for e in trace.events() if e["name"] == "engine_step"][-1]
    assert ev["ts"] <= step["ts"] + step["dur"]
    # a second traced step publishes nothing
    warmed.step()
    warmed.step()
    assert len(setups()) == 1
    # nor does the next trace
    trace.reset()
    warmed.step()
    assert setups() == []


def test_engine_that_never_warmed_publishes_nothing():
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                      num_heads=2, num_kv_heads=1, max_seq_len=64,
                      dropout=0.0)
    paddle.seed(1)
    eng = ContinuousBatchingEngine(LlamaForCausalLM(cfg), max_slots=2,
                                   page_size=16, num_pages=8,
                                   max_new_tokens=2, prefill_chunk=16)
    trace.enable()
    eng.step()
    eng.step()
    assert setups() == []
    assert eng.setup_record.programs == {}


def _tiny_step(seed=3):
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep

    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=model.parameters())

    def train_fn(x, y):
        return nn.functional.mse_loss(model(x), y)

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((16, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32))
    return TrainStep(model, train_fn, opt), x, y


def test_train_step_publishes_its_build_at_its_first_traced_call():
    step, x, y = _tiny_step()
    rec = step.setup_record
    assert rec.warm_end is None
    step(x, y)
    step(x, y)                        # one signature: one build
    (name,) = rec.programs
    assert name == step._compile_label()
    assert len(rec.programs[name]) == 3   # no run_s: it never blocks
    assert rec.warm_end is not None
    assert trace.events() == []
    trace.enable()
    step(x, y)
    (ev,) = setups()
    attrs = ev["attrs"]
    assert attrs["owner"] == "train_step" and attrs["programs"] == 1
    assert "run_s" not in attrs
    assert [attrs[k] for k in PHASES[:3]] == rec.programs[name]
    assert attrs["by_program"] == {name: rec.programs[name]}
    assert attrs["after_warm_s"] >= 0.0
    # the instant lies before the call's own span ends
    call = [e for e in trace.events() if e["name"] == "train_step"][-1]
    assert ev["ts"] <= call["ts"] + call["dur"]
    step(x, y)
    assert len(setups()) == 1


def test_train_step_first_traced_call_that_builds_waits_for_the_next():
    """Not warmed when the call began: the build is published by the
    next traced call, with its jit:* spans already in the trace."""
    step, x, y = _tiny_step(seed=4)
    trace.enable()
    step(x, y)
    assert setups() == []
    names = [e["name"] for e in trace.events()]
    assert {"jit:trace", "jit:lower", "jit:compile"} <= set(names)
    step(x, y)
    (ev,) = setups()
    assert ev["attrs"]["programs"] == 1


def test_record_keeps_a_second_build_under_one_name():
    rec = SetupRecord("train_step")
    rec.add("step", 1.0, 2.0, 3.0)
    rec.add("step", 0.5, 0.25, 0.125)
    rec.add("step", 0.0, 0.0, 0.0)
    assert list(rec.programs) == ["step", "step#2", "step#3"]
    assert rec.programs["step#2"] == [0.5, 0.25, 0.125]


def test_record_publishes_nothing_before_warm_end_or_untraced():
    rec = SetupRecord("engine")
    rec.add("decode", 1.0, 1.0, 1.0)
    trace.enable()
    rec.publish()                     # not warmed yet
    assert setups() == [] and not rec.published
    rec.warm_end = time.perf_counter()
    rec.publish()
    rec.publish()
    assert len(setups()) == 1
    assert setups()[0]["attrs"]["by_program"] == {"decode": [1.0, 1.0, 1.0]}


def test_build_helper_writes_its_phases_and_the_first_run_when_traced():
    """With the tracer on during set-up (PTPU_TRACE=1) one build writes
    jit:trace, jit:lower, jit:compile and a warm_run span for its first
    run, whose results come back ready; hlo_bytes=False leaves the
    program size out of the compile span."""
    import jax
    import jax.numpy as jnp

    from jax._src import monitoring

    fn = jax.jit(lambda a, k: a * 2 + k, static_argnums=(1,))
    rec = SetupRecord("engine")
    args = (jnp.arange(4.0), 3)
    compiles = []

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(seconds)

    monitoring.register_event_duration_secs_listener(on_duration)
    trace.enable()
    try:
        pjit.timed_lower_compile(fn, "double", *args, hlo_bytes=False,
                                 record=rec)
        built = len(compiles)
        out = rec.first_run(fn, *args)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    # the first run ran the executable the build compiled
    assert built == 1 and len(compiles) == 1
    np.testing.assert_array_equal(np.asarray(out), [3.0, 5.0, 7.0, 9.0])
    # (beside the xla_compile span of the tracer's compile listener)
    spans = {e["name"]: e for e in trace.events()
             if e["ph"] == "X" and e["cat"] == "jit"
             and e["name"] != "xla_compile"}
    assert set(spans) == {"jit:trace", "jit:lower", "jit:compile",
                          "warm_run"}
    for e in spans.values():
        assert e["attrs"] == {"function": "double"}
    trace_s, lower_s, load_s, run_s = rec.programs["double"]
    assert spans["warm_run"]["dur"] == run_s
    assert spans["jit:compile"]["dur"] == load_s
