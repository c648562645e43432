"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Run from the root of a checkout, on a machine with a TPU::

    python chip_smoke.py

ONE process, no arguments, no network. It drives the two main paths once,
through the entry points a user calls, at the full width of the models the
repo benchmarks (random weights from a seed):

1. kernels — every Pallas kernel on the two paths, COMPILED by Mosaic,
   against a float32 ``jax.numpy`` reference at the shapes the models use;
2. trainer — the GPT-1.3B line as bench.py builds it (AMP O2,
   ``GPTForCausalLMPipe``, factored AdamW, ``TrainStep``), batch chosen by
   ``memory.plan_train_step``, a few steps on one fixed batch;
3. server — the 16-layer h=2048 decoder of tools/serve_bench.py behind one
   in-process ``ContinuousBatchingEngine``, eight greedy requests;
4. sharded — only with more than one device: the LLaMA-arch ZeRO-3 line.

It exits non-zero — printing no result line — when ``jax.devices()[0]`` is
not a TPU (there is no CPU mode and no flag that allows one) or when any
phase fails: a phase's exception is never caught and turned into a field.
The last line of stdout is one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the line before it, ``summary: {"phases": ..., "claim": null}``, carries
the per-phase record. tests/test_chip_smoke.py calls the phase functions at tiny
sizes on the CPU by argument.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

#: flash shapes of the two training lines: (batch, seq, q heads, kv heads, d)
FLASH_SHAPES = {"mha": (1, 2048, 16, 16, 128), "gqa": (1, 2048, 32, 8, 128)}
#: (rows, hidden) of the GPT-1.3B residual stream at batch 3
RMS_SHAPE = (3 * 2048, 2048)
#: swiglu_down (rows, intermediate, hidden): GPT-1.3B and the LLaMA-arch line
SWIGLU_SHAPES = {"gpt1.3b": (3 * 2048, 5504, 2048),
                 "llama7b": (2048, 11008, 4096)}
#: paged decode (batch, q heads, kv heads, d, page, pages/seq): the serving
#: engine's tick at 16 slots, max_seq 1024
PAGED_SHAPE = (16, 16, 16, 128, 64, 16)


class CompileClock:
    """Seconds JAX spent obtaining executables (backend compile, or the
    persistent-cache read that replaced it) and the cache's hit count,
    from jax.monitoring — so each phase reports compile time apart from
    wall time."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return self.seconds, self.cache_hits, self.cache_misses

    def since(self, mark):
        return (round(self.seconds - mark[0], 2), self.cache_hits - mark[1],
                self.cache_misses - mark[2])


def _rel_err(got, ref):
    """max |got - ref| over max |ref|, in float64 on the host."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), "non-finite kernel output"
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-12))


def _sig(errs):
    return {k: float(f"{v:.2e}") for k, v in errs.items()}


def _mosaic_kernels(program_text):
    """kernel_name of every Mosaic custom call in a lowered program."""
    import re

    return set(re.findall(r'kernel_name = "([^"]+)"', program_text))


def _assert_kernels(program_text, expected, where):
    """The Mosaic kernels of a lowered program; every ``expected`` name
    must be among them (none replaced by its reference)."""
    kernels = _mosaic_kernels(program_text)
    missing = set(expected) - kernels
    assert not missing, (
        f"Mosaic custom calls missing from the {where}: {sorted(missing)} "
        f"(present: {sorted(kernels)})")
    return kernels


def _fixed_batch(vocab_size, shape):
    """One seeded (ids int32, labels int64) batch."""
    import paddle_tpu as paddle

    rng = np.random.default_rng(0)
    return tuple(
        paddle.to_tensor(rng.integers(0, vocab_size, shape).astype(dt))
        for dt in (np.int32, np.int64))


def _train(step, ids, labels, steps):
    """``steps`` optimizer steps on one batch, each ending in
    ``block_until_ready``: the losses, finite and falling."""
    losses = []
    for _ in range(steps):
        loss = step(ids, labels)
        loss._data.block_until_ready()
        losses.append(float(loss.numpy()))
        health = step.last_health
        assert health.finite and health.ok, health
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    return losses


def _randn(shape, dtype, seed):
    import jax.numpy as jnp

    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), dtype)


# ---------------------------------------------------------------- kernels

def check_flash(shape, dtype, tol, interpret=False):
    """flash fwd + bwd against float32 XLA attention (causal)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    b, s, hq, hk, d = shape
    q = _randn((b, s, hq, d), dtype, 1)
    k = _randn((b, s, hk, d), dtype, 2)
    v = _randn((b, s, hk, d), dtype, 3)
    w = _randn((b, s, hq, d), jnp.float32, 4)  # fixed cotangent direction

    def ref(q, k, v):
        rep = hq // hk
        qf, kf, vf = (jnp.swapaxes(t.astype(jnp.float32), 1, 2)
                      for t in (q, jnp.repeat(k, rep, 2),
                                jnp.repeat(v, rep, 2)))
        logits = jnp.einsum("bhsd,bhtd->bhst", qf, kf) / np.sqrt(d)
        logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits,
                           -jnp.inf)
        out = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(logits, -1), vf)
        return jnp.swapaxes(out, 1, 2)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    kern = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret))
    kern_grad = jax.jit(jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret)), argnums=(0, 1, 2)))
    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(ref)(q, k, v)
        ref_grad = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    errs = {"out": _rel_err(kern(q, k, v), ref_out)}
    for name, g, r in zip(("dq", "dk", "dv"), kern_grad(q, k, v), ref_grad):
        errs[name] = _rel_err(g, r)
    assert max(errs.values()) < tol, errs
    return errs


def check_rms_norm(shape, dtype, tol, interpret=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.rms_norm import rms_norm

    x = _randn(shape, dtype, 5)
    w = _randn(shape[-1:], dtype, 6)
    ct = _randn(shape, jnp.float32, 17)  # fixed cotangent direction

    def ref(x, w):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return xf * jax.lax.rsqrt(var + 1e-6) * w.astype(jnp.float32)

    def loss(fn):  # linear in the output: the grads compare the VJP itself
        return lambda x, w: jnp.sum(fn(x, w).astype(jnp.float32) * ct)

    kern = lambda x, w: rms_norm(x, w, 1e-6, interpret=interpret)  # noqa: E731
    errs = {"out": _rel_err(jax.jit(kern)(x, w), jax.jit(ref)(x, w))}
    got = jax.jit(jax.grad(loss(kern), argnums=(0, 1)))(x, w)
    want = jax.jit(jax.grad(loss(ref), argnums=(0, 1)))(x, w)
    errs["dx"], errs["dw"] = (_rel_err(g, r) for g, r in zip(got, want))
    assert max(errs.values()) < tol, errs
    return errs


def check_swiglu_down(shape, dtype, tol, interpret=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.swiglu_down import swiglu_down

    rows, m, h = shape
    g = _randn((rows, m), dtype, 10)
    u = _randn((rows, m), dtype, 11)
    wd = (_randn((m, h), jnp.float32, 12) / np.sqrt(m)).astype(dtype)

    def ref(g, u, wd):
        gf = g.astype(jnp.float32)
        # the kernel feeds the MXU the swiglu product in the model dtype
        ffn = (gf * jax.nn.sigmoid(gf) * u.astype(jnp.float32)).astype(dtype)
        return ffn.astype(jnp.float32) @ wd.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(g, u, wd)
    got = jax.jit(lambda g, u, wd: swiglu_down(
        g, u, wd, interpret=interpret))(g, u, wd)
    errs = {"out": _rel_err(got, want)}
    assert errs["out"] < tol, errs
    return errs


def _paged_inputs(shape, dtype):
    import jax.numpy as jnp

    b, hq, hkv, d, page, pps = shape
    num_pages = b * pps + 3
    k_pages = _randn((hkv, num_pages, page, d), dtype, 13)
    v_pages = _randn((hkv, num_pages, page, d), dtype, 14)
    rng = np.random.default_rng(15)
    tables = rng.permutation(num_pages)[: b * pps].reshape(b, pps)
    lengths = rng.integers(1, page * pps + 1, (b,))
    lengths[0], lengths[-1] = page * pps, 1  # full and single-token rows
    q = _randn((b, hq, d), dtype, 16)
    return (q, k_pages, v_pages, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


def _paged_reference(q, k_pages, v_pages, tables, lengths):
    """Gather each sequence's pages densely, masked softmax in float32."""
    import jax
    import jax.numpy as jnp

    b, hq, d = q.shape
    hkv, _, page, _ = k_pages.shape
    s = tables.shape[1] * page

    def dense(pages):  # [Hkv, P, page, D] -> [B, Hq, S, D]
        g = pages.astype(jnp.float32)[:, tables]  # [Hkv, B, pps, page, D]
        g = jnp.swapaxes(g, 0, 1).reshape(b, hkv, s, d)
        return jnp.repeat(g, hq // hkv, axis=1)

    with jax.default_matmul_precision("highest"):
        logits = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32),
                            dense(k_pages)) / np.sqrt(d)
        valid = jnp.arange(s)[None, None, :] < lengths[:, None, None]
        probs = jax.nn.softmax(jnp.where(valid, logits, -1e30), -1)
        return jnp.einsum("bht,bhtd->bhd", probs, dense(v_pages))


def check_paged_attention(shape, dtype, tol, interpret=False):
    import jax

    from paddle_tpu.ops.pallas.decode_attention import paged_attention

    q, kp, vp, tables, lengths = _paged_inputs(shape, dtype)
    got = jax.jit(lambda *a: paged_attention(*a, interpret=interpret))(
        q, kp, vp, tables, lengths)
    errs = {"out": _rel_err(got, _paged_reference(q, kp, vp, tables,
                                                  lengths))}
    assert errs["out"] < tol, errs
    return errs


def check_paged_attention_int8(shape, dtype, tol, interpret=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.memory import quantize_rows_int8
    from paddle_tpu.ops.pallas.decode_attention import paged_attention_int8

    q, kp, vp, tables, lengths = _paged_inputs(shape, dtype)
    kq, ks = quantize_rows_int8(kp)
    vq, vs = quantize_rows_int8(vp)
    got = jax.jit(lambda *a: paged_attention_int8(*a, interpret=interpret))(
        q, kq, ks, vq, vs, tables, lengths)
    want = _paged_reference(q, kq.astype(jnp.float32) * ks,
                            vq.astype(jnp.float32) * vs, tables, lengths)
    errs = {"out": _rel_err(got, want)}
    assert errs["out"] < tol, errs
    return errs


def kernel_phase(flash_shapes=FLASH_SHAPES, rms_shape=RMS_SHAPE,
                 swiglu_shapes=SWIGLU_SHAPES, paged_shape=PAGED_SHAPE,
                 dtype="bfloat16", tol=3e-2, interpret=False):
    """Every Pallas kernel of the two paths against its reference.

    ``interpret=False`` hands the kernels to Mosaic (the chip);
    tests pass ``interpret=True`` with tiny shapes.
    ``paged_attention_int8`` is off the default path: it is compiled
    too, and a refusal is REPORTED under the kernel's name (and filed in
    ROADMAP) without failing the run."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    required = [(f"flash_{n}", check_flash, s)
                for n, s in flash_shapes.items()]
    required.append(("rms_norm", check_rms_norm, rms_shape))
    required += [(f"swiglu_down_{n}", check_swiglu_down, s)
                 for n, s in swiglu_shapes.items()]
    required.append(("paged_attention", check_paged_attention, paged_shape))
    optional = [("paged_attention_int8", check_paged_attention_int8,
                 paged_shape)]
    report = {}
    for name, check, shape in required + optional:
        try:
            report[name] = _sig(check(shape, dt, tol, interpret))
        except Exception as e:
            if (name, check, shape) in required:
                raise
            # off the default path: reported, by the contract above
            reason = " ".join(str(e).split())[:300]
            report[name] = {"refused": f"{type(e).__name__}: {reason}"}
            print(f"  kernel {name}: not on the default path, refused: "
                  f"{type(e).__name__}: {reason}", flush=True)
            continue
        print(f"  kernel {name} {tuple(shape)}: rel err {report[name]}"
              + ("" if (name, check, shape) in required
                 else " (not on the default path)"), flush=True)
    return report


# ---------------------------------------------------------------- trainer

def trainer_phase(cfg=None, seq=2048, batches=(4, 3), head_chunk=16384,
                  steps=4, bf16=True, expect_kernels=(
                      "flash_fwd", "flash_bwd_fused", "rms_norm_fwd",
                      "swiglu_down_fwd")):
    """A few optimizer steps of the GPT-1.3B line through the planner and
    ``TrainStep``, on one fixed batch. Tests pass a tiny ``cfg`` with
    ``bf16=False`` and ``expect_kernels=()`` (no Mosaic on the CPU)."""
    import jax

    import bench
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import memory as pmem
    from paddle_tpu import quant as pquant
    from paddle_tpu.distributed.collectives import compose
    from paddle_tpu.jit import TrainStep

    if cfg is None:
        cfg = bench.tpu_model_config("gpt")
    paddle.seed(0)
    model = bench.build_model(cfg, bf16=bf16)
    opt = bench.build_optimizer(model)

    def train_fn(ids, labels):
        return model.loss(ids, labels)

    def configure(cand):
        pol = bench.quant_policy(cand.policy, cand.quant)
        cfg.recompute, cfg.recompute_policy = pol != "none", pol
        cfg.head_chunk = cand.head_chunk

    def step_factory(cand):
        configure(cand)
        aval = jax.ShapeDtypeStruct
        return TrainStep(model, train_fn, opt), (
            aval((cand.batch, seq), jax.numpy.int32),
            aval((cand.batch, seq), jax.numpy.int64))

    # two candidates, every one requesting the scaled GEMMs as the bench
    # does: the AOT pricing path and bytes_limit are exercised, and no
    # plan comes from a cache file an earlier code version wrote
    decision = pmem.plan_train_step(
        step_factory,
        [pmem.Candidate(b, bench.DEFAULT_POLICY, head_chunk=head_chunk,
                        quant="all") for b in batches],
        cache_path="")
    configure(decision)
    print(f"  plan: batch {decision.batch} fits={decision.fits} peak "
          f"{decision.peak_bytes / 2**30:.2f} GiB of budget "
          f"{decision.budget_bytes / 2**30:.2f} GiB; evaluated "
          f"{[(c['batch'], c.get('fits')) for c in decision.candidates]}",
          flush=True)
    assert decision.fits, decision

    step = TrainStep(model, train_fn, opt)
    ids, labels = _fixed_batch(cfg.vocab_size, (decision.batch, seq))
    kernels = _assert_kernels(step.lowered_text(ids, labels),
                              expect_kernels, "train step")
    losses = _train(step, ids, labels, steps)

    verdict = compose.last_verdicts().get("quant_gemm")
    gates = {
        "int8_head": bool(F.int8_head_enabled()),
        "scaled_gemm": {"verdict": verdict[0] if verdict else None,
                        "reason": verdict[1] if verdict else None,
                        "dtype": pquant.quant_dtype(),
                        "parity_gate": pquant.quant_gate_report()["ok"]},
        "fused_ffn": "swiglu_down_fwd" in kernels,
    }
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  losses {[round(l, 4) for l in losses]}", flush=True)
    print(f"  mosaic kernels in the step: {sorted(kernels)}", flush=True)
    print(f"  gates: {gates}", flush=True)
    # live buffers and the programs' reserved temp space are counted apart
    print(f"  memory_stats: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')}, peak_bytes_reserved "
          f"{stats.get('peak_bytes_reserved')}, bytes_limit "
          f"{stats.get('bytes_limit')}", flush=True)
    return {"batch": decision.batch, "losses": losses,
            "kernels": sorted(kernels), "gates": gates,
            "plan_peak_bytes": decision.peak_bytes,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "peak_bytes_reserved": stats.get("peak_bytes_reserved")}


# ----------------------------------------------------------------- server

def server_phase(cfg_kw=None, sizes=None, prompt_lens=(64, 128, 192, 256,
                                                      320, 384, 512),
                 bf16=True, expect_kernels=("paged_attention",)):
    """Eight greedy requests through one in-process engine at the serving
    bench's TPU geometry; the first prompt is submitted twice. Tests pass
    a tiny config and ``expect_kernels=()``."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from tools.serve_bench import build_decoder, serving_sizes

    tpu_cfg, tpu_sizes = serving_sizes(True)
    cfg_kw, sz = cfg_kw or tpu_cfg, sizes or tpu_sizes
    model = build_decoder(cfg_kw, seed=0, bf16=bf16)
    engine = ContinuousBatchingEngine(
        model, max_slots=sz["slots"], page_size=sz["page"],
        max_seq_len=sz["max_seq"], max_new_tokens=sz["max_new"],
        prefill_chunk=sz["chunk"])
    warm = engine.warmup()

    kernels = _assert_kernels(engine.decode_program_text(), expect_kernels,
                              "decode step")

    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, cfg_kw["vocab_size"], n)]
               for n in prompt_lens]
    prompts.append(list(prompts[0]))  # the twin
    rids = [engine.submit(p) for p in prompts]
    done = engine.run_until_complete()

    assert sorted(done) == sorted(rids), (sorted(done), rids)
    for rid, p in zip(rids, prompts):
        out = [int(t) for t in done[rid]]
        assert len(out) == len(p) + sz["max_new"], (rid, len(out), len(p))
        assert out[:len(p)] == p, f"request {rid}: prompt not echoed"
        assert all(0 <= t < cfg_kw["vocab_size"] for t in out), rid
    twin_a = [int(t) for t in done[rids[0]]]
    twin_b = [int(t) for t in done[rids[-1]]]
    assert twin_a == twin_b, "identical greedy prompts diverged"
    assert engine.preemptions == 0, engine.preemptions
    assert not engine.cancelled, engine.cancelled
    print(f"  warmup {warm:.1f}s; {len(rids)} requests x {sz['max_new']} new "
          f"tokens, prompts {[len(p) for p in prompts]}; twins identical; "
          f"0 preempted, 0 cancelled", flush=True)
    print(f"  mosaic kernels in the decode step: {sorted(kernels)}",
          flush=True)
    return {"requests": len(rids), "new_tokens": sz["max_new"],
            "warmup_seconds": round(warm, 2), "kernels": sorted(kernels)}


# ---------------------------------------------------------------- sharded

def sharded_phase(cfg=None, seq=2048, batch_per_device=1, steps=4,
                  bf16=True, expect_kernels=(
                      "flash_fwd", "flash_bwd_fused", "rms_norm_fwd",
                      "swiglu_down_fwd")):
    """The LLaMA-arch line's construction over every device: ZeRO-3
    through ``group_sharded_parallel`` + ``ShardedTrainStep``. Checks the
    loss, that the ZeRO plan ENGAGED with the Mosaic kernels inside its
    manual region, that every parameter sits on all devices at 1/n of its
    bytes, and that device memory is of the same order everywhere."""
    import jax

    import bench
    import paddle_tpu as paddle
    from paddle_tpu.distributed import group_sharded_parallel
    from paddle_tpu.distributed.parallel_step import ShardedTrainStep

    devices = jax.devices()
    n = len(devices)
    if cfg is None:
        cfg = bench.tpu_model_config("llama")
        cfg.recompute_policy = bench.quant_policy(bench.DEFAULT_POLICY,
                                                  "all")
    paddle.seed(0)
    model = bench.build_model(cfg, bf16=bf16)
    mesh, degree = bench.zero3_mesh()
    assert degree == n, (degree, n)
    opt = bench.build_optimizer(model, sharded_update=True)
    model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
    step = ShardedTrainStep(model, lambda i, l: model.loss(i, l), opt, mesh)

    ids, labels = _fixed_batch(cfg.vocab_size, (batch_per_device * n, seq))
    kernels = _assert_kernels(step.lowered_text(ids, labels),
                              expect_kernels, "sharded step")
    losses = _train(step, ids, labels, steps)

    plan = step.zero_plan()
    assert plan is not None, "the ZeRO plan did not engage"
    unsharded = []
    for name, p in model.named_parameters():
        shards = p._data.addressable_shards
        full = p._data.size * p._data.dtype.itemsize
        if (len({s.device for s in shards}) != n
                or any(s.data.size * s.data.dtype.itemsize * n != full
                       for s in shards)):
            unsharded.append(name)
    assert not unsharded, f"parameters not spread 1/{n} per device: " \
        f"{unsharded[:8]}"
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if all(b is not None for b in in_use):
        assert max(in_use) < 2 * min(in_use), in_use
    print(f"  {n} devices; losses {[round(l, 4) for l in losses]}; zero "
          f"plan {plan.zero_summary()}; bytes_in_use {in_use}", flush=True)
    print(f"  mosaic kernels in the sharded step: {sorted(kernels)}",
          flush=True)
    return {"devices": n, "losses": losses, "zero": plan.zero_summary(),
            "kernels": sorted(kernels), "bytes_in_use": in_use}


# ------------------------------------------------------------------- main

@contextlib.contextmanager
def training_defaults():
    """bench.py's tuned TPU settings (``bench.apply_tpu_defaults``) for the
    kernel and trainer phases only — the server then runs under the
    settings tools/serve_bench.py would give it, not the trainer's."""
    import bench

    before = set(os.environ)
    bench.apply_tpu_defaults()  # setdefault only: new keys are its own
    try:
        yield
    finally:
        for key in set(os.environ) - before:
            del os.environ[key]


def main():
    from paddle_tpu.core import native
    from paddle_tpu.device import (chip_peaks, compile_cache_dir,
                                   device_record)

    device = device_record()
    print(f"platform={device['platform']} device_kind={device['kind']!r} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax.devices()[0].platform is "
                 f"{device['platform']!r}. This script has no CPU mode.")

    cache_dir = compile_cache_dir()
    peaks, _ = chip_peaks()  # raises for a chip the table does not know
    print(f"compile cache: {cache_dir}", flush=True)
    print(f"native.available()={native.available()}; chip peaks {peaks}",
          flush=True)

    clock = CompileClock()
    phases = {}

    def run(name, fn):
        print(f"[{name}]", flush=True)
        t0, mark = time.perf_counter(), clock.mark()
        result = fn()
        gc.collect()  # the phase's model and optimizer leave the chip
        compile_s, hits, misses = clock.since(mark)
        wall = round(time.perf_counter() - t0, 2)
        phases[name] = {"ok": True, "wall_seconds": wall,
                        "compile_seconds": compile_s,
                        "compile_cache_hits": hits,
                        "compile_cache_misses": misses, "result": result}
        print(f"[{name}] passed: wall {wall}s, compile {compile_s}s "
              f"(persistent cache: {hits} hits, {misses} misses, dir "
              f"{cache_dir})", flush=True)

    with training_defaults():
        run("kernels", kernel_phase)
        run("trainer", trainer_phase)
    run("server", server_phase)
    if device["count"] > 1:
        with training_defaults():
            run("sharded", sharded_phase)
    else:
        print("sharded: skipped (1 device)", flush=True)
        phases["sharded"] = {"ok": True, "skipped": "1 device"}

    # The summary line, then the result line: the LAST line of stdout is
    # exactly {"ok", "device"} — whoever checks the run parses only that.
    print("summary: " + json.dumps({"phases": phases,
                                    "compile_cache_dir": cache_dir,
                                    "claim": None}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
