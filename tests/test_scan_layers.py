"""Scan-over-layers compilation + fused block seams (ISSUE 9,
docs/SCAN.md): shared scan body for both decoder frontends, the
PTPU_SCAN_LAYERS=0 bitwise escape hatch, depth-flat serialized-HLO size,
compile-phase telemetry, the swiglu-down seam megakernel, checkpoint
layout round-trip, planner scan-mode cache keys, and slab grad buckets.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle


@pytest.fixture(autouse=True)
def _no_ambient_mesh(monkeypatch):
    """Hex-parity tests must not depend on suite ordering: an earlier
    test's fleet.init can leave a logical mp>1 mesh active, which makes
    sdpa insert sharding-constraint ops that perturb fusion by ~1 ulp.
    These tests are about the scan machinery, not ambient meshes."""
    import paddle_tpu.distributed.fleet as fleet

    monkeypatch.setattr(fleet, "active_mesh", lambda: None)


def _hex(vals):
    return [np.float32(v).tobytes().hex() for v in vals]


def _tiny_cfg(**kw):
    from paddle_tpu.models.gpt import GPTConfig

    base = dict(vocab_size=64, hidden_size=32, num_layers=3, num_heads=2,
                max_seq_len=32, dropout=0.0)
    base.update(kw)
    return GPTConfig(**base)


def _clone_eager(cfg, init):
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import GPTForCausalLM

    m = GPTForCausalLM(cfg)
    sd = m.state_dict()
    for k in sd:
        sd[k]._data = jnp.asarray(init[k])
    return m


def _train_hex(model, ids, labels, steps=3):
    from paddle_tpu.jit import TrainStep

    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    step = TrainStep(model, lambda i, l: model.loss(i, l), opt)
    return _hex(float(step(ids, labels).numpy()) for _ in range(steps))


class TestScanParity:
    """The scanned eager path, the PTPU_SCAN_LAYERS=0 unrolled escape
    hatch, and the pre-scan per-layer module loop are float32-hex
    identical trajectories (forward, backward, AND optimizer update)."""

    @pytest.mark.parametrize("policy", ["full", "names:attn_q,ffn_gate"])
    def test_three_way_trajectory_hex_parity(self, monkeypatch, policy):
        from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel

        cfg = _tiny_cfg(recompute=True, recompute_policy=policy)
        paddle.seed(0)
        src = GPTForCausalLM(cfg)
        init = {k: np.asarray(v._data).copy()
                for k, v in src.state_dict().items()}
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
        labels = paddle.to_tensor(
            rng.integers(0, 64, (2, 8)).astype(np.int64))

        monkeypatch.delenv("PTPU_SCAN_LAYERS", raising=False)
        t_scan = _train_hex(_clone_eager(cfg, init), ids, labels)
        monkeypatch.setenv("PTPU_SCAN_LAYERS", "0")
        t_unroll = _train_hex(_clone_eager(cfg, init), ids, labels)
        # the pre-scan path: per-layer module loop (eligibility off)
        monkeypatch.setattr(GPTModel, "_shared_block_eligible",
                            lambda self, m: False)
        t_legacy = _train_hex(_clone_eager(cfg, init), ids, labels)

        assert t_scan == t_unroll, "scan vs unrolled escape hatch drifted"
        assert t_unroll == t_legacy, \
            "PTPU_SCAN_LAYERS=0 is not the pre-scan unrolled step"

    def test_gqa_scan_unroll_hex_and_legacy_close(self, monkeypatch):
        """GQA configs: scan vs the =0 escape hatch stays hex-identical;
        the legacy module loop agrees numerically (its
        ``repeat_interleave`` lowers the kv-head broadcast differently,
        reassociating backward reductions by ~1 ulp), and forwards match
        to float32 ulp noise."""
        from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel

        cfg = _tiny_cfg(hidden_size=64, num_heads=4, num_kv_heads=2)
        paddle.seed(1)
        src = GPTForCausalLM(cfg)
        init = {k: np.asarray(v._data).copy()
                for k, v in src.state_dict().items()}
        ids = paddle.to_tensor(
            np.arange(16).reshape(2, 8).astype(np.int32))
        labels = paddle.to_tensor(
            np.arange(16).reshape(2, 8).astype(np.int64))
        a = np.asarray(_clone_eager(cfg, init)(ids).numpy())
        t_scan = _train_hex(_clone_eager(cfg, init), ids, labels)
        monkeypatch.setenv("PTPU_SCAN_LAYERS", "0")
        t_unroll = _train_hex(_clone_eager(cfg, init), ids, labels)
        monkeypatch.setattr(GPTModel, "_shared_block_eligible",
                            lambda self, m: False)
        b = np.asarray(_clone_eager(cfg, init)(ids).numpy())
        t_legacy = _train_hex(_clone_eager(cfg, init), ids, labels)
        # step 1 (pure forward state) is hex-exact everywhere; the
        # repeat-backward of the kv-head broadcast reassociates by ~1 ulp
        # across fusion contexts, so later steps compare numerically
        assert t_scan[0] == t_unroll[0] == t_legacy[0]
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-6)
        for other in (t_unroll, t_legacy):
            np.testing.assert_allclose(
                [np.frombuffer(bytes.fromhex(h), np.float32)[0]
                 for h in t_scan],
                [np.frombuffer(bytes.fromhex(h), np.float32)[0]
                 for h in other], rtol=1e-4)

    def test_eager_backward_populates_all_grads(self):
        from paddle_tpu.models.gpt import GPTForCausalLM

        cfg = _tiny_cfg(recompute=True, recompute_policy="full")
        paddle.seed(2)
        m = GPTForCausalLM(cfg)
        rng = np.random.default_rng(2)
        ids = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
        labels = paddle.to_tensor(
            rng.integers(0, 64, (2, 8)).astype(np.int64))
        loss = m.loss(ids, labels)
        loss.backward()
        missing = [n for n, p in m.named_parameters() if p.grad is None]
        assert not missing, missing

    def test_ineligible_configs_keep_module_loop(self):
        from paddle_tpu.models.gpt import GPTForCausalLM

        # dropout, masked attention, gelu family: all stay per-layer
        m = GPTForCausalLM(_tiny_cfg(dropout=0.1))
        assert not m.model._shared_block_eligible(None)
        m2 = GPTForCausalLM(_tiny_cfg(norm_type="layernorm", act="gelu"))
        assert not m2.model._shared_block_eligible(None)
        m3 = GPTForCausalLM(_tiny_cfg())
        assert m3.model._shared_block_eligible(None)
        assert not m3.model._shared_block_eligible(object())  # mask
        # amp autocast relies on per-op white-list casting, which a
        # single fused stack op would bypass — module loop under amp
        with paddle.amp.auto_cast():
            assert not m3.model._shared_block_eligible(None)
        assert m3.model._shared_block_eligible(None)


class TestDepthSweep:
    """Acceptance: serialized-HLO bytes flat (sublinear) in depth for the
    scanned path, linear for the unrolled path — tiny dims, 2 vs 8
    layers, measured through the jit layer's hlo_program_bytes."""

    def _hlo_bytes(self, num_layers):
        import jax

        from paddle_tpu import jit as pjit
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=num_layers,
                        num_heads=2, max_seq_len=32, dropout=0.0,
                        recompute=True, recompute_policy="full")
        paddle.seed(0)
        model = GPTForCausalLMPipe(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        step = TrainStep(model, lambda i, l: model.loss(i, l), opt)
        step.aot_compile(
            jax.ShapeDtypeStruct((2, 16), np.int32),
            jax.ShapeDtypeStruct((2, 16), np.int64))
        rec = pjit.compile_summary("TrainStep[GPTForCausalLMPipe]")
        assert rec is not None and rec["hlo_program_bytes"] > 0
        assert rec["compile_seconds"] > 0 and rec["lower_seconds"] >= 0
        return rec["hlo_program_bytes"]

    def test_scan_flat_unrolled_linear(self, monkeypatch):
        monkeypatch.delenv("PTPU_SCAN_LAYERS", raising=False)
        scan2, scan8 = self._hlo_bytes(2), self._hlo_bytes(8)
        monkeypatch.setenv("PTPU_SCAN_LAYERS", "0")
        unroll2, unroll8 = self._hlo_bytes(2), self._hlo_bytes(8)
        # scanned: 4x the depth must cost well under 2x the bytes (flat
        # modulo constant overhead); unrolled: clearly linear growth
        assert scan8 < 1.6 * scan2, (scan2, scan8)
        assert unroll8 > 2.0 * unroll2, (unroll2, unroll8)
        assert scan8 < unroll8, (scan8, unroll8)


class TestCompileTelemetry:
    def test_trainstep_gauges_and_summary(self):
        import paddle_tpu.telemetry as telemetry
        from paddle_tpu import jit as pjit
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.gpt import GPTForCausalLM

        telemetry.enable()
        cfg = _tiny_cfg(num_layers=2)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = TrainStep(m, lambda i, l: m.loss(i, l), opt)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
        labels = paddle.to_tensor(
            rng.integers(0, 64, (2, 8)).astype(np.int64))
        before = float(step(ids, labels).numpy())
        assert np.isfinite(before)
        snap = telemetry.snapshot()
        label = "function=TrainStep[GPTForCausalLM]"
        for g in ("trace_seconds", "lower_seconds", "compile_seconds",
                  "hlo_program_bytes"):
            assert label in snap["gauges"].get(g, {}), (g, snap["gauges"])
        rec = pjit.compile_summary("TrainStep[GPTForCausalLM]")
        assert set(rec) == {"trace_seconds", "lower_seconds",
                            "compile_seconds", "hlo_program_bytes"}
        # steady state: a second call reuses the executable (no rebuild)
        t0 = rec["compile_seconds"]
        _ = float(step(ids, labels).numpy())
        assert pjit.compile_summary(
            "TrainStep[GPTForCausalLM]")["compile_seconds"] == t0

    def test_to_static_records_phases(self):
        import paddle_tpu.telemetry as telemetry
        from paddle_tpu import jit as pjit
        from paddle_tpu import nn

        telemetry.enable()
        lin = nn.Linear(8, 8)
        fn = paddle.jit.to_static(lin)
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        _ = fn(x)
        rec = pjit.compile_summary("Linear")
        assert rec is not None and rec["hlo_program_bytes"] > 0


class TestFusedFfnSeam:
    def test_kernel_parity_fwd_and_grads(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.swiglu_down import (
            swiglu_down, swiglu_down_supported)

        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((2, 16, 384)).astype(np.float32))
        u = jnp.asarray(rng.standard_normal((2, 16, 384)).astype(np.float32))
        wd = jnp.asarray(
            rng.standard_normal((384, 128)).astype(np.float32) * 0.05)
        assert swiglu_down_supported(g.shape, wd.shape)
        ref = (jax.nn.silu(g) * u) @ wd
        out = swiglu_down(g, u, wd, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

        def f_ref(g, u, wd):
            return jnp.sum(jnp.sin((jax.nn.silu(g) * u) @ wd))

        def f_new(g, u, wd):
            return jnp.sum(jnp.sin(swiglu_down(g, u, wd, interpret=True)))

        gr = jax.grad(f_ref, argnums=(0, 1, 2))(g, u, wd)
        gn = jax.grad(f_new, argnums=(0, 1, 2))(g, u, wd)
        for a, b in zip(gr, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_unsupported_shapes_are_loud(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.swiglu_down import (
            swiglu_down, swiglu_down_supported)

        g = jnp.ones((2, 7, 100), np.float32)
        wd = jnp.ones((100, 64), np.float32)
        assert not swiglu_down_supported(g.shape, wd.shape)
        with pytest.raises(ValueError):
            swiglu_down(g, jnp.ones_like(g), wd, interpret=True)

    def test_block_seam_end_to_end(self, monkeypatch):
        """PTPU_FUSED_FFN engages the megakernel inside the scanned block
        (interpret mode on CPU) with near-exact losses; untileable dims
        fall back to the unfused seam bitwise."""
        import jax.numpy as jnp

        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

        def run(cfg, init):
            m = GPTForCausalLMPipe(cfg)
            sd = m.state_dict()
            for k in sd:
                sd[k]._data = jnp.asarray(init[k])
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            step = TrainStep(m, lambda i, l: m.loss(i, l), opt)
            rng = np.random.default_rng(0)
            ids = paddle.to_tensor(
                rng.integers(0, 64, (2, 16)).astype(np.int32))
            labels = paddle.to_tensor(
                rng.integers(0, 64, (2, 16)).astype(np.int64))
            return [float(step(ids, labels).numpy()) for _ in range(2)]

        # tileable dims: h=128 -> intermediate 384, both 128-aligned
        cfg = GPTConfig(vocab_size=64, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=32, dropout=0.0)
        paddle.seed(0)
        init = {k: np.asarray(v._data).copy()
                for k, v in GPTForCausalLMPipe(cfg).state_dict().items()}
        monkeypatch.delenv("PTPU_FUSED_FFN", raising=False)
        plain = run(cfg, init)
        monkeypatch.setenv("PTPU_FUSED_FFN", "interpret")
        fused = run(cfg, init)
        np.testing.assert_allclose(plain, fused, rtol=2e-4, atol=1e-5)

        # untileable dims (h=32): the fused gate declines, bitwise parity
        cfg2 = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=32, dropout=0.0)
        paddle.seed(0)
        init2 = {k: np.asarray(v._data).copy()
                 for k, v in GPTForCausalLMPipe(cfg2).state_dict().items()}
        fused2 = run(cfg2, init2)
        monkeypatch.delenv("PTPU_FUSED_FFN", raising=False)
        plain2 = run(cfg2, init2)
        assert _hex(fused2) == _hex(plain2)

    def test_tp_seam_precedence(self, monkeypatch):
        """Engaged tp seams disable the fused ffn seam (docs/SCAN.md)."""
        from paddle_tpu.models.gpt import _fused_ffn_active

        monkeypatch.setenv("PTPU_FUSED_FFN", "interpret")
        assert _fused_ffn_active(None)
        assert not _fused_ffn_active(object())  # a live TPSeamPlan


class TestCheckpointLayoutRoundTrip:
    """Satellite: save under the per-layer layout, restore into the
    stacked layout (and the reverse) bit-for-bit; ckpt_inspect validates
    both roots."""

    def _models(self):
        import jax.numpy as jnp

        from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                           GPTForCausalLMPipe)

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=3,
                        num_heads=2, max_seq_len=32, dropout=0.0)
        paddle.seed(7)
        eager = GPTForCausalLM(cfg)
        pipe = GPTForCausalLMPipe(cfg)
        # give the pipe model DIFFERENT weights so a restore is provable
        for k, t in pipe.state_dict().items():
            t._data = jnp.asarray(
                np.asarray(t._data) + 1.0, t._data.dtype)
        return cfg, eager, pipe

    def test_per_layer_checkpoint_restores_into_stacked(self, tmp_path):
        from paddle_tpu.distributed.checkpoint.manager import (
            CheckpointManager)
        from paddle_tpu.models.gpt import (convert_decoder_state_dict,
                                           restore_decoder_any_layout)
        from tools.ckpt_inspect import validate

        cfg, eager, pipe = self._models()
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=eager.parameters())
        # one real step so Adam slots exist and convert too
        from paddle_tpu.jit import TrainStep

        step = TrainStep(eager, lambda i, l: eager.loss(i, l), opt)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
        labels = paddle.to_tensor(
            rng.integers(0, 64, (2, 8)).astype(np.int64))
        _ = step(ids, labels)

        mgr = CheckpointManager(str(tmp_path / "per_layer"))
        mgr.save_training_state(1, eager, opt, train_step=step)
        mgr.close()

        mgr2 = CheckpointManager(str(tmp_path / "per_layer"))
        popt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                      parameters=pipe.parameters())
        s = restore_decoder_any_layout(mgr2, pipe, popt)
        assert s == 1
        # parameters: stacked leaves equal the stacked per-layer source
        esd = {k: np.asarray(v._data)
               for k, v in eager.state_dict().items()}
        want = convert_decoder_state_dict(esd, "stacked")
        got = {k: np.asarray(v._data) for k, v in pipe.state_dict().items()}
        assert set(want) == set(got)
        for k in want:
            assert np.asarray(want[k]).tobytes() == got[k].tobytes(), k
        # optimizer slots landed (Adam moments follow their parameter)
        slots = popt._slots[id(pipe.state_dict()["decoder.wq"])]
        assert any("moment" in s for s in slots)
        # ckpt_inspect validates the per-layer root
        results = validate(str(tmp_path / "per_layer"))
        assert results and all(not r["problems"] for r in results)
        mgr2.close()

    def test_stacked_checkpoint_restores_into_per_layer(self, tmp_path):
        from paddle_tpu.distributed.checkpoint.manager import (
            CheckpointManager)
        from paddle_tpu.models.gpt import restore_decoder_any_layout
        from tools.ckpt_inspect import validate

        cfg, eager, pipe = self._models()
        mgr = CheckpointManager(str(tmp_path / "stacked"))
        mgr.save_training_state(3, pipe)
        mgr.close()

        mgr2 = CheckpointManager(str(tmp_path / "stacked"))
        s = restore_decoder_any_layout(mgr2, eager)
        assert s == 3
        psd = {k: np.asarray(v._data) for k, v in pipe.state_dict().items()}
        for k, v in eager.state_dict().items():
            if k == "model.embed_tokens.weight":
                src = psd["embed_tokens.weight"]
            elif k == "model.final_norm.weight":
                src = psd["final_norm.weight"]
            else:
                continue
            assert np.asarray(v._data).tobytes() == src.tobytes(), k
        # every decoder layer slice matches its stacked source
        for i in range(cfg.num_layers):
            got = np.asarray(
                eager.state_dict()[f"model.layers.{i}.attn.q_proj.weight"]
                ._data)
            assert got.tobytes() == psd["decoder.wq"][i].tobytes()
        results = validate(str(tmp_path / "stacked"))
        assert results and all(not r["problems"] for r in results)
        mgr2.close()

    def test_strict_false_still_converts_cross_layout(self, tmp_path):
        """strict=False must not short-circuit the conversion: a
        non-strict native restore of a cross-layout checkpoint matches
        zero keys and would otherwise 'succeed' loading nothing."""
        from paddle_tpu.distributed.checkpoint.manager import (
            CheckpointManager)
        from paddle_tpu.models.gpt import restore_decoder_any_layout

        cfg, eager, pipe = self._models()
        mgr = CheckpointManager(str(tmp_path / "pl"))
        mgr.save_training_state(1, eager)
        mgr.close()
        before = np.asarray(pipe.state_dict()["decoder.wq"]._data).copy()
        mgr2 = CheckpointManager(str(tmp_path / "pl"))
        assert restore_decoder_any_layout(mgr2, pipe, strict=False) == 1
        after = np.asarray(pipe.state_dict()["decoder.wq"]._data)
        assert before.tobytes() != after.tobytes(), \
            "strict=False restored nothing for a cross-layout checkpoint"
        mgr2.close()

    def test_strict_false_same_layout_stays_native(self, tmp_path):
        """A model-only same-layout checkpoint restored with an
        optimizer + strict=False must take the native lenient path
        (reshard-on-load, missing opt.* keys tolerated) — NOT be
        rerouted through the converter."""
        from paddle_tpu.distributed.checkpoint.manager import (
            CheckpointManager)
        from paddle_tpu.models.gpt import restore_decoder_any_layout

        cfg, eager, _ = self._models()
        mgr = CheckpointManager(str(tmp_path / "mo"))
        mgr.save_training_state(1, eager)  # no optimizer state saved
        before = {k: np.asarray(v._data).copy()
                  for k, v in eager.state_dict().items()}
        import jax.numpy as jnp

        for t in eager.state_dict().values():
            t._data = jnp.zeros_like(t._data)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=eager.parameters())
        assert restore_decoder_any_layout(mgr, eager, opt,
                                          strict=False) == 1
        for k, v in eager.state_dict().items():
            assert np.asarray(v._data).tobytes() == before[k].tobytes(), k
        mgr.close()

    def test_same_layout_keeps_native_path(self, tmp_path):
        """A same-layout checkpoint restores through the pre-existing
        restore_training_state path (no conversion involved)."""
        from paddle_tpu.distributed.checkpoint.manager import (
            CheckpointManager)
        from paddle_tpu.models.gpt import restore_decoder_any_layout

        cfg, eager, _ = self._models()
        mgr = CheckpointManager(str(tmp_path / "native"))
        mgr.save_training_state(2, eager)
        before = {k: np.asarray(v._data).copy()
                  for k, v in eager.state_dict().items()}
        for k, t in eager.state_dict().items():
            import jax.numpy as jnp

            t._data = jnp.zeros_like(t._data)
        assert restore_decoder_any_layout(mgr, eager) == 2
        after = {k: np.asarray(v._data)
                 for k, v in eager.state_dict().items()}
        for k in before:
            assert before[k].tobytes() == after[k].tobytes(), k
        mgr.close()


class TestPlannerScanKeys:
    def _plan(self, tmp_path, candidates):
        import jax

        from paddle_tpu import memory as pmem
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

        def step_factory(cand):
            cfg = GPTConfig(vocab_size=64, hidden_size=32,
                            num_layers=cand.depth or 2, num_heads=2,
                            max_seq_len=32, dropout=0.0)
            paddle.seed(0)
            model = GPTForCausalLMPipe(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=model.parameters())
            s = TrainStep(model, lambda i, l: model.loss(i, l), opt)
            return s, (jax.ShapeDtypeStruct((cand.batch, 8), np.int32),
                       jax.ShapeDtypeStruct((cand.batch, 8), np.int64))

        return pmem.plan_train_step(
            step_factory, candidates, budget_bytes=10**12,
            cache_path=str(tmp_path / "plan.json"))

    def test_scan_mode_invalidates_cache(self, tmp_path, monkeypatch):
        """Satellite: a decision cached under the scanned program must
        not be replayed for an unrolled build (PR 2 staleness class)."""
        from paddle_tpu import memory as pmem

        monkeypatch.delenv("PTPU_SCAN_LAYERS", raising=False)
        cands = [pmem.Candidate(2, "none")]
        d1 = self._plan(tmp_path, cands)
        assert d1.source == "planner"
        d2 = self._plan(tmp_path, cands)
        assert d2.source == "cache"
        monkeypatch.setenv("PTPU_SCAN_LAYERS", "0")
        d3 = self._plan(tmp_path, cands)
        assert d3.source == "planner", \
            "unrolled-mode plan replayed a scanned-mode cache entry"

    def test_depth_is_a_plan_axis(self, tmp_path, monkeypatch):
        from paddle_tpu import memory as pmem

        monkeypatch.delenv("PTPU_SCAN_LAYERS", raising=False)
        d2 = self._plan(tmp_path, [pmem.Candidate(2, "none", depth=2)])
        d4 = self._plan(tmp_path, [pmem.Candidate(2, "none", depth=4)])
        assert d2.depth == 2 and d4.depth == 4
        assert d2.key != d4.key
        assert d4.peak_bytes > d2.peak_bytes  # deeper model, more HBM
        # same depth again: cache hit
        assert self._plan(
            tmp_path, [pmem.Candidate(2, "none", depth=2)]).source == "cache"


class TestSlabBuckets:
    NAMES = [
        ("model.embed_tokens.weight", (64, 32), np.float32),
        ("model.layers.0.attn.q_proj.weight", (512, 512), np.float32),
        ("model.layers.1.attn.q_proj.weight", (512, 512), np.float32),
        ("model.layers.0.mlp.gate_proj.weight", (512, 512), np.float32),
        ("model.layers.1.mlp.gate_proj.weight", (512, 512), np.float32),
        ("model.layers.0.input_norm.weight", (32,), np.float32),
        ("model.layers.1.input_norm.weight", (32,), np.float32),
    ]

    def test_slab_grouping(self):
        from paddle_tpu.distributed.collectives.overlap import (
            partition_buckets)

        buckets = partition_buckets(self.NAMES, bucket_bytes=2**20,
                                    quantized=True, slab=True)
        by_names = {b.names: b for b in buckets}
        assert ("model.layers.0.attn.q_proj.weight",
                "model.layers.1.attn.q_proj.weight") in by_names
        assert ("model.layers.0.mlp.gate_proj.weight",
                "model.layers.1.mlp.gate_proj.weight") in by_names
        # norms are exact AND layer-indexed: one exact slab bucket
        norm = by_names[("model.layers.0.input_norm.weight",
                         "model.layers.1.input_norm.weight")]
        assert not norm.quantized
        # non-indexed tensors are their own bucket
        assert ("model.embed_tokens.weight",) in by_names

    def test_second_index_stays_literal(self):
        """Only the LAYER index wildcards: MoE-style expert ordinals
        keep their own slab per expert (the stacked layout stacks over
        layers — each expert is its own [L, ...] leaf)."""
        from paddle_tpu.distributed.collectives.overlap import (
            partition_buckets)

        names = [(f"model.layers.{i}.mlp.experts.{j}.weight",
                  (512, 512), np.float32)
                 for i in range(2) for j in range(2)]
        buckets = partition_buckets(names, quantized=True, slab=True)
        assert len(buckets) == 2  # one slab per EXPERT, not one total
        groups = sorted(b.names for b in buckets)
        assert groups[0] == ("model.layers.0.mlp.experts.0.weight",
                             "model.layers.1.mlp.experts.0.weight")

    def test_env_knob_and_default(self, monkeypatch):
        from paddle_tpu.distributed.collectives.overlap import (
            partition_buckets, slab_grouping_enabled)

        monkeypatch.delenv("PTPU_COMM_SLAB", raising=False)
        assert not slab_grouping_enabled()
        # default path unchanged: cap-based partitioning still packs
        # consecutive same-class leaves together
        default = partition_buckets(self.NAMES, bucket_bytes=64 * 2**20,
                                    quantized=True)
        slabbed = partition_buckets(self.NAMES, bucket_bytes=64 * 2**20,
                                    quantized=True, slab=True)
        assert default != slabbed
        monkeypatch.setenv("PTPU_COMM_SLAB", "1")
        assert slab_grouping_enabled()
        assert partition_buckets(self.NAMES, bucket_bytes=64 * 2**20,
                                 quantized=True) == slabbed
