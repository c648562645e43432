"""End-to-end low-precision compute (ISSUE 17, docs/QUANT.md): scaled
fp8/int8 GEMMs with delayed scaling for training, int8-resident decode
weights for serving, the quant: policy syntax, the int8-head-style
parity gate, the decline matrix, plan-cache key separation, amax-state
durability (CheckpointManager + StepGuard), and the bench/telemetry
reporting contract."""
import io
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import quant
from paddle_tpu.quant import gemm as qgemm


@pytest.fixture(autouse=True)
def _no_ambient_mesh(monkeypatch):
    """Hex-parity tests must not depend on suite ordering (an earlier
    fleet.init can leave a logical mp>1 mesh active — see
    test_scan_layers)."""
    import paddle_tpu.distributed.fleet as fleet

    monkeypatch.setattr(fleet, "active_mesh", lambda: None)


@pytest.fixture(autouse=True)
def _clean_quant_env(monkeypatch):
    """Quant decisions read env at trace time — every test starts from
    an unset knob set so nothing leaks between tests."""
    for k in qgemm.QUANT_KNOBS + ("PTPU_SCAN_LAYERS",):
        monkeypatch.delenv(k, raising=False)
    yield
    # trace-time flop-rate latch is module state: drop it so later
    # note_step_tokens callers (TrainStep) don't tick a stale series
    qgemm._LAST_TRACE[0] = None


@pytest.fixture
def metrics():
    import paddle_tpu.telemetry as telemetry

    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def _hex(vals):
    return [np.float32(v).tobytes().hex() for v in vals]


def _tiny_cfg(**kw):
    from paddle_tpu.models.gpt import GPTConfig

    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=32, dropout=0.0, recompute=True)
    base.update(kw)
    return GPTConfig(**base)


def _clone(cfg, init):
    from paddle_tpu.models.gpt import GPTForCausalLM

    m = GPTForCausalLM(cfg)
    sd = m.state_dict()
    for k in sd:
        sd[k]._data = jnp.asarray(init[k])
    return m


def _init_of(cfg, seed=0):
    from paddle_tpu.models.gpt import GPTForCausalLM

    paddle.seed(seed)
    src = GPTForCausalLM(cfg)
    return {k: np.asarray(v._data).copy()
            for k, v in src.state_dict().items()}


def _batch():
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
    labels = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int64))
    return ids, labels


def _train_hex(model, ids, labels, steps=3):
    from paddle_tpu.jit import TrainStep

    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    step = TrainStep(model, lambda i, l: model.loss(i, l), opt)
    return _hex(float(step(ids, labels).numpy()) for _ in range(steps)), step


# ---------------------------------------------------------------------------
# the scaled GEMM kernel: narrow forward, wide exact backward
# ---------------------------------------------------------------------------
class TestScaledGemm:
    @pytest.mark.parametrize("dtype", ["fp8", "int8"])
    def test_forward_parity_and_quantization_visible(self, dtype):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((32, 64)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32))
        h = jnp.zeros((4,), jnp.float32)
        out, _, _ = quant.scaled_gemm(x, w, h, h, dtype=dtype)
        ref = np.asarray(x @ w)
        err = np.abs(np.asarray(out) - ref) / (np.abs(ref) + 1.0)
        assert err.mean() < 0.08, err.mean()
        # it IS quantized — not secretly running the wide matmul
        assert np.abs(np.asarray(out) - ref).max() > 0

    @pytest.mark.parametrize("dtype", ["fp8", "int8"])
    def test_backward_is_the_exact_wide_rule(self, dtype):
        """grads through the scaled GEMM equal the exact matmul's grads
        BITWISE — quantization noise is forward-only (custom_vjp)."""
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
        h = jnp.zeros((4,), jnp.float32)

        def f_quant(x, w):
            out, _, _ = quant.scaled_gemm(x, w, h, h, dtype=dtype)
            return out.sum()

        gx, gw = jax.grad(f_quant, argnums=(0, 1))(x, w)
        ex, ew = jax.grad(lambda x, w: (x @ w).sum(), argnums=(0, 1))(x, w)
        np.testing.assert_array_equal(np.asarray(gx), np.asarray(ex))
        np.testing.assert_array_equal(np.asarray(gw), np.asarray(ew))

    def test_history_shift_insert(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((4, 8)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
        hx = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
        hw = jnp.asarray([5.0, 6.0, 7.0, 8.0], jnp.float32)
        _, nhx, nhw = quant.scaled_gemm(x, w, hx, hw, dtype="int8")
        # ring shift-insert: current amax in front, oldest entry dropped
        assert float(nhx[0]) == float(jnp.max(jnp.abs(x)))
        np.testing.assert_array_equal(np.asarray(nhx[1:]),
                                      np.asarray(hx[:-1]))
        assert float(nhw[0]) == float(jnp.max(jnp.abs(w)))
        np.testing.assert_array_equal(np.asarray(nhw[1:]),
                                      np.asarray(hw[:-1]))

    def test_zero_history_bootstraps_from_current_amax(self):
        """A fresh (all-zero) history must scale from the current step's
        amax — identical output to a history pre-seeded with it."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
        zero = jnp.zeros((4,), jnp.float32)
        seeded_x = zero.at[0].set(jnp.max(jnp.abs(x)))
        seeded_w = zero.at[0].set(jnp.max(jnp.abs(w)))
        boot, _, _ = quant.scaled_gemm(x, w, zero, zero, dtype="fp8")
        seed, _, _ = quant.scaled_gemm(x, w, seeded_x, seeded_w,
                                       dtype="fp8")
        np.testing.assert_array_equal(np.asarray(boot), np.asarray(seed))

    def test_scale_comes_from_history_max_not_current(self):
        """Delayed scaling: a larger amax in the history wins over the
        current step's — the output visibly changes."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
        zero = jnp.zeros((4,), jnp.float32)
        big = zero.at[2].set(100.0 * float(jnp.max(jnp.abs(x))))
        a, _, _ = quant.scaled_gemm(x, w, zero, zero, dtype="int8")
        b, _, _ = quant.scaled_gemm(x, w, big, zero, dtype="int8")
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_all_zero_operands_stay_finite(self):
        # SCALE_EPS floors the scale — no 0/0
        z = jnp.zeros((4, 4), jnp.float32)
        h = jnp.zeros((2,), jnp.float32)
        out, _, _ = quant.scaled_gemm(z, z, h, h, dtype="fp8")
        np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 4)))

    def test_inline_matches_zero_history_entry(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
        h = jnp.zeros((quant.amax_hist_len(),), jnp.float32)
        ref, _, _ = quant.scaled_gemm(x, w, h, h, dtype="int8")
        got = quant.inline_scaled_gemm(x, w, dtype="int8")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_incubate_fp8_delegates_to_the_shared_core(self):
        """PR 4 discipline: incubate.fp8_gemm IS inline_scaled_gemm —
        one quantizer implementation, bitwise."""
        from paddle_tpu.incubate.nn.functional import fp8_gemm

        rng = np.random.default_rng(6)
        x = rng.standard_normal((8, 16)).astype(np.float32)
        w = rng.standard_normal((16, 4)).astype(np.float32)
        got = fp8_gemm(paddle.to_tensor(x), paddle.to_tensor(w)).numpy()
        ref = np.asarray(quant.inline_scaled_gemm(
            jnp.asarray(x), jnp.asarray(w), dtype="fp8"))
        np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# quant: policy syntax
# ---------------------------------------------------------------------------
class TestPolicyParsing:
    def test_entries_split_and_remainder_preserved(self):
        rest, sites = quant.split_quant_entries(
            "attn_q,int8:resid_mid,quant:attn")
        assert rest == "attn_q,int8:resid_mid"
        assert sites == frozenset({"wq", "wk", "wv", "wo"})

    @pytest.mark.parametrize("spec,want", [
        ("quant:all", frozenset(quant.GEMM_SITES)),
        ("quant:ffn", frozenset({"wg", "wu", "wd"})),
        ("quant:wd,quant:wq", frozenset({"wd", "wq"})),
        ("attn_q,ffn_gate", frozenset()),
    ])
    def test_aliases_and_sites(self, spec, want):
        _, sites = quant.split_quant_entries(spec)
        assert sites == want

    def test_empty_entry_raises(self):
        with pytest.raises(ValueError, match="empty quant:"):
            quant.split_quant_entries("attn_q,quant:")

    def test_unknown_site_raises_with_vocabulary(self):
        with pytest.raises(ValueError, match="wq"):
            quant.split_quant_entries("quant:bogus")

    def test_sites_from_policy_names_only(self):
        assert quant.quant_sites_from_policy(
            "names:attn_q,quant:all") == frozenset(quant.GEMM_SITES)
        assert quant.quant_sites_from_policy("full") == frozenset()
        assert quant.quant_sites_from_policy(None) == frozenset()


# ---------------------------------------------------------------------------
# gate + enablement resolution
# ---------------------------------------------------------------------------
class TestEnablement:
    def _cfg(self, policy):
        return types.SimpleNamespace(recompute_policy=policy)

    def test_env_forces_both_ways(self, monkeypatch):
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        assert quant.quant_compute_enabled(requested=False)
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "0")
        assert not quant.quant_compute_enabled(requested=True)

    def test_unset_and_unrequested_is_off(self):
        assert not quant.quant_compute_enabled(requested=False)

    def test_cpu_default_off_when_unset(self):
        # CPU backend: no narrow-GEMM rate to win — requested or not
        assert jax.default_backend() == "cpu"
        assert not quant.quant_compute_enabled(requested=True)

    def test_requested_sites_track_request_not_gate(self, monkeypatch):
        cfg = self._cfg("names:attn_q,quant:attn")
        assert quant.requested_quant_sites(cfg) == frozenset(
            {"wq", "wk", "wv", "wo"})
        # env escape hatch: NO request, no buffer, pre-quant programs
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "0")
        assert quant.requested_quant_sites(cfg) == frozenset()
        # env force with no policy sites means all
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        assert quant.requested_quant_sites(
            self._cfg("full")) == frozenset(quant.GEMM_SITES)

    def test_engaged_sites_respect_the_cpu_gate(self, monkeypatch):
        cfg = self._cfg("names:quant:all")
        assert quant.engaged_quant_sites(cfg) == frozenset()  # CPU off
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        assert quant.engaged_quant_sites(cfg) == frozenset(
            quant.GEMM_SITES)

    def test_gate_passes_on_clean_probe(self):
        rep = quant.quant_gate_report()
        assert rep["ok"] and rep["loss_rel_err"] < rep["tol"]
        assert rep["grad_rel_err"] < rep["grad_tol"]
        assert rep["dtype"] in ("fp8", "int8")

    def test_drifting_probe_fails_loudly(self, monkeypatch):
        monkeypatch.setattr(qgemm, "_GATE_CACHE", {})
        monkeypatch.setattr(qgemm, "_gate_probe",
                            lambda tol, dtype: (False, 0.5, 0.5))
        with pytest.warns(RuntimeWarning, match="drift"):
            rep = quant.quant_gate_report()
        assert not rep["ok"] and not quant.quant_gate()

    def test_probe_runs_inside_an_outer_trace(self, monkeypatch):
        """On an accelerator the gate's first caller is the step's own
        jit trace (engagement resolves at trace time): the probe must
        evaluate eagerly there instead of seeing that trace's tracers."""
        monkeypatch.setattr(qgemm, "_GATE_CACHE", {})
        seen = []
        jax.jit(lambda x: (seen.append(quant.quant_gate_report()["ok"]),
                           x)[1])(1.0)
        assert seen == [True]

    def test_crashed_probe_raises(self, monkeypatch):
        """A probe that CRASHES is a bug to see, not a default to flip:
        it raises (only measured drift keeps the warning + default-off),
        and nothing is cached."""
        monkeypatch.setattr(qgemm, "_GATE_CACHE", {})

        def boom(tol, dtype):
            raise RuntimeError("no narrow dot here")

        monkeypatch.setattr(qgemm, "_gate_probe", boom)
        with pytest.raises(RuntimeError, match="no narrow dot here"):
            quant.quant_gate_report()
        assert not qgemm._GATE_CACHE

    def test_dtype_resolution(self, monkeypatch):
        monkeypatch.setenv("PTPU_QUANT_DTYPE", "int8")
        assert quant.quant_dtype() == "int8"
        monkeypatch.setenv("PTPU_QUANT_DTYPE", "bf16")
        with pytest.raises(ValueError, match="fp8, int8 or auto"):
            quant.quant_dtype()
        monkeypatch.delenv("PTPU_QUANT_DTYPE")
        assert quant.quant_dtype() in ("fp8", "int8")

    def test_cache_key_knobs_cover_every_knob(self, monkeypatch):
        monkeypatch.setenv("PTPU_QUANT_AMAX_HIST", "9")
        knobs = dict(quant.cache_key_knobs())
        assert set(knobs) == set(quant.QUANT_KNOBS)
        assert knobs["PTPU_QUANT_AMAX_HIST"] == "9"

    def test_loss_drift_probe_inside_budget(self):
        assert quant.loss_drift_probe() < 0.005


# ---------------------------------------------------------------------------
# the decline matrix (PR 6/7 owner precedence)
# ---------------------------------------------------------------------------
class TestDeclineMatrix:
    def _resolve(self, monkeypatch, policy="names:quant:all", **kw):
        from paddle_tpu.distributed.collectives import compose
        from paddle_tpu.models import gpt

        cfg = _tiny_cfg(recompute_policy=policy)
        sites, dtype = gpt._resolve_quant(cfg, **kw)
        verdict = compose.last_verdicts().get("quant_gemm")
        return sites, dtype, verdict

    def test_owner_declines_win_over_the_gate(self, monkeypatch):
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        for kw, reason in [(dict(composed=True), "composed_region"),
                           (dict(pipelined=True), "pipeline_stage_fn"),
                           (dict(tp_seams=object()), "tp_seam_owns_gemm")]:
            sites, dtype, verdict = self._resolve(monkeypatch, **kw)
            assert sites == frozenset() and dtype is None
            assert verdict == ("declined", reason), (kw, verdict)

    def test_cpu_unforced_declines_on_the_gate(self, monkeypatch):
        sites, dtype, verdict = self._resolve(monkeypatch)
        assert sites == frozenset()
        assert verdict == ("declined", "quant_parity_gate")

    def test_fused_ffn_owns_its_site_only(self, monkeypatch):
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        monkeypatch.setenv("PTPU_FUSED_FFN", "interpret")
        sites, dtype, verdict = self._resolve(monkeypatch)
        assert sites == frozenset(quant.GEMM_SITES) - {"wd"}
        assert verdict == ("engaged", "engaged")
        # ffn-only request: swiglu_down takes wd, gate/up stay engaged
        sites, dtype, verdict = self._resolve(
            monkeypatch, policy="names:quant:ffn")
        assert sites == frozenset({"wg", "wu"})
        assert verdict == ("engaged", "engaged")
        # the one owned site alone: owned away -> nothing engages
        sites, dtype, verdict = self._resolve(
            monkeypatch, policy="names:quant:wd")
        assert sites == frozenset() and dtype is None
        assert verdict == ("declined", "fused_kernel_owns_gemm")

    def test_forced_engagement_records_modes(self, monkeypatch, metrics):
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        monkeypatch.setenv("PTPU_QUANT_DTYPE", "fp8")
        sites, dtype, verdict = self._resolve(monkeypatch, path="train")
        assert sites == frozenset(quant.GEMM_SITES) and dtype == "fp8"
        assert verdict == ("engaged", "engaged")
        g = metrics.snapshot()["gauges"]["gemm_dtype_mode"]
        for s in quant.GEMM_SITES:
            assert g[f"site={s},path=train"] == 2.0


# ---------------------------------------------------------------------------
# whole-model training: escape hatch, two-sided program proof, parity
# ---------------------------------------------------------------------------
class TestTrainingPrograms:
    def test_escape_hatch_is_hex_identical_and_bufferless(self,
                                                          monkeypatch):
        """PTPU_QUANT_COMPUTE=0 with a quant: policy == the plain policy:
        no amax buffer, float32-hex-identical 3-step trajectory."""
        ids, labels = _batch()
        cfg_plain = _tiny_cfg(recompute_policy="names:attn_q")
        init = _init_of(cfg_plain)
        h_plain, _ = _train_hex(_clone(cfg_plain, init), ids, labels)

        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "0")
        cfg_q = _tiny_cfg(recompute_policy="names:attn_q,quant:all")
        m = _clone(cfg_q, init)
        assert "model.quant_amax" not in m.state_dict()
        h_off, _ = _train_hex(m, ids, labels)
        assert h_off == h_plain, "escape hatch drifted from pre-quant"

    def test_two_sided_program_proof(self, monkeypatch):
        """Forced-on programs CONTAIN fp8 operands; the env-0 escape
        hatch's program contains NONE — the structural two-sided proof
        on the full compiled train step."""
        from paddle_tpu.jit import TrainStep

        ids, labels = _batch()
        cfg = _tiny_cfg(recompute_policy="names:attn_q,quant:all")
        init = _init_of(cfg)

        def hlo_of():
            m = _clone(cfg, init)
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            step = TrainStep(m, lambda i, l: m.loss(i, l), opt)
            return step.aot_compile(ids, labels).as_text()

        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        monkeypatch.setenv("PTPU_QUANT_DTYPE", "fp8")
        assert "f8e4m3" in hlo_of()
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "0")
        assert "f8e4m3" not in hlo_of()

    @pytest.mark.slow  # two full train-step compiles; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_forced_quant_scan_unroll_hex_parity_and_amax(self,
                                                          monkeypatch):
        """Engaged scaled GEMMs: scan vs the PTPU_SCAN_LAYERS=0 unrolled
        escape hatch stay float32-hex-identical INCLUDING the threaded
        amax state, and the buffer actually advances."""
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        monkeypatch.setenv("PTPU_QUANT_DTYPE", "fp8")
        ids, labels = _batch()
        cfg = _tiny_cfg(recompute_policy="names:attn_q,quant:all")
        init = _init_of(cfg)

        def run():
            m = _clone(cfg, init)
            h, _ = _train_hex(m, ids, labels)
            return h, np.asarray(m.state_dict()["model.quant_amax"]._data)

        h_scan, a_scan = run()
        assert (a_scan != 0).any(), "amax never advanced"
        monkeypatch.setenv("PTPU_SCAN_LAYERS", "0")
        h_un, a_un = run()
        assert h_scan == h_un
        assert a_scan.tobytes() == a_un.tobytes()

    @pytest.mark.slow  # two full train-step compiles; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_quantization_changes_numerics_when_engaged(self,
                                                        monkeypatch):
        ids, labels = _batch()
        cfg = _tiny_cfg(recompute_policy="names:attn_q,quant:all")
        init = _init_of(cfg)
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "0")
        h_off, _ = _train_hex(_clone(cfg, init), ids, labels)
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        h_on, _ = _train_hex(_clone(cfg, init), ids, labels)
        assert h_on != h_off  # narrow GEMMs are really in the program


# ---------------------------------------------------------------------------
# amax-state durability: CheckpointManager + StepGuard (satellite 3)
# ---------------------------------------------------------------------------
class TestAmaxDurability:
    @pytest.mark.slow  # train-step compile + ckpt io; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_checkpoint_roundtrip_and_layout_convert(self, monkeypatch,
                                                     tmp_path):
        from paddle_tpu.distributed.checkpoint.manager import \
            CheckpointManager
        from paddle_tpu.models.gpt import convert_decoder_state_dict

        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        ids, labels = _batch()
        cfg = _tiny_cfg(recompute_policy="names:attn_q,quant:all")
        init = _init_of(cfg)
        m = _clone(cfg, init)
        _train_hex(m, ids, labels, steps=2)
        amax = np.asarray(m.state_dict()["model.quant_amax"]._data)
        assert (amax != 0).any()

        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(2, m.state_dict())
        fresh = _clone(cfg, init)
        np.testing.assert_array_equal(
            np.asarray(fresh.state_dict()["model.quant_amax"]._data), 0)
        assert mgr.restore(fresh.state_dict()) == 2
        got = np.asarray(fresh.state_dict()["model.quant_amax"]._data)
        assert got.tobytes() == amax.tobytes()

        # layout converters pass the stacked buffer through unchanged
        state = {k: np.asarray(v._data) for k, v in m.state_dict().items()}
        per_layer = convert_decoder_state_dict(state, "per_layer")
        assert per_layer["model.quant_amax"].tobytes() == amax.tobytes()
        back = convert_decoder_state_dict(per_layer, "stacked")
        assert np.asarray(
            back["model.quant_amax"]).tobytes() == amax.tobytes()

    @pytest.mark.slow  # guarded + clean full runs; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_stepguard_skip_preserves_amax_bitwise(self, monkeypatch):
        """A guarded skip discards the anomalous step's amax advance with
        the rest of the update: trajectory AND final amax state equal the
        clean run's float32 hex exactly."""
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.resilience import StepGuard
        from paddle_tpu.testing import chaos

        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        ids, labels = _batch()
        cfg = _tiny_cfg(recompute_policy="names:attn_q,quant:all")
        init = _init_of(cfg)

        def run(inject_at=None, steps=5):
            m = _clone(cfg, init)
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            step = TrainStep(m, lambda i, l: m.loss(i, l), opt)
            got, skips = {}, 0
            if inject_at is None:
                for s in range(1, steps + 1):
                    got[s] = _hex([float(step(ids, labels).numpy())])[0]
            else:
                guard = StepGuard(step, max_consecutive=5)
                with chaos.inject_nonfinite(inject_at, kind="nan",
                                            site="grads"):
                    g = 1
                    while g <= steps:
                        out = guard(g, ids, labels)
                        skips += out.action == "skip"
                        if out.accepted:
                            got[g] = _hex([out.health.loss])[0]
                        g = out.next_step
            return (got, skips,
                    np.asarray(m.state_dict()["model.quant_amax"]._data))

        clean, _, a_clean = run()
        guarded, skips, a_guard = run(inject_at=3)
        assert skips == 1
        assert guarded == clean
        assert a_guard.tobytes() == a_clean.tobytes()


# ---------------------------------------------------------------------------
# plan-cache key separation (satellite 2)
# ---------------------------------------------------------------------------
class TestPlanCacheKeys:
    def _factory(self, calls):
        def factory(cand):
            calls.append(cand)
            step = types.SimpleNamespace(
                memory_stats=lambda *a: {"peak_bytes": 1000,
                                         "argument_bytes": 500,
                                         "output_bytes": 500,
                                         "temp_bytes": 500,
                                         "alias_bytes": 0})
            return step, (jax.ShapeDtypeStruct((1,), jnp.float32),)

        return factory

    def test_quant_knob_flip_misses_the_cache(self, monkeypatch,
                                              tmp_path):
        from paddle_tpu import memory as pmem

        calls = []
        factory = self._factory(calls)
        cpath = str(tmp_path / "plan.json")
        cands = [pmem.Candidate(2, "names:attn_q", quant="all")]
        d1 = pmem.plan_train_step(factory, cands, budget_bytes=1e9,
                                  cache_path=cpath)
        assert d1.source == "planner" and d1.quant == "all"
        n = len(calls)
        # same knobs -> hit, and the hit carries the quant spec
        d2 = pmem.plan_train_step(factory, cands, budget_bytes=1e9,
                                  cache_path=cpath)
        assert d2.source == "cache" and d2.quant == "all"
        assert len(calls) == n
        # a wide-priced plan must NOT replay for a quantized build
        monkeypatch.setenv("PTPU_QUANT_COMPUTE", "1")
        d3 = pmem.plan_train_step(factory, cands, budget_bytes=1e9,
                                  cache_path=cpath)
        assert d3.source == "planner" and d3.key != d1.key
        assert len(calls) > n

    def test_candidate_quant_axis_is_part_of_the_key(self, tmp_path):
        from paddle_tpu import memory as pmem

        calls = []
        factory = self._factory(calls)
        cpath = str(tmp_path / "plan.json")
        d_wide = pmem.plan_train_step(
            factory, [pmem.Candidate(2, "names:attn_q")],
            budget_bytes=1e9, cache_path=cpath)
        assert d_wide.quant is None
        n = len(calls)
        d_q = pmem.plan_train_step(
            factory, [pmem.Candidate(2, "names:attn_q", quant="ffn")],
            budget_bytes=1e9, cache_path=cpath)
        assert d_q.source == "planner" and d_q.key != d_wide.key
        assert d_q.quant == "ffn" and len(calls) > n


# ---------------------------------------------------------------------------
# serving int8-resident weights (satellite 6)
# ---------------------------------------------------------------------------
def _llama(seed=0):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=96, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=128,
                      dropout=0.0)
    paddle.seed(seed)
    return LlamaForCausalLM(cfg)


class TestInt8Weights:
    def test_pack_shapes_and_roundtrip(self):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((64, 48)).astype(np.float32)
        w *= rng.uniform(0.01, 8.0, (1, 48)).astype(np.float32)
        codes, scales = quant.quantize_weight_cols_int8(jnp.asarray(w))
        assert codes.dtype == jnp.int8 and codes.shape == (64, 48)
        assert scales.dtype == jnp.float32 and scales.shape == (1, 48)
        x = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
        got = np.asarray(quant.int8_weight_matmul(x, codes, scales))
        exact = np.asarray(x) @ w
        err = np.mean(np.abs(got - exact)) / np.mean(np.abs(exact))
        assert err < 0.05, err
        # the packed pair is the resident footprint win
        assert codes.nbytes + scales.nbytes < 0.5 * w.nbytes

    def test_pack_handles_stacked_layer_trees(self):
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((3, 16, 8)).astype(np.float32))
        codes, scales = quant.quantize_weight_cols_int8(w)
        assert codes.shape == (3, 16, 8) and scales.shape == (3, 1, 8)
        # per-layer pack == stacked pack, sliced
        c0, s0 = quant.quantize_weight_cols_int8(w[1])
        np.testing.assert_array_equal(np.asarray(codes[1]), np.asarray(c0))
        np.testing.assert_array_equal(np.asarray(scales[1]), np.asarray(s0))

    def test_gate_env_forces_and_probe_paths(self, monkeypatch):
        monkeypatch.setenv("PTPU_INT8_WEIGHTS", "0")
        assert not quant.int8_weights_enabled(requested=True)
        monkeypatch.setenv("PTPU_INT8_WEIGHTS", "1")
        assert quant.int8_weights_enabled(requested=False)
        monkeypatch.delenv("PTPU_INT8_WEIGHTS")
        assert not quant.int8_weights_enabled(requested=False)
        monkeypatch.setattr(qgemm, "_INT8_W_PROBE", [None])
        assert quant.int8_weights_enabled(requested=True)  # real probe
        monkeypatch.setattr(qgemm, "_INT8_W_PROBE", [False])
        with pytest.warns(RuntimeWarning, match="probe failed"):
            assert not quant.int8_weights_enabled(requested=True)

    @pytest.mark.slow  # two serving-engine compiles; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_engine_footprint_stream_parity_and_load(self, metrics):
        """THE satellite-6 acceptance: an int8-packed engine reports the
        reduced per-dtype footprint (load(), weight_bytes, the
        serving_weight_bytes gauge) and serves the exact greedy tokens
        of the wide engine."""
        from paddle_tpu.inference.serving import ContinuousBatchingEngine

        model = _llama()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 96, (n,)).tolist() for n in (5, 3)]

        def serve(eng):
            for pr in prompts:
                eng.submit(pr)
            return eng.run_until_complete(max_ticks=1000)

        eng_w = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                         max_seq_len=64, max_new_tokens=4)
        assert not eng_w.int8_weights
        assert set(eng_w.weight_bytes) == {"float32"}
        done_w = serve(eng_w)

        eng_q = ContinuousBatchingEngine(model, max_slots=2, page_size=16,
                                         max_seq_len=64, max_new_tokens=4,
                                         int8_weights=True)
        assert eng_q.int8_weights
        assert eng_q.weight_bytes["int8"] > 0
        total_q = sum(eng_q.weight_bytes.values())
        total_w = sum(eng_w.weight_bytes.values())
        assert total_q < 0.5 * total_w, (total_q, total_w)
        done_q = serve(eng_q)
        assert done_q == done_w  # greedy streams identical

        info = eng_q.load()
        assert info["int8_weights"] is True
        assert info["weight_bytes"] == dict(eng_q.weight_bytes)
        g = metrics.snapshot()["gauges"]["serving_weight_bytes"]
        assert g["dtype=int8"] == float(eng_q.weight_bytes["int8"])

    @pytest.mark.slow  # two eager generate decodes; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_generate_int8_weights_matches_exact(self):
        model = _llama(seed=3)
        rng = np.random.default_rng(3)
        ids = paddle.to_tensor(
            rng.integers(1, 96, (1, 6)).astype(np.int32))
        want = np.asarray(model.generate(ids, max_new_tokens=4).numpy())
        got = np.asarray(model.generate(ids, max_new_tokens=4,
                                        int8_weights=True).numpy())
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# bench gate + telemetry report (satellites 5 + 4)
# ---------------------------------------------------------------------------
class TestBenchGateQuant:
    def _rec(self, **kw):
        block = {"requested": True, "engaged": True, "dtype": "fp8",
                 "verdict": "engaged", "reason": "engaged",
                 "gate": {"ok": True, "tol": 0.02, "loss_rel_err": 1e-4,
                          "grad_rel_err": 1e-3, "grad_tol": 0.1,
                          "dtype": "fp8"},
                 "loss_drift_rel": 0.0007, "loss_drift_budget": 0.005}
        block.update(kw)
        return {"quant": block}

    def test_green_block_passes(self):
        import tools.bench_gate as bg

        assert bg.quant_violations(self._rec()) == []
        assert bg.quant_violations({"metric": "m"}) == []  # no block

    def test_red_gate_fails_and_names_the_force(self):
        import tools.bench_gate as bg

        rec = self._rec(gate={"ok": False, "tol": 0.02,
                              "loss_rel_err": 0.9, "grad_rel_err": 0.9,
                              "grad_tol": 0.1, "dtype": "fp8"})
        v = bg.quant_violations(rec)
        assert len(v) == 1 and "gate red" in v[0]
        assert "forced past a failing probe" in v[0]  # engaged anyway

    def test_drift_over_budget_fails(self):
        import tools.bench_gate as bg

        v = bg.quant_violations(self._rec(loss_drift_rel=0.02))
        assert len(v) == 1 and "loss drift" in v[0]

    def test_documented_declines_pass_silent_ones_fail(self):
        import tools.bench_gate as bg

        for reason in sorted(bg.QUANT_CONFIG_DECLINES):
            rec = self._rec(engaged=False, verdict="declined",
                            reason=reason)
            assert bg.quant_violations(rec) == [], reason
        v = bg.quant_violations(
            self._rec(engaged=False, verdict="declined", reason=None))
        assert len(v) == 1 and "never engaged" in v[0]

    def test_main_gates_on_quant_block(self, tmp_path, capsys):
        import tools.bench_gate as bg

        def _round(name, quant_block):
            line = json.dumps({"metric": "m", "value": 100.0,
                               "unit": "tokens/sec/chip",
                               "quant": quant_block})
            p = tmp_path / name
            p.write_text(json.dumps({"n": 1, "cmd": "bench", "rc": 0,
                                     "tail": line, "parsed": {}}))
            return str(p)

        good = self._rec()["quant"]
        bad = dict(good, loss_drift_rel=0.02)
        old = _round("BENCH_r01.json", good)
        assert bg.main([_round("BENCH_r02.json", good),
                        "--against", old]) == 0
        assert bg.main([_round("BENCH_r03.json", bad),
                        "--against", old]) == 1
        assert "QUANT" in capsys.readouterr().out


class TestTelemetryReportQuant:
    def test_section_renders_all_three_series(self):
        import tools.telemetry_report as tr

        snap = {"gauges": {"gemm_dtype_mode": {"site=wq,path=train": 2.0,
                                               "site=wd,path=train": 0.0},
                           "serving_weight_bytes": {"dtype=int8": 73728.0,
                                                    "dtype=float32":
                                                        38144.0}},
                "counters": {"quant_gemm_flops_total":
                             {"dtype=fp8": 12345.0}}}
        out = io.StringIO()
        tr.print_quant(snap, out=out)
        text = out.getvalue()
        assert "-- quant (scaled-GEMM compute) --" in text
        assert "gemm[wq]@train: fp8" in text
        assert "gemm[wd]@train: wide" in text
        assert "narrow_flops[fp8]: 12345" in text
        assert "serving_weight_bytes[int8]: 73728" in text

    def test_silent_when_no_quant_series(self):
        import tools.telemetry_report as tr

        out = io.StringIO()
        tr.print_quant({"gauges": {}, "counters": {}}, out=out)
        assert out.getvalue() == ""

    def test_flop_counter_ticks_from_trace_latch(self, metrics):
        quant.note_gemm_mode("train", frozenset({"wq"}), "fp8",
                             flops_per_token=10)
        quant.note_step_tokens(16)
        snap = metrics.snapshot()
        assert snap["counters"]["quant_gemm_flops_total"][
            "dtype=fp8"] == 160.0
        assert snap["gauges"]["gemm_dtype_mode"]["site=wq,path=train"] == 2.0
        assert snap["gauges"]["gemm_dtype_mode"]["site=wk,path=train"] == 0.0
        # a disengaged retrace drops the latch: no further ticks
        quant.note_gemm_mode("train", frozenset(), None)
        quant.note_step_tokens(16)
        assert metrics.snapshot()["counters"]["quant_gemm_flops_total"][
            "dtype=fp8"] == 160.0
