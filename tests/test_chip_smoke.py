"""chip_smoke.py and the bring-up contracts around it (ISSUE 23).

The phase functions run here at tiny sizes BY ARGUMENT (no flag, no env
variable selects a CPU mode — the script itself has none and must fail
without a TPU). Beside them: the one peaks table, the compile-cache
placement, and the one-process-per-chip rules of the fleet's children.
"""
import json
import os
import subprocess
import sys
import types

import pytest

import jax

import paddle_tpu  # noqa: F401  (x64 on, as every entry point runs)
from paddle_tpu import device as pdevice

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY_GPT = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                max_seq_len=256, dropout=0.0, recompute=True)


class TestPhasesAtTinySize:
    def test_kernel_phase_interpreted(self):
        report = chip_smoke.kernel_phase(
            flash_shapes={"gqa": (1, 128, 4, 2, 64)},
            rms_shape=(16, 128), swiglu_shapes={"tiny": (16, 128, 128)},
            paged_shape=(2, 4, 2, 64, 8, 2), dtype="float32", tol=1e-4,
            interpret=True)
        assert set(report) == {
            "flash_gqa", "rms_norm", "swiglu_down_tiny",
            "paged_attention", "paged_attention_int8"}
        assert not any("refused" in r for r in report.values())

    def test_optional_kernel_refusal_is_reported_required_one_raises(
            self, monkeypatch):
        def refuse(*a, **kw):
            raise RuntimeError("mosaic says no")

        kw = dict(flash_shapes={}, rms_shape=(16, 128), swiglu_shapes={},
                  paged_shape=(2, 4, 2, 64, 8, 2), dtype="float32",
                  tol=1e-4, interpret=True)
        monkeypatch.setattr(chip_smoke, "check_paged_attention_int8", refuse)
        report = chip_smoke.kernel_phase(**kw)
        assert "mosaic says no" in report["paged_attention_int8"]["refused"]
        monkeypatch.setattr(chip_smoke, "check_rms_norm", refuse)
        with pytest.raises(RuntimeError, match="mosaic says no"):
            chip_smoke.kernel_phase(**kw)

    def test_trainer_phase(self):
        from paddle_tpu.models.gpt import GPTConfig

        out = chip_smoke.trainer_phase(
            cfg=GPTConfig(**TINY_GPT), seq=128, batches=(4, 2),
            head_chunk=256, steps=2, bf16=False, expect_kernels=())
        assert out["losses"][-1] < out["losses"][0]
        assert out["kernels"] == []  # no Mosaic on the CPU

    def test_a_missing_kernel_fails_the_phase(self):
        text = 'custom_call @tpu_custom_call(%0) {kernel_name = "flash_fwd"}'
        assert chip_smoke._assert_kernels(
            text, ("flash_fwd",), "step") == {"flash_fwd"}
        with pytest.raises(AssertionError, match="rms_norm_fwd"):
            chip_smoke._assert_kernels(
                text, ("flash_fwd", "rms_norm_fwd"), "step")

    def test_server_phase(self):
        out = chip_smoke.server_phase(
            cfg_kw=dict(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, max_seq_len=128,
                        dropout=0.0),
            sizes=dict(max_new=8, page=8, slots=4, chunk=8, max_seq=64),
            prompt_lens=(6, 10, 14, 20), bf16=False, expect_kernels=())
        assert out["requests"] == 5 and out["new_tokens"] == 8

    def test_sharded_phase_spreads_parameters(self):
        from paddle_tpu.distributed.auto_parallel import set_mesh
        from paddle_tpu.models.gpt import GPTConfig

        try:
            out = chip_smoke.sharded_phase(
                cfg=GPTConfig(**dict(TINY_GPT, num_kv_heads=2)), seq=128,
                steps=2, bf16=False, expect_kernels=())
        finally:
            set_mesh(None)
        assert out["devices"] == len(jax.devices())
        assert out["zero"]["engaged"] and out["zero"]["stage"] == 3


class TestBenchIsTheBuilders:
    """bench.py is what benchmark/harness, benchmark/tests and
    chip_smoke.py import from it, and no runner (ISSUE 32)."""

    def _taken(self):
        """Every ``bench.<name>`` in the code of the files that import
        it (the syntax tree's, so the harness's span names, the strings
        "bench.tick" and the like, are no part of it)."""
        import ast
        import glob

        files = [os.path.join(REPO, "chip_smoke.py")]
        for sub in ("harness", "tests"):
            files += glob.glob(os.path.join(REPO, "benchmark", sub, "**",
                                            "*.py"), recursive=True)
        taken = {}
        for path in files:
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Attribute)
                        and isinstance(node.value, ast.Name)
                        and node.value.id == "bench"):
                    taken.setdefault(node.attr, os.path.relpath(path, REPO))
        return taken

    def test_every_name_taken_from_bench_resolves(self):
        import bench

        taken = self._taken()
        assert {"apply_tpu_defaults", "build_model", "build_optimizer",
                "DEFAULT_POLICY"} <= set(taken)  # the walk found the users
        missing = {n: f for n, f in taken.items() if not hasattr(bench, n)}
        assert not missing, missing

    def test_bench_runs_nothing(self):
        import bench

        for gone in ("main", "run_model", "run_long_context"):
            assert not hasattr(bench, gone), gone


class TestNoChipMeansFailure:
    def test_script_fails_without_a_tpu_and_says_why(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
            capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0
        assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
        assert proc.stdout.startswith("platform=cpu")
        assert '"ok"' not in proc.stdout  # no result line

    def test_last_line_is_exactly_ok_and_device(self, monkeypatch, capsys):
        """Whoever checks a chip run parses only the last stdout line and
        refuses any key beside ``ok`` and ``device``; the per-phase record
        rides on the ``summary:`` line before it."""
        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        monkeypatch.setattr(pdevice, "device_record", lambda: dict(device))
        monkeypatch.setattr(pdevice, "chip_peaks", lambda: ({}, False))
        monkeypatch.setattr(pdevice, "compile_cache_dir", lambda: "/cache")
        for phase in ("kernel_phase", "trainer_phase", "server_phase"):
            monkeypatch.setattr(chip_smoke, phase, lambda: {"stub": True})
        chip_smoke.main()
        lines = capsys.readouterr().out.strip().splitlines()
        assert json.loads(lines[-1]) == {"ok": True, "device": device}
        assert lines[-2].startswith("summary: ")
        summary = json.loads(lines[-2][len("summary: "):])
        assert summary["claim"] is None
        assert set(summary["phases"]) == {
            "kernels", "trainer", "server", "sharded"}

    def test_require_accelerator(self, monkeypatch):
        assert pdevice.require_accelerator("t") is False  # CPU was asked
        # the TPU machine's own setting keeps a CPU backend BESIDE the
        # TPU: it does not ask for the CPU
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert not pdevice.cpu_requested()
        with pytest.raises(RuntimeError, match="JAX found no TPU"):
            pdevice.require_accelerator("bench.py")
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(RuntimeError, match="bench.py"):
            pdevice.require_accelerator("bench.py")

    def test_accelerator_place_never_resolves_to_a_cpu_device(self):
        with pytest.raises(RuntimeError, match="no accelerator"):
            pdevice.set_device("tpu")
        with pytest.raises(RuntimeError, match="CPU device"):
            pdevice.jax_device_for(pdevice.TPUPlace(0))
        with pytest.raises(IndexError, match="out of range"):
            pdevice.jax_device_for(pdevice.CPUPlace(len(jax.devices("cpu"))))
        assert pdevice.jax_device_for(pdevice.CPUPlace(0)).platform == "cpu"


class TestOnePeaksTable:
    def test_v5e_row_and_cpu_placeholder(self):
        v5e = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")
        peaks, placeholder = pdevice.chip_peaks(v5e)
        assert not placeholder
        assert peaks["bf16_flops"] == 197e12
        assert peaks["int8_ops"] == 393e12
        assert peaks["hbm_bytes"] == 16e9
        assert peaks["hbm_bytes_per_sec"] == 819e9
        assert peaks["ici_bytes_per_sec"] == 1600e9 / 8
        assert pdevice.chip_peaks()[1] is True  # this CPU: flagged

    def test_unknown_tpu_kind_raises(self):
        unknown = types.SimpleNamespace(platform="tpu",
                                        device_kind="TPU v99")
        with pytest.raises(KeyError, match="TPU v99"):
            pdevice.chip_peaks(unknown)

    def test_every_reader_uses_it(self):
        from paddle_tpu import jit as pjit
        from paddle_tpu import memory as pmem
        from paddle_tpu.memory import autotune

        cpu = pdevice.chip_peaks()[0]
        assert pjit._device_peaks() == (
            cpu["bf16_flops"], cpu["hbm_bytes_per_sec"], True)
        assert pmem.hbm_budget_bytes() == int(cpu["hbm_bytes"])
        assert autotune.link_bytes_per_sec() == (
            cpu["ici_bytes_per_sec"], True)


class TestCompileCachePlacement:
    def test_env_variable_wins_and_nothing_else_is_set(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        monkeypatch.delenv("JAX_PLATFORMS")
        before = jax.config.jax_compilation_cache_dir
        assert pdevice.compile_cache_dir() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        # a caller who asked for the CPU gets no cache at all
        assert pdevice.compile_cache_dir() is None
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        try:
            assert pdevice.compile_cache_dir() == os.path.join(
                REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == os.path.join(
                REPO, ".jax_cache")
        finally:  # CPU collectives must never meet a warm cache here
            jax.config.update("jax_compilation_cache_dir", None)


_PARENT_STAYS_OFF_JAX = """
import json, sys
from jax._src import xla_bridge
from paddle_tpu.inference.fleet import FleetSupervisor, make_model_spec
spec = make_model_spec(
    dict(vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
         max_seq_len=64, dropout=0.0),
    engine_kw=dict(max_slots=2, page_size=8, max_seq_len=32,
                   max_new_tokens=4, prefill_chunk=8))
sup = FleetSupervisor(spec, 1, proc=True, lease_seconds=120.0,
                      workdir=sys.argv[1])
try:
    assert sup.proc
    child = sup.children[0]
    print(json.dumps({"device": child.device,
                      "parent_backends": xla_bridge.backends_are_initialized()}))
finally:
    sup.close()
"""


class TestOneProcessPerChip:
    def test_child_env_inherits_and_pins_nothing(self, monkeypatch):
        from paddle_tpu.inference.fleet.cluster import child_env

        assert child_env()["JAX_PLATFORMS"] == "cpu"  # the tests' own
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        env = child_env()
        assert "JAX_PLATFORMS" not in env and "XLA_FLAGS" not in env
        assert env["PYTHONUNBUFFERED"] == "1"

    def test_spec_dtype_is_what_the_child_serves(self):
        """--procs on a TPU serves the bf16 decoder the in-process path
        serves: the dtype rides the spec (a float32 child of the serving
        bench's decoder does not fit the chip beside its KV cache)."""
        from paddle_tpu.inference.fleet.cluster import (
            build_model_from_spec, make_model_spec)

        cfg = dict(vocab_size=128, hidden_size=32, num_layers=1,
                   num_heads=2, max_seq_len=64, dropout=0.0)
        assert "dtype" not in make_model_spec(cfg)
        model = build_model_from_spec(make_model_spec(cfg,
                                                      dtype="bfloat16"))
        assert {str(p._data.dtype)
                for _, p in model.named_parameters()} == {"bfloat16"}

    def test_second_chip_process_on_a_host_is_refused(self, monkeypatch):
        from paddle_tpu.inference.fleet.cluster import (
            check_one_process_per_chip)

        check_one_process_per_chip(3, "t")      # CPU asked for: no limit
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        check_one_process_per_chip(0, "t")      # the first holder is fine
        with pytest.raises(RuntimeError, match="one process per chip"):
            check_one_process_per_chip(1, "FleetSupervisor")

    def test_launcher_refuses_two_ranks_per_host_on_a_tpu(self, tmp_path):
        script = tmp_path / "train.py"
        script.write_text("print('ran')\n")
        env = dict(os.environ, PYTHONPATH=REPO)
        env.pop("JAX_PLATFORMS")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--log_dir", str(tmp_path / "log"), "--nproc_per_node", "2",
             str(script)],
            env=env, cwd=str(tmp_path), capture_output=True, text=True,
            timeout=120)
        assert proc.returncode != 0
        assert "a chip belongs to one process" in proc.stderr
        assert not (tmp_path / "log").exists()  # nothing was spawned

    def test_proc_fleet_parent_initialises_no_backend(self, tmp_path):
        """The supervisor of process-mode replicas never opens a JAX
        backend (it would hold the chip its child needs); the child's
        handshake names the device it serves from."""
        proc = subprocess.run(
            [sys.executable, "-c", _PARENT_STAYS_OFF_JAX, str(tmp_path)],
            env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO,
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        assert rec["parent_backends"] is False
        assert rec["device"]["platform"] == "cpu"
        assert rec["device"]["count"] >= 1
