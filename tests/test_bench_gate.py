"""tools/bench_gate.py — the CI tokens/sec regression gate (ISSUE 4)."""
import json

import tools.bench_gate as bg


def _round(tmp_path, name, metrics):
    tail = "log noise\n" + "\n".join(
        json.dumps({"metric": m, "value": v, "unit": "tokens/sec/chip",
                    "mfu": 0.5}) for m, v in metrics.items())
    p = tmp_path / name
    p.write_text(json.dumps({"n": 5, "cmd": "python bench.py", "rc": 0,
                             "tail": tail, "parsed": {}}))
    return str(p)


def test_loads_driver_round_and_raw_formats(tmp_path):
    p = _round(tmp_path, "BENCH_r07.json", {"m": 100.0})
    assert bg.load_metrics(p)["m"]["value"] == 100.0
    raw = tmp_path / "raw.json"
    raw.write_text('junk\n{"metric": "m", "value": 7.5}\n')
    assert bg.load_metrics(str(raw))["m"]["value"] == 7.5


def test_pass_within_threshold(tmp_path, capsys):
    old = _round(tmp_path, "BENCH_r01.json", {"m": 100.0, "k": 50.0})
    new = _round(tmp_path, "BENCH_r02.json", {"m": 96.0, "k": 55.0})
    assert bg.main([new, "--against", old]) == 0
    out = capsys.readouterr().out
    assert "OK" in out or "DOWN" in out


def test_fails_on_regression_over_threshold(tmp_path, capsys):
    old = _round(tmp_path, "BENCH_r01.json", {"m": 100.0})
    new = _round(tmp_path, "BENCH_r02.json", {"m": 90.0})
    assert bg.main([new, "--against", old]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    # a looser threshold lets the same pair pass
    assert bg.main([new, "--against", old, "--threshold", "0.15"]) == 0


def test_new_metric_is_not_gated(tmp_path):
    old = _round(tmp_path, "BENCH_r01.json", {"m": 100.0})
    new = _round(tmp_path, "BENCH_r02.json", {"m": 101.0, "fresh": 10.0})
    assert bg.main([new, "--against", old]) == 0


def _round_mfu(tmp_path, name, metric, value, mfu, extra=None):
    rec = {"metric": metric, "value": value, "unit": "tokens/sec/chip",
           "mfu": mfu}
    rec.update(extra or {})
    p = tmp_path / name
    p.write_text(json.dumps({"tail": json.dumps(rec)}))
    return str(p)


def test_mfu_gate_fails_on_regression(tmp_path, capsys):
    """ISSUE 10 satellite: the stage-3 config-5 line is gated on MFU
    alongside tokens/sec (docs/ZERO.md) — a run whose tokens/sec holds
    but whose hardware-normalised throughput collapses must fail."""
    old = _round_mfu(tmp_path, "BENCH_r01.json",
                     "llama7b_arch_8L_pretrain_tokens_per_sec",
                     100.0, 0.65)
    new = _round_mfu(tmp_path, "BENCH_r02.json",
                     "llama7b_arch_8L_pretrain_tokens_per_sec",
                     100.0, 0.50)
    assert bg.main([new, "--against", old]) == 1
    assert "MFU" in capsys.readouterr().out


def test_mfu_gate_passes_within_threshold_and_skips_missing(tmp_path):
    old = _round_mfu(tmp_path, "BENCH_r01.json", "m", 100.0, 0.65,
                     extra={"zero": {"engaged": True, "stage": 3}})
    new = _round_mfu(tmp_path, "BENCH_r02.json", "m", 100.0, 0.64)
    assert bg.main([new, "--against", old]) == 0
    # a record with no mfu field is not gated
    nom = tmp_path / "BENCH_r03.json"
    nom.write_text(json.dumps({"tail": json.dumps(
        {"metric": "m", "value": 100.0})}))
    assert bg.main([str(nom), "--against", old]) == 0


def test_discovers_latest_round_in_root(tmp_path):
    _round(tmp_path, "BENCH_r01.json", {"m": 100.0})
    _round(tmp_path, "BENCH_r02.json", {"m": 99.0})   # -1%: inside 5%
    assert bg.main(["--root", str(tmp_path)]) == 0
    _round(tmp_path, "BENCH_r03.json", {"m": 80.0})   # -19.2% vs r02
    assert bg.main(["--root", str(tmp_path)]) == 1


def _round_with_resilience(tmp_path, name, value, resilience):
    rec = {"metric": "m", "value": value, "unit": "tokens/sec/chip",
           "resilience": resilience}
    p = tmp_path / name
    p.write_text(json.dumps(rec) + "\n")
    return str(p)


def test_resilience_gate_fails_on_anomalies(tmp_path, capsys):
    """ISSUE 5: a clean bench run reporting guard anomalies must fail
    even with no tokens/sec regression."""
    old = _round(tmp_path, "BENCH_r01.json", {"m": 100.0})
    new = _round_with_resilience(
        tmp_path, "dirty.json", 100.0,
        {"enabled": True, "anomalies": {"nonfinite": 2},
         "anomalies_total": 2, "skips": 2, "rollbacks": 0,
         "aborted": False})
    assert bg.main([new, "--against", old]) == 1
    assert "guard_anomalies_total=2" in capsys.readouterr().out


def test_resilience_gate_fails_on_rollback_without_reference(tmp_path,
                                                             capsys):
    # no earlier round: tokens/sec not gated, resilience still is
    new = _round_with_resilience(
        tmp_path, "BENCH_r01.json", 100.0,
        {"enabled": True, "anomalies": {}, "anomalies_total": 0,
         "skips": 0, "rollbacks": 1, "aborted": False})
    assert bg.main(["--root", str(tmp_path)]) == 1
    assert "rollbacks=1" in capsys.readouterr().out


def test_resilience_gate_passes_clean_and_disabled_blocks(tmp_path):
    old = _round(tmp_path, "BENCH_r01.json", {"m": 100.0})
    clean = _round_with_resilience(
        tmp_path, "clean.json", 100.0,
        {"enabled": True, "anomalies": {}, "anomalies_total": 0,
         "skips": 0, "rollbacks": 0, "aborted": False})
    assert bg.main([clean, "--against", old]) == 0
    off = _round_with_resilience(tmp_path, "off.json", 100.0,
                                 {"enabled": False})
    assert bg.main([off, "--against", old]) == 0
    # records with no block at all (older rounds) keep passing
    assert bg.main([old, "--against", old]) == 0


def test_resilience_block_suppresses_duplicate_counter_report(tmp_path):
    """bench.py attaches the process-global telemetry snapshot to every
    metric line; when an enabled guard block is present it already
    reports those same events — the counters must not re-report them
    (one anomaly would otherwise print up to once per source per line,
    and model A's anomaly would land on model B's line)."""
    rec = {"metric": "m", "value": 10.0,
           "resilience": {"enabled": True, "anomalies": {"spike": 2},
                          "anomalies_total": 2, "rollbacks": 1,
                          "aborted": False},
           "telemetry": {"counters": {
               "guard_anomalies_total": {"kind=spike": 2},
               "guard_rollbacks_total": {"": 1}}}}
    v = bg.resilience_violations(rec)
    assert v == ["guard_anomalies_total=2 ({'spike': 2})",
                 "guard rollbacks=1"]  # block only, nothing doubled


def test_resilience_gate_reads_telemetry_counters(tmp_path, capsys):
    rec = {"metric": "m", "value": 10.0,
           "telemetry": {"counters": {
               "guard_anomalies_total": {"kind=spike": 3}}}}
    p = tmp_path / "tel.json"
    p.write_text(json.dumps(rec) + "\n")
    old = _round(tmp_path, "BENCH_r01.json", {"m": 10.0})
    assert bg.main([str(p), "--against", old]) == 1
    assert "guard_anomalies_total=3" in capsys.readouterr().out


def test_baseline_without_numbers_is_skipped(tmp_path, capsys):
    new = _round(tmp_path, "BENCH_r02.json", {"m": 100.0})
    base = tmp_path / "BASELINE.json"
    base.write_text(json.dumps({"metric": "description only",
                                "published": {}}))
    assert bg.main([new, "--against", str(base)]) == 0
    assert "skipped" in capsys.readouterr().out


# -- comms gate (ISSUE 6: quantized-collective parity, docs/COMMS.md) -------
def _round_with_comms(tmp_path, name, comms):
    rec = {"metric": "m", "value": 100.0, "unit": "tokens/sec/chip",
           "comms": comms}
    p = tmp_path / name
    p.write_text(json.dumps(rec) + "\n")
    return str(p)


def test_comms_gate_fails_on_parity_drift(tmp_path, capsys):
    p = _round_with_comms(tmp_path, "BENCH_r08.json", {
        "enabled": True,
        "parity": {"enabled": True, "max_rel_err": 0.5,
                   "threshold": 0.00787, "ok": False}})
    assert bg.main([p, "--against", p]) == 1
    assert "parity drift" in capsys.readouterr().out


def test_comms_gate_passes_ok_probe_and_disabled(tmp_path):
    ok = _round_with_comms(tmp_path, "BENCH_r08.json", {
        "enabled": True,
        "parity": {"enabled": True, "max_rel_err": 0.003,
                   "threshold": 0.00787, "ok": True}})
    assert bg.main([ok, "--against", ok]) == 0
    off = _round_with_comms(tmp_path, "BENCH_r09.json", {
        "enabled": False, "parity": {"enabled": False}})
    assert bg.main([off, "--against", off]) == 0


# -- compile gate (ISSUE 9: scan-over-layers flat compile, docs/SCAN.md) ----
def _round_with_compile(tmp_path, name, compile_block, value=100.0):
    rec = {"metric": "m", "value": value, "unit": "tokens/sec/chip",
           "compile": compile_block}
    p = tmp_path / name
    p.write_text(json.dumps(rec) + "\n")
    return str(p)


def _compile_block(total, num_layers=24, scan=True):
    return {"trace_seconds": total * 0.1, "lower_seconds": total * 0.1,
            "compile_seconds": total * 0.8, "hlo_program_bytes": 400000,
            "function": "TrainStep[GPTForCausalLMPipe]",
            "num_layers": num_layers, "scan_layers": scan}


def test_compile_gate_fails_on_regression_same_depth(tmp_path, capsys):
    old = _round_with_compile(tmp_path, "BENCH_r01.json",
                              _compile_block(10.0))
    new = _round_with_compile(tmp_path, "BENCH_r02.json",
                              _compile_block(14.0))
    assert bg.main([new, "--against", old]) == 1
    assert "COMPILE" in capsys.readouterr().out
    # a looser threshold lets the same pair pass
    assert bg.main([new, "--against", old,
                    "--compile-threshold", "0.5"]) == 0


def test_compile_gate_passes_within_threshold(tmp_path):
    old = _round_with_compile(tmp_path, "BENCH_r01.json",
                              _compile_block(10.0))
    new = _round_with_compile(tmp_path, "BENCH_r02.json",
                              _compile_block(11.0))
    assert bg.main([new, "--against", old]) == 0


def test_compile_gate_skips_depth_or_mode_mismatch(tmp_path):
    old = _round_with_compile(tmp_path, "BENCH_r01.json",
                              _compile_block(10.0, num_layers=8))
    new = _round_with_compile(tmp_path, "BENCH_r02.json",
                              _compile_block(40.0, num_layers=48))
    assert bg.main([new, "--against", old]) == 0  # depth changed: no gate
    old2 = _round_with_compile(tmp_path, "BENCH_r03.json",
                               _compile_block(10.0, scan=False))
    new2 = _round_with_compile(tmp_path, "BENCH_r04.json",
                               _compile_block(40.0, scan=True))
    assert bg.main([new2, "--against", old2]) == 0  # mode changed: no gate


def test_compile_gate_skips_missing_block_and_subsecond(tmp_path):
    plain_old = _round(tmp_path, "BENCH_r01.json", {"m": 100.0})
    new = _round_with_compile(tmp_path, "BENCH_r02.json",
                              _compile_block(14.0))
    assert bg.main([new, "--against", plain_old]) == 0
    tiny_old = _round_with_compile(tmp_path, "BENCH_r03.json",
                                   _compile_block(0.5))
    tiny_new = _round_with_compile(tmp_path, "BENCH_r04.json",
                                   _compile_block(0.9))
    assert bg.main([tiny_new, "--against", tiny_old]) == 0


def test_default_refs_bridge_a_gap_round(tmp_path, capsys, monkeypatch):
    """Metric continuity: when the previous round lacks a tracked
    metric (a CPU-only gap round like BENCH_r06), the default gate
    walks back to the newest earlier round that carries it — a real
    regression after the gap must still fail."""
    _round(tmp_path, "BENCH_r01.json", {"tracked": 100.0})
    _round(tmp_path, "BENCH_r02.json", {"smoke_only": 5.0})  # gap round
    _round(tmp_path, "BENCH_r03.json", {"tracked": 80.0,
                                        "smoke_only": 5.0})
    assert bg.main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION tracked" in out
    # and a healthy post-gap round passes
    _round(tmp_path, "BENCH_r04.json", {"tracked": 101.0,
                                        "smoke_only": 5.0})
    assert bg.main(["--root", str(tmp_path)]) == 0


# ---------------------------------------------------------- serving gates
def _round_with_serving(tmp_path, name, serving, extra=None):
    rec = {"metric": "serve_goodput_tokens_per_sec_r4", "value": 100.0,
           "unit": "tokens/sec", "serving": serving}
    rec.update(extra or {})
    p = tmp_path / name
    p.write_text(json.dumps({"tail": json.dumps(rec)}))
    return str(p)


def test_serving_gate_fails_p99_over_budget(tmp_path, capsys):
    """ISSUE 12 satellite: the soak embeds its p99-TTFT budget and the
    gate fails a round whose tail latency blows it (docs/SERVING.md)."""
    bad = _round_with_serving(tmp_path, "bad.json", {
        "enabled": True, "requests": 10, "completed": 10, "cancelled": 0,
        "ttft": {"p99": 0.9}, "p99_ttft_budget": 0.2})
    assert bg.main([bad, "--against", bad]) == 1
    assert "SERVE" in capsys.readouterr().out


def test_serving_gate_fails_scaling_below_target(tmp_path, capsys):
    """The acceptance bar: 4 replicas must reach the embedded scaling
    target (3.5x single-replica goodput)."""
    bad = _round_with_serving(tmp_path, "bad.json", {
        "enabled": True, "requests": 10, "completed": 10, "cancelled": 0,
        "replicas": 4, "goodput_x_single": 2.9, "scaling_target": 3.5})
    assert bg.main([bad, "--against", bad]) == 1
    assert "scaling" in capsys.readouterr().out


def test_serving_gate_fails_lost_requests_and_passes_clean(tmp_path):
    lost = _round_with_serving(tmp_path, "lost.json", {
        "enabled": True, "requests": 10, "completed": 7, "cancelled": 1})
    assert bg.main([lost, "--against", lost]) == 1
    ok = _round_with_serving(tmp_path, "ok.json", {
        "enabled": True, "requests": 10, "completed": 9, "cancelled": 1,
        "replicas": 4, "goodput_x_single": 3.8, "scaling_target": 3.5,
        "ttft": {"p99": 0.05}, "p99_ttft_budget": 0.2})
    assert bg.main([ok, "--against", ok]) == 0
    # unserved rounds ({"enabled": false}) are not gated
    off = _round_with_serving(tmp_path, "off.json", {"enabled": False})
    assert bg.main([off, "--against", off]) == 0


def test_cold_start_gate_vs_reference(tmp_path, capsys):
    """Replica cold start is gated like the compile gate: same scan
    mode, sub-second references skipped, --compile-threshold bound."""
    old = _round_with_serving(tmp_path, "old.json", {
        "enabled": True, "requests": 1, "completed": 1, "cancelled": 0,
        "cold_start_seconds": 1.5, "scan_layers": True})
    slow = _round_with_serving(tmp_path, "slow.json", {
        "enabled": True, "requests": 1, "completed": 1, "cancelled": 0,
        "cold_start_seconds": 2.5, "scan_layers": True})
    assert bg.main([slow, "--against", old]) == 1
    assert "COLD" in capsys.readouterr().out
    # improvement passes; a scan-mode change is not comparable
    assert bg.main([old, "--against", slow]) == 0
    other_mode = _round_with_serving(tmp_path, "mode.json", {
        "enabled": True, "requests": 1, "completed": 1, "cancelled": 0,
        "cold_start_seconds": 9.0, "scan_layers": False})
    assert bg.main([other_mode, "--against", old]) == 0


# --------------------------------------------------------- overload gate
def _overload_block(**overrides):
    """A gate-clean overload block (docs/SERVING.md 'Overload &
    degradation'); overrides poke individual violations."""
    block = {
        "enabled": True, "replicas": 2, "submitted": 100, "served": 70,
        "cancelled": 5, "shed": 15, "rejected": 10, "conserved": True,
        "ttft": {"p99": 0.4}, "p99_ttft_seconds": 0.4,
        "p99_ttft_budget": 1.0, "shed_fraction": 0.25,
        "shed_ceiling": 0.5, "breaker_opens": 3,
        "breaker_flap_bound": 8,
        "brownout": {"level": 0, "max_level": 2, "restored": True},
    }
    block.update(overrides)
    return block


def _round_with_overload(tmp_path, name, block):
    rec = {"metric": "serve_overload_goodput_r2", "value": 100.0,
           "unit": "tokens/sec", "overload": block}
    p = tmp_path / name
    p.write_text(json.dumps({"tail": json.dumps(rec)}))
    return str(p)


def test_overload_gate_passes_clean_and_skips_disabled(tmp_path):
    ok = _round_with_overload(tmp_path, "ok.json", _overload_block())
    assert bg.main([ok, "--against", ok]) == 0
    off = _round_with_overload(tmp_path, "off.json", {"enabled": False})
    assert bg.main([off, "--against", off]) == 0


def test_overload_gate_fails_lost_requests(tmp_path, capsys):
    """Zero lost/hung requests at 2x capacity is the hard floor: a
    broken outcome conservation fails reference-free."""
    bad = _round_with_overload(tmp_path, "bad.json", _overload_block(
        served=65, conserved=False))
    assert bg.main([bad, "--against", bad]) == 1
    out = capsys.readouterr().out
    assert "OVERLOAD" in out and "conservation" in out


def test_overload_gate_fails_admitted_p99_over_budget(tmp_path, capsys):
    bad = _round_with_overload(tmp_path, "bad.json", _overload_block(
        p99_ttft_seconds=1.7))
    assert bg.main([bad, "--against", bad]) == 1
    assert "p99 TTFT" in capsys.readouterr().out


def test_overload_gate_fails_shed_over_ceiling(tmp_path, capsys):
    bad = _round_with_overload(tmp_path, "bad.json", _overload_block(
        shed_fraction=0.8))
    assert bg.main([bad, "--against", bad]) == 1
    assert "ceiling" in capsys.readouterr().out


def test_overload_gate_fails_breaker_flaps_over_bound(tmp_path, capsys):
    bad = _round_with_overload(tmp_path, "bad.json", _overload_block(
        breaker_opens=20))
    assert bg.main([bad, "--against", bad]) == 1
    assert "flap" in capsys.readouterr().out


def test_overload_gate_fails_unrestored_brownout(tmp_path, capsys):
    bad = _round_with_overload(tmp_path, "bad.json", _overload_block(
        brownout={"level": 2, "max_level": 3, "restored": False}))
    assert bg.main([bad, "--against", bad]) == 1
    assert "brownout" in capsys.readouterr().out


def test_serving_gate_counts_shed_and_rejected_as_outcomes(tmp_path):
    """A soak that shed/rejected under overload control did NOT lose
    those requests — the SERVE lost-request arithmetic must count every
    terminal outcome (docs/SERVING.md)."""
    ok = _round_with_serving(tmp_path, "ok.json", {
        "enabled": True, "requests": 10, "completed": 6, "cancelled": 1,
        "shed": 2, "rejected": 1})
    assert bg.main([ok, "--against", ok]) == 0
    lost = _round_with_serving(tmp_path, "lost.json", {
        "enabled": True, "requests": 10, "completed": 6, "cancelled": 1,
        "shed": 2, "rejected": 0})
    assert bg.main([lost, "--against", lost]) == 1


def _layout_block(**over):
    """A healthy autotuned "layout" block (docs/AUTOTUNE.md shapes)."""
    block = {
        "label": "sharding8/z3/b2/r-names", "predicted_score": 1200.0,
        "predicted_step_seconds": 0.01, "peak_bytes": 100, "fits": True,
        "budget_bytes": 1000, "source": "search", "chip": "cpu",
        "device_count": 8, "key": "k", "searched": 15, "pruned_total": 5,
        "pruned_by_reason": {"unsupported_mesh_axes": 5},
        "search_seconds": 1.0, "fallback_reason": None,
        "baseline": {"label": "dp8/z0/b2/r-names", "fits": True,
                     "predicted_tokens_per_sec": 1000.0},
    }
    block.update(over)
    return block


def _round_with_layout(tmp_path, name, layout):
    rec = {"metric": "gpt_pretrain_tokens_per_sec", "value": 100.0,
           "unit": "tokens/sec/chip", "mfu": 0.5, "layout": layout}
    p = tmp_path / name
    p.write_text(json.dumps({"tail": json.dumps(rec)}))
    return str(p)


def test_layout_gate_passes_winner_and_disabled_blocks(tmp_path):
    """ISSUE 19 satellite: a winner that beats (or IS) the hand-picked
    baseline passes, as do non-autotuned rounds ({"enabled": false} or
    no block at all) — the gate only speaks when a search ran."""
    ok = _round_with_layout(tmp_path, "ok.json", _layout_block())
    assert bg.main([ok, "--against", ok]) == 0
    tie = _round_with_layout(tmp_path, "tie.json", _layout_block(
        predicted_score=1000.0))
    assert bg.main([tie, "--against", tie]) == 0
    off = _round_with_layout(tmp_path, "off.json", {"enabled": False})
    assert bg.main([off, "--against", off]) == 0


def test_layout_gate_fails_winner_losing_to_baseline(tmp_path, capsys):
    """An autotuned layout whose PREDICTED score loses to the hand-picked
    config's predicted score at equal chips is a misranked search — the
    baseline went through the same cost model, so the winner can only
    lose by construction error (docs/AUTOTUNE.md gate recipe)."""
    bad = _round_with_layout(tmp_path, "bad.json", _layout_block(
        predicted_score=900.0))
    assert bg.main([bad, "--against", bad]) == 1
    assert "LAYOUT" in capsys.readouterr().out


def test_layout_gate_fails_silent_fallback(tmp_path, capsys):
    """source="fallback" without a structured fallback_reason measures
    the hand config while claiming a search — only a reasoned fallback
    (e.g. no_candidate_fit) is a legitimate outcome."""
    silent = _round_with_layout(tmp_path, "silent.json", _layout_block(
        source="fallback", fallback_reason=None))
    assert bg.main([silent, "--against", silent]) == 1
    assert "fallback_reason" in capsys.readouterr().out
    reasoned = _round_with_layout(tmp_path, "reasoned.json", _layout_block(
        source="fallback", fallback_reason="no_candidate_fit"))
    assert bg.main([reasoned, "--against", reasoned]) == 0


def test_layout_gate_skips_unfit_baseline(tmp_path):
    """A baseline that itself does not fit the HBM budget cannot anchor
    the predicted-score comparison — the searched winner was the only
    runnable choice."""
    ok = _round_with_layout(tmp_path, "unfit.json", _layout_block(
        predicted_score=900.0,
        baseline={"label": "dp8/z0/b2/r-names", "fits": False,
                  "predicted_tokens_per_sec": 1000.0}))
    assert bg.main([ok, "--against", ok]) == 0
