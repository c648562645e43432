"""Calibrate the auto_tuner's time/memory models at BENCH scale on the
real chip (VERDICT r3 item 10): run measure() over the top single-chip
configs of the GPT-3 1.3B bench model and record predicted-vs-measured in
docs/TUNER_CALIBRATION.md. Run from the checkout root on a machine with
the chip; takes a few minutes of chip time (one compile per config).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.getcwd())  # run as `python tools/calibrate_tuner.py`
                                 # from the checkout root


def main():
    import jax

    from paddle_tpu.distributed.auto_tuner import AutoTuner

    kind = jax.devices()[0].device_kind.lower()
    on_tpu = jax.default_backend() not in ("cpu",)
    tflops = (197.0 if on_tpu else 0.05)
    hbm = (15.75 if on_tpu else 64.0)

    t = AutoTuner({
        "world_size": 1,
        "model_cfg": dict(
            hidden_size=2048, num_layers=24, num_attention_heads=16,
            vocab_size=32000, seq_length=2048, global_batch_size=4,
            bytes_per_param=2, hbm_gb=hbm, mxu_tflops=tflops,
            ici_gbps=100.0),
        "max_mp_degree": 1,
        "max_pp_degree": 1,
        "tune_recompute": True,   # nothing single-chip fits without remat
    })
    best, ranked = t.measure(top_k=3, steps=3)
    rows = []
    for r in t.calibration:
        c = r["cfg"]
        rows.append({
            "cfg": f"dp{c.dp}/mp{c.mp}/pp{c.pp}/shard{c.sharding}"
                   f"/mbs{c.micro_batch}/rc:{c.recompute}",
            "predicted_ms": round(r["predicted_ms"], 1),
            "measured_ms": round(r.get("measured_ms", float("nan")), 1),
            "time_ratio": round(r.get("time_ratio", float("nan")), 2),
            "predicted_gb": round(r["predicted_gb"], 2),
            "measured_gb": round(r.get("measured_gb", float("nan")), 2),
            "memory_ratio": round(r.get("memory_ratio", float("nan")), 2),
            "tokens_per_sec": round(r["tokens_per_sec"], 0),
        })
    print(json.dumps(rows, indent=1))
    dev = kind if on_tpu else "cpu"
    lines = [
        "# auto_tuner calibration at bench scale (round 4)",
        "",
        f"`tools/calibrate_tuner.py` on ONE real chip ({dev}): "
        "`AutoTuner.measure()` over the top single-chip configs of the "
        "GPT-3 1.3B bench model (BASELINE.md config 4), 3 timed steps "
        "each. VERDICT r3 item 10: the 2x memory-model bound had only "
        "been checked at toy scale on the CPU mesh.",
        "",
        "| cfg | pred ms | meas ms | t-ratio | pred GB | meas GB "
        "| m-ratio | tok/s |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['cfg']} | {r['predicted_ms']} | {r['measured_ms']} "
            f"| {r['time_ratio']} | {r['predicted_gb']} "
            f"| {r['measured_gb']} | {r['memory_ratio']} "
            f"| {r['tokens_per_sec']} |")
    lines += [
        "",
        "Bound check: time_ratio and memory_ratio must sit in [0.5, 2.0] "
        "for the static models to stay trustworthy rankers; rows outside "
        "the bound are a model bug to fix, not a footnote.",
        "",
    ]
    with open("docs/TUNER_CALIBRATION.md", "w") as f:
        f.write("\n".join(lines))
    print("wrote docs/TUNER_CALIBRATION.md")
    bad = [r for r in rows
           if not (0.5 <= r["time_ratio"] <= 2.0
                   and 0.5 <= r["memory_ratio"] <= 2.0)]
    if bad:
        print("OUT OF BOUND:", json.dumps(bad, indent=1))


if __name__ == "__main__":
    main()
