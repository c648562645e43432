"""The readings a limit is set from, on the chip at a cell's own size, many
seeds to a process: the program against the reference (the lower reading)
and the control (the reference in a lower precision, in the program's
place) against the reference (the upper). Not part of a benchmark run.

    python3 benchmark/readings.py --workload W --seeds 1,2,3 \
        [--control-seeds 2] [--control int8] [--seconds 20] [--out FILE]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]


def train_readings(cell, seeds, n_control, control, emit):
    from harness import train

    cfg, mix = cell["config"], cell["mix"]
    n = mix["check_steps"]
    tr = train.Trainer(cfg, mix, cell["chips"])
    prog = {}
    for seed in seeds:
        tr.load_seed(seed)
        prog[seed] = tr.first_steps(n)
        emit({"seed": seed, "program": prog[seed]["loss"]})
    batch = tr.batch
    tr.free()
    for i, seed in enumerate(seeds):
        ref = train.reference_readings(cfg, mix, seed, batch, n)
        row = {"seed": seed, "lower": train.compare(prog[seed], ref)}
        if i < n_control:
            row["upper"] = train.compare(train.reference_readings(
                cfg, mix, seed, batch, n, mode=control), ref)
            if batch > 1:  # half of the batch left out
                row["half_batch"] = train.compare(train.reference_readings(
                    cfg, mix, seed, batch, n, rows=slice(0, batch // 2)),
                    ref)
        emit(row)


def serve_readings(cell, seeds, n_control, control, seconds, emit):
    from harness import serve, trace

    cfg, mix = cell["config"], cell["mix"]

    class Args:
        pass

    for i, seed in enumerate(seeds):
        Args.seed, Args.seconds = seed, seconds
        engine = serve.build_engine(cfg, seed)
        load = serve.Load(engine, trace.Spans())
        env = {"start_window": lambda: None}
        loop = (serve.run_open if mix["kind"] == "serve-open"
                else serve.run_closed)
        t0, t1 = loop(load, mix, Args, env, cfg["vocab_size"])
        finished = load.finished(t0, t1)
        load.cancel_rest()
        e2e, _ = serve.window_metrics(load, t0, t1, cfg, {})
        load.engine = engine = None
        gc.collect()
        row = {"seed": seed, "e2e": e2e, "lower": serve.check_served(
            finished, cfg, seed, mix["check_requests"])}
        if i < n_control:
            row["upper"] = serve.check_served(
                finished, cfg, seed, mix["check_requests"], control=control)
        emit(row)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control", default="int8")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import run

    _, cell = run.load_cell(REPO, args.workload)
    run.prepare(cell, on_tpu=True)
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(dict(row, workload=args.workload))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        if cell["mix"]["kind"] == "train":
            train_readings(cell, seeds, args.control_seeds, args.control,
                           emit)
        else:
            serve_readings(cell, seeds, args.control_seeds, args.control,
                           args.seconds, emit)
    finally:
        if out:
            out.close()


if __name__ == "__main__":
    main()
