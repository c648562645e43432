"""Find the knee of an open-loop serving cell once, on the chip: the
highest arrival rate at which the queue does not grow through a window.
One engine, one rate after another; prints a line per rate. Not part of a
benchmark run: the rate it finds is written into the mix's file by hand.

    python3 benchmark/sweep.py --workload serve-chat-open \
        --rates 0.8,1.0,1.2,1.4,1.6 --seconds 60 --seed 5
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    import run

    _, cell = run.load_cell(REPO, args.workload)
    run.prepare(cell, on_tpu=True)
    from harness import serve, trace

    cfg = cell["config"]
    engine = serve.build_engine(cfg, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell["mix"], ramp_s=0.0,
                   arrivals=dict(cell["mix"]["arrivals"], rate=rate))
        load = serve.Load(engine, trace.Spans())
        t0, t1 = serve.run_open(load, mix, args,
                                {"start_window": lambda: None},
                                cfg["vocab_size"])
        mid = (t0 + t1) / 2
        reqs = list(load.req.values())
        first = [r["times"][0] - r["due"] for r in reqs
                 if r["times"] and r["due"] < mid]
        second = [r["times"][0] - r["due"] for r in reqs
                  if r["times"] and r["due"] >= mid]
        waiting = sum(1 for r in reqs if not r["times"])
        e2e, attempted = serve.window_metrics(load, t0, t1, cfg, {})
        load.cancel_rest()
        print(json.dumps({
            "rate": rate, "due": attempted, "no_first_token_at_close": waiting,
            "ttft_mean_first_half_s": statistics.fmean(first),
            "ttft_mean_second_half_s": statistics.fmean(second) if second
            else None, "ttft_max_s": max(first + second),
            "finished": len(load.done), **e2e}), flush=True)


if __name__ == "__main__":
    main()
