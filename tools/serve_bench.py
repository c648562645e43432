"""Fleet serving soak benchmark (docs/SERVING.md soak recipe).

Drives Poisson-arrival synthetic traffic (mixed prompt lengths,
optional shared system prefix / sampled fraction / deadlines) against
1..N engine replicas behind a FleetRouter and prints ONE JSON metric
line per replica count:

    {"metric": "serve_goodput_tokens_per_sec_rN", "value": <goodput>,
     "unit": "tokens/sec", "serving": {<gateable block>}}

``tools/bench_gate.py`` consumes these lines like any bench artifact:
reference-free gates on ``p99_ttft_seconds`` vs ``p99_ttft_budget``
(derived from the single-replica run's p50 unless --ttft-budget pins
it) and ``goodput_x_single`` vs ``--scaling-target`` (the acceptance
bar: 4 replicas >= 3.5x single-replica goodput), plus a referenced
cold-start gate at the same scan mode.

Goodput and TTFT run on the soak harness's simulated-parallel clock
(replicas tick concurrently in deployment; see
paddle_tpu/inference/fleet/soak.py). Run from /root/repo:

    python tools/serve_bench.py                      # CPU smoke, r1+r2
    python tools/serve_bench.py --replicas 1 4 --requests 2000 \
        --scaling-target 3.5                         # the soak gate run
    python tools/serve_bench.py --disagg --spec --int8-kv \
        --prefix-cache --shared-prefix 64            # full topology
    python tools/serve_bench.py --overload           # 2x-capacity
        # overload scenario: mixed priorities, one chaos-flapping
        # replica, admission/shedding/breakers/brownout on — emits the
        # OVERLOAD-gated "overload" block (docs/SERVING.md)
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import numpy as np


def serving_sizes(on_tpu):
    """The decoder and engine geometry of the serving bench:
    ``(config kwargs, sizes)``. On TPU a 16-layer h=2048 bf16 decoder
    behind a 16-slot engine; the tiny CPU smoke otherwise."""
    if on_tpu:
        cfg = dict(vocab_size=32000, hidden_size=2048, num_layers=16,
                   num_heads=16, max_seq_len=1024, dropout=0.0)
        sizes = dict(requests=2000, prompt_lens=(64, 128, 256, 512),
                     max_new=64, page=64, slots=16, chunk=128,
                     max_seq=1024, replicas=[1, 4])
    else:
        cfg = dict(vocab_size=256, hidden_size=64, num_layers=2,
                   num_heads=4, num_kv_heads=2, max_seq_len=128,
                   dropout=0.0)
        sizes = dict(requests=96, prompt_lens=(6, 10, 14, 20),
                     max_new=8, page=8, slots=4, chunk=8, max_seq=64,
                     replicas=[1, 2])
    return cfg, sizes


def build_decoder(cfg_kw, seed, bf16):
    """Seeded random-weight decoder (bf16 parameters on the TPU), built
    the one way a fleet worker builds it from its spec."""
    from paddle_tpu.inference.fleet.cluster import (build_model_from_spec,
                                                    make_model_spec)

    return build_model_from_spec(make_model_spec(
        cfg_kw, seed=seed, dtype="bfloat16" if bf16 else None))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="fleet serving soak benchmark (docs/SERVING.md)")
    ap.add_argument("--replicas", type=int, nargs="+", default=None,
                    help="replica counts to sweep (default: 1 2 on CPU, "
                    "1 4 on TPU; 1 is always prepended as the baseline)")
    ap.add_argument("--requests", type=int, default=None,
                    help="synthetic requests per sweep point "
                    "(default 96 CPU / 2000 TPU)")
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate, req/sim-second "
                    "(default: saturating)")
    ap.add_argument("--policy", default="least_loaded",
                    help="router policy: least_loaded | round_robin | "
                    "prefix_affinity")
    ap.add_argument("--disagg", action="store_true",
                    help="replicas are disaggregated prefill/decode pairs")
    ap.add_argument("--spec", action="store_true",
                    help="attach a 1-layer draft model (speculative "
                    "decoding) to every replica")
    ap.add_argument("--spec-tokens", type=int, default=3)
    ap.add_argument("--int8-kv", action="store_true",
                    help="request the int8 paged KV mode (engages only "
                    "behind the parity probe; PTPU_INT8_KV overrides)")
    ap.add_argument("--prefix-cache", action="store_true")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of shared system prompt per request")
    ap.add_argument("--sampled-fraction", type=float, default=0.0)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline_seconds")
    ap.add_argument("--scaling-target", type=float, default=None,
                    help="gate: multi-replica goodput must reach this "
                    "multiple of the single-replica run (e.g. 3.5 at 4 "
                    "replicas)")
    ap.add_argument("--ttft-budget", type=float, default=None,
                    help="gate: absolute p99 TTFT bound in sim-seconds "
                    "(default: 10x the single-replica p50)")
    ap.add_argument("--ttft-budget-x", type=float, default=10.0,
                    help="derived budget = this x single-replica p50")
    ap.add_argument("--overload", action="store_true",
                    help="after the sweep, run the overload scenario: "
                    "sustained arrivals at --overload-x the measured "
                    "fleet capacity, mixed interactive/batch "
                    "priorities, one chaos-flapping replica, overload "
                    "control on — emits the gateable 'overload' block "
                    "(docs/SERVING.md 'Overload & degradation')")
    ap.add_argument("--overload-x", type=float, default=2.0,
                    help="overload arrival rate as a multiple of the "
                    "measured capacity (default 2.0)")
    ap.add_argument("--overload-requests", type=int, default=None,
                    help="requests in the overload scenario (default: "
                    "same as --requests)")
    ap.add_argument("--procs", type=int, default=None,
                    help="run the multi-process fleet scenario instead "
                    "of the in-process sweep: N replicas as real OS "
                    "processes behind the socket transport "
                    "(FleetSupervisor), one replica SIGKILLed "
                    "mid-soak, a chaos-injected link, and a rolling "
                    "weight upgrade — emits the gateable 'upgrade' "
                    "block (docs/SERVING.md 'Process topology'). "
                    "PTPU_FLEET_PROC=0 falls back to in-process "
                    "loopback children, bitwise")
    ap.add_argument("--hosts", type=int, default=None,
                    help="run the cross-host fleet scenario instead of "
                    "the in-process sweep: replicas spread across N "
                    "host agents discovered through the rendezvous "
                    "store, one whole host partitioned away mid-soak "
                    "(fenced leases + fleet-wide replay), then healed "
                    "— emits the gateable 'partition' block "
                    "(docs/SERVING.md 'Cross-host topology'). "
                    "PTPU_FLEET_HOSTS=0 collapses to the single-host "
                    "topology, bitwise")
    ap.add_argument("--sever-tick", type=int, default=4,
                    help="soak tick at which the host partition starts "
                    "(--hosts scenario)")
    ap.add_argument("--heal-tick", type=int, default=None,
                    help="soak tick at which the partition heals "
                    "(--hosts scenario; default: after the soak drains)")
    ap.add_argument("--kill-agent", action="store_true",
                    help="also SIGKILL the severed host's agent "
                    "(--hosts scenario; the host stays lost and the "
                    "fleet must reconverge on the survivors)")
    ap.add_argument("--kill-tick", type=int, default=3,
                    help="soak tick at which one replica is SIGKILLed "
                    "(--procs scenario; negative disables the kill)")
    ap.add_argument("--upgrade-tick", type=int, default=6,
                    help="soak tick at which the rolling weight "
                    "upgrade starts (--procs scenario)")
    ap.add_argument("--no-chaos", action="store_true",
                    help="skip the ChaosTransport link faults in the "
                    "--procs scenario")
    ap.add_argument("--window-goodput-floor", type=float, default=None,
                    help="gate: goodput inside the upgrade window must "
                    "stay above this fraction of whole-run goodput "
                    "(opt-in — completion-based goodput is lumpy at "
                    "smoke scale)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeline-dir", default=None,
                    help="record a per-tick timeline JSONL per soak "
                    "into this directory (serve_rN.jsonl / "
                    "serve_overload_rN.jsonl) and run the SLO engine "
                    "live — the blocks then embed 'timeline' and 'slo' "
                    "sub-blocks (docs/TELEMETRY.md)")
    args = ap.parse_args(argv)

    from paddle_tpu.device import (compile_cache_dir, cpu_requested,
                                   device_record, require_accelerator)
    from paddle_tpu.inference.fleet import build_workload, soak_block
    from paddle_tpu.models.llama import LlamaConfig

    # --procs/--hosts: the replicas are child processes and a chip belongs
    # to one process, so this parent must never initialise a backend —
    # the size comes from the environment and each child builds its own
    # model from the spec (worker.py), failing there if it finds no chip
    multiproc = bool(args.procs or args.hosts)
    on_tpu = (not cpu_requested() if multiproc
              else require_accelerator("tools/serve_bench.py"))
    cfg_kw, sz = serving_sizes(on_tpu)
    requests = args.requests or sz["requests"]
    prompt_lens = sz["prompt_lens"]
    max_new, page, slots, chunk, max_seq = (
        sz["max_new"], sz["page"], sz["slots"], sz["chunk"], sz["max_seq"])
    replica_counts = args.replicas or sz["replicas"]
    if replica_counts[0] != 1:
        replica_counts = [1] + list(replica_counts)
    # a shared prefix longer than the drawn prompt length yields
    # prefix+1 tokens — grow the sequence geometry to fit the longest
    # possible prompt + generation (+ spec headroom) instead of
    # crashing the first submit
    max_prompt = max(max(prompt_lens), args.shared_prefix + 1)
    need = max_prompt + max_new + (args.spec_tokens if args.spec else 0)
    if need > max_seq:
        max_seq = need
        cfg_kw["max_seq_len"] = max(cfg_kw["max_seq_len"], max_seq)
    cfg = LlamaConfig(**cfg_kw)  # resolves num_kv_heads et al.

    model = draft = None
    if not multiproc:
        compile_cache_dir()
        model = build_decoder(cfg_kw, args.seed, bf16=on_tpu)
        if args.spec:
            draft = build_decoder(
                dict(vocab_size=cfg.vocab_size,
                     hidden_size=cfg.hidden_size // 2,
                     num_layers=1, num_heads=max(1, cfg.num_heads // 2),
                     num_kv_heads=max(1, cfg.num_kv_heads // 2),
                     max_seq_len=cfg.max_seq_len, dropout=0.0),
                args.seed + 1, bf16=on_tpu)

    workload = build_workload(
        requests, args.rate or (requests * 4.0), prompt_lens,
        cfg.vocab_size, shared_prefix=args.shared_prefix,
        sampled_fraction=(0.0 if args.spec else args.sampled_fraction),
        deadline_seconds=args.deadline, seed=args.seed)

    engine_kw = dict(max_seq_len=max_seq, max_new_tokens=max_new,
                     prefill_chunk=chunk, int8_kv=args.int8_kv,
                     spec_tokens=args.spec_tokens)
    disagg_kw = None
    if args.disagg:
        disagg_kw = dict(prefill_slots=max(2, slots // 2),
                         decode_slots=slots, page_size=page,
                         enable_prefix_cache=args.prefix_cache)
    else:
        engine_kw.update(max_slots=slots, page_size=page,
                         enable_prefix_cache=args.prefix_cache)

    if args.hosts:
        from paddle_tpu.inference.fleet import (FleetSupervisor,
                                                fleet_hosts_enabled,
                                                fleet_proc_enabled,
                                                make_model_spec,
                                                partition_block)

        n_hosts = args.hosts
        if not fleet_hosts_enabled():
            sys.stderr.write("# serve_bench: PTPU_FLEET_HOSTS=0 — "
                             "cross-host scenario collapses to the "
                             "single-host topology; skipping\n")
            return
        n = max(max(replica_counts), n_hosts)
        he_kw = dict(engine_kw)
        he_kw.setdefault("max_slots", slots)
        he_kw.setdefault("page_size", page)
        he_kw["seed"] = args.seed
        spec = make_model_spec(
            cfg_kw,
            seed=args.seed, engine_kw=he_kw,
            dtype="bfloat16" if on_tpu else None)
        proc = fleet_proc_enabled()
        sup = FleetSupervisor(
            spec, n, proc=proc, policy=args.policy, hosts=n_hosts,
            lease_seconds=120.0, host_lease_seconds=1.0,
            transport_kw=dict(timeouts={"step": 10.0, "submit": 10.0},
                              backoff=0.01))
        try:
            block = partition_block(
                sup, workload, host="host0",
                sever_tick=args.sever_tick, heal_tick=args.heal_tick,
                kill_agent=args.kill_agent,
                upgrade_version=(1 if args.upgrade_tick >= 0 else None),
                upgrade_tick=(args.upgrade_tick
                              if args.upgrade_tick >= 0 else None))
        finally:
            sup.close()
        print(json.dumps({
            "metric": f"serve_crosshost_goodput_h{n_hosts}_r{n}",
            "value": block.get("goodput_tokens_per_sec"),
            "unit": "tokens/sec",
            "partition": block,
        }), flush=True)
        return

    if args.procs:
        from paddle_tpu.inference.fleet import (FleetSupervisor,
                                                fleet_proc_enabled,
                                                make_model_spec,
                                                upgrade_block)
        from paddle_tpu.testing.chaos import ChaosTransport

        n = args.procs
        proc = fleet_proc_enabled()
        if not proc:
            sys.stderr.write("# serve_bench: PTPU_FLEET_PROC=0 — "
                             "in-process loopback children (bitwise "
                             "fallback)\n")
        # the multi-process scenario always runs plain engines (the
        # transport/supervisor mechanics are topology-independent)
        pe_kw = dict(engine_kw)
        pe_kw.setdefault("max_slots", slots)
        pe_kw.setdefault("page_size", page)
        pe_kw["seed"] = args.seed
        spec = make_model_spec(
            cfg_kw,
            seed=args.seed, engine_kw=pe_kw,
            dtype="bfloat16" if on_tpu else None)
        chaos = None
        if not args.no_chaos and n > 1:
            # deterministic small fault schedule on replica 1's link:
            # one dropped request (timeout + idempotent re-send), one
            # duplicated frame (served from the reply cache), one
            # corrupted frame (CRC reject, re-send)
            chaos = {1: lambda t: ChaosTransport(
                t, drop_sends={5}, duplicate_sends={9},
                corrupt_sends={13})}
        sup = FleetSupervisor(
            spec, n, proc=proc, policy=args.policy, chaos=chaos,
            lease_seconds=120.0,
            transport_kw=dict(timeouts={"step": 10.0, "submit": 10.0},
                              backoff=0.01))
        try:
            block = upgrade_block(
                sup, workload, version=1,
                upgrade_tick=args.upgrade_tick,
                kill_tick=(args.kill_tick if args.kill_tick >= 0
                           and n > 1 else None),
                kill_replica=0,
                window_goodput_floor=args.window_goodput_floor,
                window_ttft_budget=args.ttft_budget)
            # the device each CHILD serves from, from its handshake (this
            # parent holds no backend to ask)
            block["devices"] = [getattr(c, "device", None)
                                for c in sup.children.values()]
        finally:
            sup.close()
        block["chaos"] = (None if chaos is None else
                          {"link": 1, "drop_sends": [5],
                           "duplicate_sends": [9], "corrupt_sends": [13]})
        print(json.dumps({
            "metric": f"serve_upgrade_procs_r{n}",
            "value": block.get("goodput_tokens_per_sec"),
            "unit": "tokens/sec",
            "upgrade": block,
        }), flush=True)
        return

    baseline = None
    for n in replica_counts:
        budget = args.ttft_budget
        if budget is None and baseline is not None:
            p50 = (baseline.get("ttft") or {}).get("p50")
            budget = args.ttft_budget_x * p50 if p50 else None
        timeline = (os.path.join(args.timeline_dir,
                                 f"serve_r{n}.jsonl")
                    if args.timeline_dir else None)
        block = soak_block(
            model, replicas=n, workload=workload, policy=args.policy,
            disagg=args.disagg, draft_model=draft, engine_kw=engine_kw,
            disagg_kw=disagg_kw, baseline=baseline,
            scaling_target=(args.scaling_target if n > 1 else None),
            ttft_budget=(budget if n > 1 or args.ttft_budget else None),
            timeline_path=timeline)
        if baseline is None:
            baseline = block
        print(json.dumps({
            "metric": f"serve_goodput_tokens_per_sec_r{n}",
            "value": block.get("goodput_tokens_per_sec"),
            "unit": "tokens/sec",
            "device": device_record(),
            "serving": block,
        }), flush=True)

    if args.overload:
        from paddle_tpu.inference.fleet import OverloadConfig
        from paddle_tpu.inference.fleet.soak import (overload_block,
                                                     overload_workload)
        from paddle_tpu.testing.chaos import ChaosReplica

        n = max(replica_counts)
        # measured capacity: what ONE replica actually served per
        # simulated second in the baseline sweep run
        base_rate = (baseline["completed"]
                     / max(baseline["sim_seconds"], 1e-9))
        p50 = (baseline.get("ttft") or {}).get("p50") or 0.1
        slo = args.ttft_budget or args.ttft_budget_x * p50
        n_over = args.overload_requests or requests
        wl = overload_workload(
            base_rate * n, n_over, prompt_lens, cfg.vocab_size,
            rate_x_capacity=args.overload_x, batch_fraction=0.4,
            seed=args.seed + 7)
        depth = 2 * n * slots
        ov_cfg = OverloadConfig(
            ttft_slo=slo, admit_depth=2 * depth, shed_depth=depth,
            breaker_backoff=0.02, breaker_threshold=2,
            breaker_close_after=2, brownout_up_ticks=3,
            brownout_down_ticks=6)
        flap = (12, 3)
        holder = []

        def wrap(e):
            holder.append(ChaosReplica(e, flap=flap))
            return holder[-1]

        # the overload scenario always runs plain engines (the breaker /
        # brownout mechanics are topology-independent); a --disagg sweep
        # kept slots/page in disagg_kw, so re-add them here
        ov_engine_kw = dict(engine_kw)
        ov_engine_kw.setdefault("max_slots", slots)
        ov_engine_kw.setdefault("page_size", page)
        block = overload_block(
            model, replicas=n, workload=wl, overload_cfg=ov_cfg,
            policy=args.policy, engine_kw=ov_engine_kw,
            chaos_wrap={0: wrap}, ttft_budget=2.0 * slo,
            shed_ceiling=0.9, rate_x_capacity=args.overload_x,
            timeline_path=(os.path.join(
                args.timeline_dir, f"serve_overload_r{n}.jsonl")
                if args.timeline_dir else None))
        # bound the breaker flap count by the fault bursts the chaos
        # schedule actually fired: at most two opens per down-phase
        # (threshold-crossing + one failed half-open probe inside the
        # same burst), never one per fault
        chaos = holder[0]
        bursts = chaos.steps // (flap[0] + flap[1]) + 1
        block["breaker_flap_bound"] = 2 * bursts + 2
        block["chaos"] = {"flap": list(flap), "steps": chaos.steps,
                          "faults": chaos.faults}
        print(json.dumps({
            "metric": f"serve_overload_goodput_r{n}",
            "value": block.get("goodput_tokens_per_sec"),
            "unit": "tokens/sec",
            "device": device_record(),
            "overload": block,
        }), flush=True)


if __name__ == "__main__":
    main()
