"""``python -m paddle_tpu.distributed.launch`` — multi-host job launcher.

Capability parity: `python/paddle/distributed/launch/main.py:23` +
`controllers/collective.py` (pod/process model, env contract, restart).

TPU-native process model: ONE controller process per HOST drives all local
chips (multi-controller jax), so ``--nproc_per_node`` is 1 on TPU — unlike
the reference's process-per-GPU — and a larger value is refused there (a
chip belongs to one process). Values > 1 serve the CPU fake-backend path
(``JAX_PLATFORMS=cpu``; each process becomes one "rank").

Env contract written for each process (consumed by init_parallel_env):
  PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM, PADDLE_MASTER,
  PADDLE_LOCAL_RANK, PADDLE_NNODES, PADDLE_JOB_ID

Rendezvous: ``--master host:port`` backed by the native TCPStore
(core/native/store.cc); with ``--rank -1`` node ranks are auto-assigned
by an atomic ADD on the store. ``--max_restart`` relaunches failed
processes (elastic restart-from-checkpoint model, SURVEY §5 failure
detection).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time


def _parse():
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="TPU-native distributed launcher",
    )
    p.add_argument("--master", default=None,
                   help="rendezvous server host:port (TCPStore)")
    p.add_argument("--rank", type=int, default=-1,
                   help="node rank; -1 = auto-assign via master")
    p.add_argument("--nnodes", default="1",
                   help="number of nodes (elastic range 'lo:hi' takes lo)")
    p.add_argument("--nproc_per_node", type=int, default=None)
    p.add_argument("--log_dir", default="log")
    p.add_argument("--log_level", default="INFO")
    p.add_argument("--run_mode", default="collective")
    p.add_argument("--job_id", default="default")
    p.add_argument("--devices", default=None,
                   help="accepted for API parity; the TPU runtime binds all "
                        "local chips to the one controller process")
    p.add_argument("--max_restart", type=int, default=0)
    p.add_argument("--elastic_level", type=int, default=-1)
    p.add_argument("--elastic_timeout", type=int, default=30)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args()


def _nnodes(spec: str) -> int:
    return int(str(spec).split(":")[0])


def _rendezvous(master: str, rank: int, nnodes: int, job_id: str):
    """Return (node_rank, store_or_none). Starts the store on the master
    node (the one whose --rank is 0 or that can bind the port)."""
    from ..store import TCPStore

    host, port = master.split(":")
    port = int(port)
    store = None
    if rank == 0 or rank == -1:
        try:
            store = TCPStore(host="127.0.0.1", port=port, is_master=True,
                             world_size=nnodes)
        except Exception:
            store = None  # another node owns the master port
    if store is None:
        store = TCPStore(host=host, port=port, is_master=False,
                         world_size=nnodes)
    if rank == -1:
        rank = store.add(f"{job_id}/node_count", 1) - 1
    store.set(f"{job_id}/node/{rank}", str(os.getpid()))
    return rank, store


def _spawn_ranks(args, node_rank, nproc, world, script_args, generation=0):
    """Spawn `nproc` local rank processes; returns (procs, logfiles)."""
    procs, logs = [], []
    for i in range(nproc):
        rank = node_rank * nproc + i
        env = dict(os.environ)
        env.update(
            PADDLE_TRAINER_ID=str(rank),
            PADDLE_TRAINERS_NUM=str(world),
            PADDLE_LOCAL_RANK=str(i),
            PADDLE_NNODES=str(max(world // max(nproc, 1), 1)),
            PADDLE_JOB_ID=args.job_id,
            PADDLE_ELASTIC_GENERATION=str(generation),
            FLAGS_selected_tpus=str(i),
        )
        if args.master:
            env["PADDLE_MASTER"] = args.master
        log_path = os.path.join(args.log_dir, f"{args.job_id}.{rank}.log")
        lf = open(log_path, "ab")
        logs.append(lf)
        procs.append(subprocess.Popen(
            [sys.executable, args.training_script] + script_args,
            env=env, stdout=lf, stderr=subprocess.STDOUT,
        ))
    return procs, logs


def _launch_elastic(args, node_rank, nproc, min_world, script_args) -> None:
    """Elastic (level 2) process supervision: scale-in AND scale-out
    re-rendezvous.

    Capability parity: fleet/elastic/manager.py:462 `_match` + pod
    relaunch — on member death the job does NOT abort: the survivors are
    re-launched as a new *generation* with the shrunken world size (as
    long as it stays >= the `--nnodes lo` bound), and training resumes
    from checkpoint. Scale-out: a (re)joining member calls
    ElasticManager.request_join() against the job store (`--master`);
    the supervisor honors pending requests up to the original world by
    relaunching the next generation larger. Generation numbers reach
    workers via PADDLE_ELASTIC_GENERATION.
    """
    # Dedicated supervisor store on an EPHEMERAL port — never the --master
    # port, which rank 0 must bind for jax.distributed / rendezvous. The
    # endpoint reaches workers via PADDLE_ELASTIC_ENDPOINT; external
    # rejoiners get it out-of-band (it is printed on startup).
    from ..fleet.elastic import _store_int
    from ..store import TCPStore

    store = TCPStore(host="127.0.0.1", port=0, is_master=True, world_size=1)
    endpoint = f"127.0.0.1:{store.port}"
    os.environ["PADDLE_ELASTIC_ENDPOINT"] = endpoint
    sys.stderr.write(f"elastic: supervisor endpoint {endpoint}\n")

    def _pending_joins():
        raw = store.get("elastic/join_requests")
        return _store_int(raw) if raw else 0

    def _consume_joins(k):
        store.add("elastic/join_requests", -int(k))

    world = nproc
    generation = 0
    relaunches = 0
    while True:
        procs, logs = _spawn_ranks(args, node_rank, world, world,
                                   script_args, generation)
        # supervise: a dead member must trigger re-rendezvous IMMEDIATELY —
        # survivors may be blocked in a collective waiting for it, so
        # waiting for all ranks to exit would deadlock the job
        codes = [None] * world
        scale_out = 0
        last_join_check = 0.0
        while any(c is None for c in codes):
            time.sleep(0.2)
            codes = [p.poll() for p in procs]
            if any(c is not None and c != 0 for c in codes):
                for p, c in zip(procs, codes):
                    if c is None:
                        p.terminate()
                for p in procs:
                    p.wait()
                codes = [p.returncode for p in procs]
                break
            now = time.time()
            if now - last_join_check > 0.3:
                last_join_check = now
                joins = _pending_joins()
                if joins > 0:
                    grow = min(joins, nproc - world)
                    # consume EVERY pending request: capacity-exceeding
                    # requests are discarded, not banked — a stale request
                    # must never trigger a surprise re-rendezvous later
                    _consume_joins(joins)
                    if grow > 0:
                        for p in procs:
                            p.terminate()
                        for p in procs:
                            p.wait()
                        codes = [p.returncode for p in procs]
                        scale_out = grow
                        break
        for lf in logs:
            lf.close()
        if scale_out:
            relaunches += 1  # scale-out counts against max_restart too:
            if relaunches > args.max_restart:  # bounds join/term loops
                sys.stderr.write(
                    f"elastic: relaunch budget exhausted "
                    f"({relaunches}/{args.max_restart})\n")
                sys.exit(1)
            generation += 1
            world += scale_out
            sys.stderr.write(
                f"elastic: {scale_out} member(s) joined; re-rendezvous "
                f"generation {generation} with world {world}\n")
            time.sleep(0.3)
            continue
        if all(c == 0 for c in codes):
            store.close()
            return
        # terminated survivors (negative returncode from our SIGTERM) are
        # still members; only self-failed ranks count as dead
        n_dead = sum(1 for c in codes if c is not None and c > 0)
        n_dead = max(n_dead, 1)
        new_world = world - n_dead
        relaunches += 1
        if new_world < min_world or relaunches > args.max_restart:
            sys.stderr.write(
                f"elastic: cannot continue (world {world} -> {new_world}, "
                f"min {min_world}, relaunch {relaunches}/{args.max_restart})\n")
            sys.exit(next((c for c in codes if c and c > 0), 1))
        generation += 1
        sys.stderr.write(
            f"elastic: {n_dead} member(s) lost; re-rendezvous generation "
            f"{generation} with world {new_world}\n")
        world = new_world
        time.sleep(0.5)


def launch() -> None:
    args = _parse()
    nnodes = _nnodes(args.nnodes)
    nproc = args.nproc_per_node or 1
    if nproc > 1:
        from ...device import cpu_requested

        if not cpu_requested():
            # each rank only receives FLAGS_selected_tpus, which confines
            # nothing: every rank would open every local chip, and a chip
            # belongs to one process. One controller process per host
            # drives all its chips (multi-controller jax).
            sys.exit(
                f"paddle_tpu.distributed.launch: --nproc_per_node {nproc} "
                "on a TPU host would start several processes on the same "
                "chips, and a chip belongs to one process — use one "
                "process per host (it drives every local chip), or set "
                "JAX_PLATFORMS=cpu for the CPU fake-backend path.")
    node_rank = max(args.rank, 0)
    store = None
    if args.master and nnodes > 1:
        node_rank, store = _rendezvous(args.master, args.rank, nnodes,
                                       args.job_id)

    world = nnodes * nproc
    os.makedirs(args.log_dir, exist_ok=True)
    script_args = [a for a in args.training_script_args if a != "--"]

    if args.elastic_level >= 2 and nnodes == 1:
        _launch_elastic(args, node_rank, nproc, nnodes, script_args)
        if store is not None:
            store.close()
        return
    if args.elastic_level >= 2 and nnodes > 1:
        # Per-rank elastic supervision is single-node only today; multi-node
        # jobs degrade to the whole-job restart loop below. Say so loudly
        # instead of silently downgrading the documented behavior.
        sys.stderr.write(
            "paddle_tpu.launch: --elastic_level >= 2 with nnodes > 1 is not "
            "supported; falling back to whole-job restart (max_restart="
            f"{args.max_restart}). Scale-in/out supervision runs only with "
            "nnodes == 1.\n")

    for attempt in range(args.max_restart + 1):
        procs, logs = _spawn_ranks(args, node_rank, nproc, world, script_args)
        codes = [p.wait() for p in procs]
        for lf in logs:
            lf.close()
        if all(c == 0 for c in codes):
            break
        if attempt == args.max_restart:
            for rank, c in enumerate(codes):
                if c != 0:
                    log_path = os.path.join(
                        args.log_dir, f"{args.job_id}.{node_rank * nproc + rank}.log")
                    sys.stderr.write(
                        f"rank {rank} exited {c}; last log lines "
                        f"({log_path}):\n")
                    try:
                        with open(log_path, "rb") as f:
                            sys.stderr.write(
                                f.read()[-2000:].decode(errors="replace"))
                    except OSError:
                        pass
            sys.exit(next((c for c in codes if c and c > 0), 1))
        time.sleep(1.0)

    if store is not None:
        store.close()
