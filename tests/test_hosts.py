"""Cross-host fleet topology: rendezvous, placement, fenced leases,
partition/heal, shedding-becomes-migration (docs/SERVING.md "Cross-host
topology").

These tests run the REAL cross-host machinery — TCPStore rendezvous,
HostAgent spawn/kill RPCs, epoch-fenced transports, whole-host
sever/heal with fleet-wide replay, shed-rescue and steal-based
rebalance — against in-process agents and LocalChild replicas; the real
process-tree path (two AgentProc trees, SIGKILLed agent) is slow-marked
at the bottom.  The load-bearing guarantees:

- the supervisor discovers hosts by READING the store (agents register
  themselves; ordinals come from the atomic counter);
- replicas spread across hosts (the failure domains);
- an injected stale-epoch replay cannot double-serve a rid: the old
  lease's frames are fenced server-side and its late replies dropped
  client-side, so every token is delivered exactly once;
- a severed host's work replays on the survivors with zero lost
  requests, and a healed host's surviving workers are quarantined
  before adoption or retirement;
- ``PTPU_FLEET_HOSTS=0`` collapses hosts= topologies to the single-host
  PR 18 path, bitwise.
"""
import time

import pytest

from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.inference.fleet import (FleetSupervisor, build_workload,
                                        fleet_hosts_enabled,
                                        make_model_spec, partition_block,
                                        run_soak)
from paddle_tpu.inference.fleet import hosts as hosts_mod
from paddle_tpu.inference.fleet.transport import (LoopbackTransport,
                                                  RemoteEngine,
                                                  is_stale_lease)

CONFIG_KW = dict(vocab_size=64, hidden_size=32, num_layers=1,
                 num_heads=2, num_kv_heads=2, max_seq_len=64)
ENGINE_KW = dict(max_slots=2, page_size=8, max_new_tokens=4,
                 max_seq_len=48, seed=0)


def _spec(engine_kw=None, **kw):
    return make_model_spec(dict(CONFIG_KW), seed=0,
                           engine_kw=dict(ENGINE_KW, **(engine_kw or {})),
                           **kw)


def _sup(n=2, hosts=2, **kw):
    kw.setdefault("proc", False)
    kw.setdefault("lease_seconds", 120.0)
    kw.setdefault("host_lease_seconds", 0.2)
    spec = kw.pop("spec", None) or _spec(engine_kw=kw.pop("engine_kw", None))
    return FleetSupervisor(spec, n, hosts=hosts, **kw)


def _wl(n=12, seed=1):
    return build_workload(n, 50.0, (4, 6), 64, seed=seed)


def _drain(sup, want, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sup.step()
        if sup.outcomes()["served"] >= want:
            return True
        time.sleep(0.001)
    return False


# ---------------------------------------------------------------------------
# Rendezvous + agent RPC
# ---------------------------------------------------------------------------
class TestRendezvous:
    def test_agents_register_supervisor_discovers(self):
        store = TCPStore(is_master=True)
        try:
            directory = hosts_mod.HostDirectory(store)
            a = hosts_mod.HostAgent({}, host_id="hA", directory=directory,
                                    slots=3)
            b = hosts_mod.HostAgent({}, host_id="hB", directory=directory)
            assert a.register() == 0
            assert b.register() == 1
            assert directory.count() == 2
            recs = directory.wait_hosts(2, timeout=5.0)
            assert [r["host_id"] for r in recs] == ["hA", "hB"]
            assert recs[0]["slots"] == 3
            assert recs[0]["pid"] > 0
            # the agent never touches JAX (the chip belongs to its
            # workers): its record carries no device inventory
            assert "chips" not in recs[0]
        finally:
            store.close()

    def test_heartbeat_is_a_monotone_counter_not_a_timestamp(self):
        store = TCPStore(is_master=True)
        try:
            directory = hosts_mod.HostDirectory(store)
            a = hosts_mod.HostAgent({}, host_id="hA", directory=directory)
            a.register()                      # registers + first beat
            before = directory.beats(0)
            a.beat()
            assert directory.beats(0) == before + 1
            # the partition seam: a severed agent's beats stop advancing
            a.severed = True
            a.beat()
            assert directory.beats(0) == before + 1
        finally:
            store.close()

    def test_agent_spawns_and_kills_workers_with_slot_cap(self):
        agent = hosts_mod.HostAgent(_spec(), host_id="hA", slots=1)
        client = hosts_mod.AgentClient(LoopbackTransport(agent))
        try:
            assert client.info["host_id"] == "hA"
            assert client.ping() is True
            info = client.spawn_worker(None, 0)
            assert info["mode"] == "local" and info["replica_id"] == 0
            listed = client.list_workers()["workers"]
            assert listed["0"]["alive"] is True
            # slot cap: a second worker does not fit
            with pytest.raises(Exception):
                client.spawn_worker(None, 1)
            assert client.kill_worker(0)["killed"] is True
            assert client.kill_worker(0)["killed"] is False
        finally:
            agent.close()


# ---------------------------------------------------------------------------
# Fencing: the injected stale-epoch replay
# ---------------------------------------------------------------------------
class TestFencing:
    def test_stale_epoch_cannot_double_serve_a_rid(self):
        """The split-brain scenario, injected: an old lease keeps its
        link to a replica while the supervisor re-leases it at a higher
        epoch and replays the rid.  The old lease must be fenced at
        both ends — no token reaches two deliveries."""
        agent = hosts_mod.HostAgent(_spec(), host_id="hA", slots=2)
        agent_client = hosts_mod.AgentClient(LoopbackTransport(agent))
        try:
            agent_client.spawn_worker(None, 0)
            old_link = agent.worker_transport(0)
            old_link.epoch = 1
            old_eng = RemoteEngine(old_link)
            old_tokens = []
            rid = old_eng.submit([1, 2, 3], rid=7,
                                 on_token=lambda r, t: old_tokens.append(t))
            assert rid == 7

            # the supervisor's side of the partition: a NEW lease at a
            # higher epoch; the hello quarantines the old lease's state
            new_link = agent.worker_transport(0)
            new_link.epoch = 2
            new_eng = RemoteEngine(new_link)
            lease = new_eng.lease()
            assert lease["epoch"] == 2
            assert lease["quarantines"] == 1
            assert 7 in lease["quarantined_rids"]

            # the old lease is fenced server-side ...
            with pytest.raises(Exception) as ei:
                old_eng.step()
            assert is_stale_lease(ei.value)
            assert old_eng.transport.last_ep == 2

            # ... and the rid replays exactly once under the new lease
            new_tokens = []
            new_eng.submit([1, 2, 3], rid=7,
                           on_token=lambda r, t: new_tokens.append(t))
            finished = {}
            for _ in range(50):
                finished.update(new_eng.step())
                new_eng.stream()
                if 7 in finished:
                    break
            assert 7 in finished
            assert len(new_tokens) == ENGINE_KW["max_new_tokens"]
            assert old_tokens == []   # zero deliveries on the old lease
        finally:
            agent.close()


# ---------------------------------------------------------------------------
# The cross-host supervisor
# ---------------------------------------------------------------------------
class TestHostsSupervisor:
    def test_placement_spreads_and_epochs_are_monotone(self):
        sup = _sup(4, hosts=2)
        try:
            placed = [h.host for h in sup.router.replicas]
            assert sorted(placed) == ["host0", "host0", "host1", "host1"]
            epochs = [c.transport.epoch for c in sup.children.values()]
            assert sorted(epochs) == [1, 2, 3, 4]
            assert sup._push is True
            assert sup.summary()["hosts"] == {"host0": "alive",
                                              "host1": "alive"}
        finally:
            sup.close()

    def test_soak_conserves_across_hosts(self):
        sup = _sup(2, hosts=2)
        try:
            stats, _ = run_soak(sup, _wl(12))
            assert stats["outcomes_conserved"]
            assert stats["completed"] == 12
        finally:
            sup.close()

    def test_severed_host_replays_and_heals_without_duplicates(self):
        sup = _sup(2, hosts=2)
        try:
            delivered = {}
            for i in range(8):
                sup.submit([1, 2, 3 + i], on_token=lambda r, t:
                           delivered.setdefault(r, []).append(t))
            sup.step()
            sup.sever_host("host0")
            assert _drain(sup, 8)
            assert sup.host_severs == 1
            assert sup.outcomes()["served"] == 8
            # every stream delivered exactly once despite the replay
            assert sorted(len(v) for v in delivered.values()) == [4] * 8
            # the respawned replica landed on the surviving host
            live_hosts = {h.host for h in sup.router.replicas
                          if h.healthy and not h.retired}
            assert live_hosts == {"host1"}

            sup.heal_host("host0")
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline \
                    and sup.host_handles["host0"].state != "alive":
                sup.step()
                time.sleep(0.01)
            assert sup.host_handles["host0"].state == "alive"
            assert sup.host_heals == 1
            # fleet already at target: the stranded worker was fenced +
            # retired, not adopted as an extra replica
            live = [h for h in sup.router.replicas
                    if h.healthy and not h.retired]
            assert len(live) == 2
        finally:
            sup.close()

    def test_shed_rescue_dispatches_to_host_with_headroom(self):
        sup = _sup(2, hosts=2)
        try:
            # park a request in the router queue while both replicas
            # are draining, then rescue it explicitly
            for h in sup.router.replicas:
                h.draining = True
            delivered = []
            sup.submit([1, 2, 3],
                       on_token=lambda r, t: delivered.append(t))
            assert len(sup.router._pending) == 1
            entry = sup.router._pending[0]
            for h in sup.router.replicas:
                h.draining = False
            assert sup._rescue_shed(entry, "queue_depth") is True
            assert len(sup.router._pending) == 0
            assert _drain(sup, 1)
            assert len(delivered) == 4
        finally:
            sup.close()

    def test_rebalance_steals_queue_to_other_host(self):
        sup = _sup(2, hosts=2)
        try:
            # pile everything on replica 0 (host0) by draining host1
            sup.router.replicas[1].draining = True
            delivered = {}
            for i in range(8):
                sup.submit([1, 2, 3 + i], on_token=lambda r, t:
                           delivered.setdefault(r, []).append(t))
            sup.router.replicas[1].draining = False
            sup.router.max_queue_depth = 3
            sup._rebalance_tick()
            assert sup.rebalanced >= 1
            assert sup.summary()["migration_bytes"] > 0
            assert _drain(sup, 8)
            # exactly-once across the live migration
            assert sorted(len(v) for v in delivered.values()) == [4] * 8
        finally:
            sup.close()

    def test_prefix_warm_survives_a_drain(self):
        sup = _sup(2, hosts=2,
                   engine_kw=dict(enable_prefix_cache=True,
                                  prefill_chunk=8))
        try:
            # build the cache on replica 0 ONLY (drive its engine
            # directly, bypassing the router) — the peer must be cold
            prefix = list(range(1, 17))       # two full pages
            donor = sup.router.replicas[0]
            for i in range(4):
                donor.engine.submit(prefix + [30 + i], rid=900 + i)
            donor.engine.run_until_complete()
            assert donor.engine.export_prefix()
            peers = [sup.router.replicas[1]]
            warmed = sup._warm_prefix(donor, peers)
            assert warmed > 0
            assert sup.prefix_warm_pages == warmed
            assert peers[0].engine.prefix_match_pages(prefix) > 0
        finally:
            sup.close()

    def test_hosts_env_off_is_bitwise_single_host(self, monkeypatch):
        monkeypatch.setenv("PTPU_FLEET_HOSTS", "0")
        assert fleet_hosts_enabled() is False
        sup_a = _sup(2, hosts=2)
        try:
            assert sup_a.host_handles == {}
            assert [c.transport.epoch for c in sup_a.children.values()] \
                == [0, 0]
            assert sup_a._push is False
            assert all(h.host is None for h in sup_a.router.replicas)
            _, done_a = run_soak(sup_a, _wl(10))
        finally:
            sup_a.close()
        sup_b = FleetSupervisor(_spec(), 2, proc=False,
                                lease_seconds=120.0)
        try:
            _, done_b = run_soak(sup_b, _wl(10))
        finally:
            sup_b.close()
        assert done_a == done_b              # bitwise

    def test_partition_block_gates_clean(self):
        sup = _sup(2, hosts=2)
        try:
            block = partition_block(sup, _wl(16), host="host0",
                                    sever_tick=2)
        finally:
            sup.close()
        assert block["conserved"] is True
        assert block["lost_requests"] == 0
        assert block["duplicate_stream_tokens"] == 0
        assert block["lost_stream_tokens"] == 0
        assert block["fleet_live_at_drain"] is True
        assert block["partition"]["healed"] is True
        assert block["partition"]["host_severs"] == 1
        import sys
        sys.path.insert(0, "tools")
        try:
            import bench_gate
            assert bench_gate.partition_violations(block) == []
        finally:
            sys.path.remove("tools")


# ---------------------------------------------------------------------------
# Two real host processes (slow)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_two_proc_hosts_partition_kill_heal_upgrade(tmp_path):
    """The full chaos scenario on real process trees: two AgentProc
    hosts each running subprocess workers, one host partitioned away
    mid-soak and its agent SIGKILLed, plus a rolling weight upgrade —
    zero lost requests, zero duplicate tokens, fleet reconverged on the
    survivor."""
    sup = FleetSupervisor(
        _spec(), 2, proc=True, hosts=2, lease_seconds=120.0,
        host_lease_seconds=1.0, workdir=str(tmp_path),
        transport_kw=dict(timeouts={"step": 10.0, "submit": 10.0},
                          backoff=0.01))
    try:
        assert sup.summary()["proc_backend"] is True
        block = partition_block(
            sup, _wl(16), host="host0", sever_tick=3, kill_agent=True,
            upgrade_version=1, upgrade_tick=6)
    finally:
        sup.close()
    assert block["conserved"] is True
    assert block["lost_requests"] == 0
    assert block["duplicate_stream_tokens"] == 0
    assert block["lost_stream_tokens"] == 0
    assert block["fleet_live_at_drain"] is True
    assert block["partition"]["agent_killed"] is True
    assert block["upgrade"]["complete"] is True
    import sys
    sys.path.insert(0, "tools")
    try:
        import bench_gate
        assert bench_gate.partition_violations(block) == []
        assert bench_gate.upgrade_violations(block) == []
    finally:
        sys.path.remove("tools")
