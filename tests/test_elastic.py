"""Elastic manager + launcher scale-in tests.

Parity: fleet/elastic/manager.py:125-520 (membership over leases,
generation-driven re-rendezvous, scale-in with checkpoint resume).
"""
import os
import socket
import subprocess
import sys

import pytest

from paddle_tpu.distributed.fleet.elastic import ElasticManager
from paddle_tpu.distributed.store import TCPStore


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def store_pair():
    port = _free_port()
    master = TCPStore(host="127.0.0.1", port=port, is_master=True, world_size=2)
    client = TCPStore(host="127.0.0.1", port=port, is_master=False, world_size=2)
    yield master, client
    client.close()
    master.close()


class TestElasticManager:
    def test_membership_and_heartbeat(self, store_pair):
        master, client = store_pair
        a = ElasticManager(store=master, heartbeat_timeout=5.0)
        b = ElasticManager(store=client, heartbeat_timeout=5.0)
        a.member_id, b.member_id = "nodeA", "nodeB"
        a.announce()
        b.announce()
        a.register()
        b.register()
        assert a.alive_members() == ["nodeA", "nodeB"]
        assert not a.should_restart() or a.np <= 2  # np from env default 1

    def test_stale_member_drops_out(self, store_pair):
        master, client = store_pair
        a = ElasticManager(store=master, heartbeat_timeout=0.2)
        b = ElasticManager(store=client, heartbeat_timeout=0.2)
        a.member_id, b.member_id = "nodeA", "nodeB"
        a.announce()
        b.announce()
        a._beat(0)
        b._beat(0)
        import time

        time.sleep(0.3)
        a._beat(0)  # only A refreshes
        assert a.alive_members() == ["nodeA"]

    def test_generation_bump_observed_by_peer(self, store_pair):
        master, client = store_pair
        a = ElasticManager(store=master)
        b = ElasticManager(store=client)
        g0 = b.generation()
        assert not b.membership_changed(g0)
        a.bump_generation()
        assert b.membership_changed(g0)
        assert b.wait_generation_change(g0, timeout=2.0) == g0 + 1

    def test_rerendezvous_dense_ranks_and_world(self, store_pair):
        master, client = store_pair
        a = ElasticManager(store=master)
        b = ElasticManager(store=client)
        a.member_id, b.member_id = "survivor1", "survivor2"
        a.bump_generation()
        a.freeze_world(2)
        ra, wa, ga = a.rerendezvous()
        rb, wb, gb = b.rerendezvous()
        assert sorted([ra, rb]) == [0, 1]     # dense new ranks
        assert wa == wb == 2                   # frozen world
        assert ga == gb == 1
        # both members visible in the new generation's roster
        assert a.alive_members(gen=1) == ["survivor1", "survivor2"]
        a.exit()
        b.exit()


@pytest.mark.slow
def test_launcher_elastic_scale_in(tmp_path):
    """3 ranks; rank 2 dies at step 3 -> relaunch generation 1 with world 2,
    survivors resume from the checkpoint (start_step >= 3) and finish."""
    worker = os.path.join(os.path.dirname(__file__), "launch_assets",
                          "elastic_worker.py")
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/root"),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "JAX_PLATFORMS": "cpu",
    }
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "1", "--nproc_per_node", "3",
         "--elastic_level", "2", "--max_restart", "2",
         "--log_dir", str(tmp_path / "logs"),
         worker],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(tmp_path),
    )
    logs = ""
    for f in sorted((tmp_path / "logs").iterdir()):
        logs += f"\n--- {f.name} ---\n" + f.read_text()
    assert proc.returncode == 0, (proc.stderr[-2000:], logs[-4000:])
    assert "re-rendezvous generation 1 with world 2" in proc.stderr, proc.stderr
    ok_lines = [ln for ln in logs.splitlines() if ln.startswith("ELASTIC_OK")]
    gen1 = [ln for ln in ok_lines if "gen=1" in ln]
    assert len(gen1) == 2, ok_lines
    for ln in gen1:
        assert "world=2" in ln
        start = int(ln.split("start_step=")[1])
        assert start >= 3, ln  # resumed from checkpoint, not from scratch


@pytest.mark.slow
def test_launcher_elastic_scale_out(tmp_path):
    """Scale-in then scale-OUT: rank dies -> world 2; a join request via
    the job store -> world back to 3; all three finish from checkpoint."""
    worker = os.path.join(os.path.dirname(__file__), "launch_assets",
                          "elastic_join_worker.py")
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/root"),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "JAX_PLATFORMS": "cpu",
    }
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "1", "--nproc_per_node", "3",
         "--elastic_level", "2", "--max_restart", "4",
         "--log_dir", str(tmp_path / "logs"),
         worker],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=str(tmp_path),
    )
    logs = ""
    for f in sorted((tmp_path / "logs").iterdir()):
        logs += f"\n--- {f.name} ---\n" + f.read_text()
    assert proc.returncode == 0, (proc.stderr[-2500:], logs[-4000:])
    assert "re-rendezvous generation 1 with world 2" in proc.stderr
    assert "joined; re-rendezvous generation 2 with world 3" in proc.stderr
    done = [ln for ln in logs.splitlines()
            if ln.startswith("ELASTIC_OK") and "gen=2" in ln]
    assert len(done) == 3, (proc.stderr[-1500:], logs[-3000:])
    for ln in done:
        assert "world=3" in ln
        assert int(ln.split("start_step=")[1]) >= 4  # resumed, not restarted


class TestStoreClock:
    def test_wait_deadline_runs_on_injected_monotonic_clock(self):
        """Regression: wait() deadlines are measured on the store's own
        monotonic clock, never wall time — an NTP step must not hang or
        instantly expire a rendezvous wait.  With an injected clock that
        jumps 10 "seconds" per probe, a 25s timeout expires after ~3
        polls of real sleep (<1s wall), proving the deadline math reads
        the injected clock and not time.time()/time.monotonic()."""
        import time as _time

        port = _free_port()
        master = TCPStore(host="127.0.0.1", port=port, is_master=True,
                          world_size=1)
        ticks = {"n": 0}

        def fake_clock():
            ticks["n"] += 1
            return ticks["n"] * 10.0

        client = TCPStore(host="127.0.0.1", port=port, is_master=False,
                          world_size=1, clock=fake_clock)
        try:
            start = _time.monotonic()
            with pytest.raises(TimeoutError, match="missing/key"):
                client.wait("missing/key", timeout=25.0)
            # real wall time stays tiny: the 25s budget was consumed by
            # the fake clock, not by sleeping
            assert _time.monotonic() - start < 5.0
            assert ticks["n"] >= 2  # deadline set + at least one check
            # an existing key is still returned immediately
            master.set("present", b"v")
            assert client.wait("present", timeout=25.0) == b"v"
        finally:
            client.close()
            master.close()
