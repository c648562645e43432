"""Fleet transport: frame codec, RPC semantics, exception wire format,
chaos fault injection, and the host-snapshot wire contract.

The load-bearing guarantees (docs/SERVING.md "Process topology"):
- frames round-trip bitwise under both codecs (msgpack and the stdlib
  fallback), and truncated/corrupt frames raise loudly — a frame is
  either delivered intact or rejected, never half-parsed;
- structured terminal outcomes (``Overloaded`` and friends) cross the
  RPC boundary intact — a child-process reject reaches the client with
  its retry_after / reason / predicted_ttft;
- retries are idempotent: a dropped or duplicated frame never makes the
  server execute a call twice;
- transport faults classify as transient (they feed the breakers, not
  a crash);
- ``extract() -> serialize -> pipe -> deserialize -> inject()``
  round-trips bitwise for fp AND int8 paged KV.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.fleet import wire
from paddle_tpu.inference.fleet.overload import (
    Overloaded, TransientReplicaError, RemoteReplicaError,
    classify_step_exception, outcome_from_wire, outcome_to_wire)
from paddle_tpu.inference.fleet.transport import (
    LoopbackTransport, RemoteEngine, ReplicaServer, TransportError,
    TransportSevered, TransportTimeout)
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing.chaos import ChaosTransport


def _tiny_model(seed=0):
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                      num_heads=2, num_kv_heads=2, max_seq_len=64,
                      dropout=0.0)
    paddle.seed(seed)
    return LlamaForCausalLM(cfg)


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("max_new_tokens", 4)
    kw.setdefault("seed", 0)
    return ContinuousBatchingEngine(model, **kw)


def _remote(engine, **tkw):
    server = ReplicaServer(engine)
    tkw.setdefault("timeout", 5.0)
    tkw.setdefault("backoff", 0.001)
    return RemoteEngine(LoopbackTransport(server, **tkw)), server


_PAYLOAD = {
    "ints": [1, 2, 3], "nested": {"a": (4, 5), "b": None},
    "floats": [0.5, -1.25], "text": "héllo", "blob": b"\x00\xff",
    "arr_f32": np.arange(6, dtype=np.float32).reshape(2, 3),
    "arr_i8": np.array([-128, 127], dtype=np.int8),
    "tup": (np.ones(3, dtype=np.float32), np.float32(0.125)),
}


def _assert_payload_equal(a, b):
    assert sorted(a) == sorted(b)
    np.testing.assert_array_equal(a["arr_f32"], b["arr_f32"])
    assert b["arr_f32"].dtype == np.float32
    np.testing.assert_array_equal(a["arr_i8"], b["arr_i8"])
    assert b["arr_i8"].dtype == np.int8
    assert isinstance(b["nested"]["a"], tuple)     # not decoded to list
    assert isinstance(b["tup"], tuple)
    np.testing.assert_array_equal(a["tup"][0], b["tup"][0])
    assert b["ints"] == [1, 2, 3] and b["text"] == "héllo"
    assert b["blob"] == b"\x00\xff" and b["nested"]["b"] is None


class TestFrameCodec:
    @pytest.mark.parametrize("codec", wire.available_codecs())
    def test_roundtrip_bitwise(self, codec):
        buf = wire.encode_frame(_PAYLOAD, codec=codec)
        assert buf[:4] == wire.MAGIC
        out = wire.decode_frame(buf)
        _assert_payload_equal(_PAYLOAD, out)

    def test_codec_travels_in_band(self):
        # a stdlib-encoded frame decodes without any out-of-band codec
        # agreement — the codec byte is part of the header
        buf = wire.encode_frame({"x": 1}, codec=wire.CODEC_STDLIB)
        assert wire.decode_frame(buf) == {"x": 1}

    def test_truncated_frame_raises(self):
        buf = wire.encode_frame({"x": 1})
        for cut in (3, wire.HEADER_SIZE - 1, len(buf) - 1):
            with pytest.raises(wire.FrameError):
                wire.decode_frame(buf[:cut])

    def test_corrupt_payload_raises(self):
        buf = bytearray(wire.encode_frame({"x": 1}))
        buf[wire.HEADER_SIZE] ^= 0xFF          # flip one payload byte
        with pytest.raises(wire.FrameError):
            wire.decode_frame(bytes(buf))

    def test_bad_magic_raises(self):
        buf = b"XXXX" + wire.encode_frame({"x": 1})[4:]
        with pytest.raises(wire.FrameError):
            wire.decode_frame(buf)


class TestOutcomeWire:
    def test_overloaded_roundtrip(self):
        exc = Overloaded("queue_full", retry_after=0.75,
                         predicted_ttft=1.5, priority="batch")
        back = outcome_from_wire(outcome_to_wire(exc))
        assert isinstance(back, Overloaded)
        assert back.reason == "queue_full"
        assert back.retry_after == 0.75
        assert back.predicted_ttft == 1.5
        assert back.priority == "batch"

    def test_transient_roundtrip(self):
        back = outcome_from_wire(outcome_to_wire(
            TransientReplicaError("UNAVAILABLE: preempted")))
        assert isinstance(back, TransientReplicaError)
        assert classify_step_exception(back) == "transient"

    def test_builtin_and_unknown(self):
        assert isinstance(outcome_from_wire(outcome_to_wire(
            ValueError("bad prompt"))), ValueError)
        weird = outcome_from_wire({"kind": "SomeExoticError",
                                   "message": "boom"})
        assert isinstance(weird, RemoteReplicaError)
        assert weird.remote_type == "SomeExoticError"

    def test_overloaded_crosses_rpc(self):
        # a child-process admission reject must reach the client intact
        eng = _engine(_tiny_model())
        remote, _ = _remote(eng)

        def raising_submit(prompt, **kw):
            raise Overloaded("ttft_slo", retry_after=0.5,
                             predicted_ttft=2.0)

        eng.submit = raising_submit
        with pytest.raises(Overloaded) as ei:
            remote.submit([7, 8])
        assert ei.value.reason == "ttft_slo"
        assert ei.value.retry_after == 0.5
        assert ei.value.predicted_ttft == 2.0


class TestTransportTaxonomy:
    def test_transport_errors_are_transient(self):
        for exc in (TransportError("link reset"),
                    TransportTimeout("step timed out after 1.0s"),
                    TransportSevered("severed for 3 calls")):
            assert classify_step_exception(exc) == "transient"
        assert issubclass(TransportError, ConnectionError)


class TestLoopbackRpc:
    def test_bitwise_vs_inprocess(self):
        prompts = [[1, 5, 9, 2], [3, 3, 7], [11, 2, 8, 4, 1]]
        local = _engine(_tiny_model(seed=0))
        rids = [local.submit(list(p)) for p in prompts]
        want = local.run_until_complete()

        remote, _ = _remote(_engine(_tiny_model(seed=0)))
        rrids = [remote.submit(list(p)) for p in prompts]
        got = remote.run_until_complete()
        for rl, rr in zip(rids, rrids):
            assert want[rl] == got[rr]

    def test_streaming_and_load(self):
        remote, _ = _remote(_engine(_tiny_model()))
        toks = []
        rid = remote.submit([1, 2, 3], on_token=lambda r, t:
                            toks.append((r, t)))
        done = remote.run_until_complete()
        gen = done[rid][3:]
        assert [t for _, t in toks] == gen
        load = remote.load()
        assert load["queue_depth"] == 0 and load["occupied_slots"] == 0


    def test_drain_ships_the_tokens_of_the_tick_in_flight(self):
        """``extract()`` settles the decode tick the engine launched
        ahead (docs/SERVING.md "The step's order"); no step follows a
        drain, so its tokens ride in the drain's own reply, and the
        peer that is injected the request streams the rest."""
        prompts = [[1, 5, 9, 2], [3, 3, 7]]
        local = _engine(_tiny_model(), max_new_tokens=6)
        rids = [local.submit(list(p)) for p in prompts]
        want = local.run_until_complete()

        src, server = _remote(_engine(_tiny_model(), max_new_tokens=6))
        dst, _ = _remote(_engine(_tiny_model(), max_new_tokens=6,
                                 rid_base=100))
        toks = {}
        cb = lambda r, t: toks.setdefault(r, []).append(t)  # noqa: E731
        rr = [src.submit(list(p), on_token=cb) for p in prompts]
        for _ in range(3):
            src.step()
        assert server.engine._in_flight is not None
        had = {r: len(toks[r]) for r in rr}
        data = src.drain_requests()
        assert server.engine._in_flight is None
        assert all(len(toks[r]) == had[r] + 1 for r in rr)
        for req in data["running"]:
            rid = int(req["rid"])
            dst.inject_wire(req)
            dst.adopt_stream(rid, src.release_stream(rid))
        done = dst.run_until_complete()
        for rl, r, p in zip(rids, rr, prompts):
            assert done[r] == want[rl]
            assert toks[r] == want[rl][len(p):]    # none lost, none twice


class TestChaos:
    def test_drop_retries_exactly_once(self):
        eng = _engine(_tiny_model())
        server = ReplicaServer(eng)
        t = LoopbackTransport(server, timeout=0.05, backoff=0.001)
        chaos = ChaosTransport(t, drop_sends={1})
        remote = RemoteEngine(chaos, hello=False)
        rid = remote.submit([1, 2, 3])
        assert chaos.dropped == 1
        assert t.retries >= 1
        done = remote.run_until_complete()
        assert len(done[rid]) == 7              # 3 prompt + 4 new
        # the drop cost a re-send of the SAME call id, not a re-execute
        assert eng.load()["queue_depth"] == 0

    def test_duplicate_served_from_cache(self):
        eng = _engine(_tiny_model())
        server = ReplicaServer(eng)
        chaos = ChaosTransport(
            LoopbackTransport(server, timeout=1.0, backoff=0.001),
            duplicate_sends={1})
        remote = RemoteEngine(chaos, hello=False)
        remote.submit([4, 5, 6])
        assert chaos.duplicated == 1
        done = remote.run_until_complete()
        assert len(done) == 1                   # executed exactly once

    def test_corrupt_rejected_then_resent(self):
        eng = _engine(_tiny_model())
        server = ReplicaServer(eng)
        t = LoopbackTransport(server, timeout=0.05, backoff=0.001)
        chaos = ChaosTransport(t, corrupt_sends={1})
        remote = RemoteEngine(chaos, hello=False)
        rid = remote.submit([7, 8])
        assert chaos.corrupted == 1
        done = remote.run_until_complete()
        assert rid in done

    def test_sever_raises_transient(self):
        eng = _engine(_tiny_model())
        server = ReplicaServer(eng)
        t = LoopbackTransport(server, timeout=0.05, backoff=0.001,
                              max_retries=1)
        chaos = ChaosTransport(t)
        remote = RemoteEngine(chaos, hello=False)
        remote.submit([1, 2])
        chaos.sever_for(8)
        with pytest.raises(TransportSevered) as ei:
            remote.step()
        assert classify_step_exception(ei.value) == "transient"


def _snapshot_roundtrip_over_pipe(int8):
    """extract -> encode_frame -> os.pipe -> read_frame -> inject."""
    env = dict(os.environ)
    os.environ["PTPU_INT8_KV"] = "1" if int8 else "0"
    try:
        # the reference: the same request served to completion on ONE
        # untouched engine (extract() removes it from the source)
        ref = _engine(_tiny_model(seed=0), int8_kv=int8)
        ref_rid = ref.submit([1, 5, 9, 2, 7])
        want = ref.run_until_complete()[ref_rid]

        src = _engine(_tiny_model(seed=0), int8_kv=int8)
        dst = _engine(_tiny_model(seed=0), int8_kv=int8)
        rid = src.submit([1, 5, 9, 2, 7])
        for _ in range(2):
            src.step()                 # prefill + one generated token
        req = src.extract(0)
        d = wire.request_to_wire(req)
        if int8:
            # the quantized wire: codes + per-row scales as a TUPLE
            flat = []

            def walk(x):
                if isinstance(x, tuple):
                    flat.append(x)
                    for y in x:
                        walk(y)
                elif isinstance(x, (list, dict)):
                    for y in (x.values() if isinstance(x, dict) else x):
                        walk(y)
            walk(d["swapped"])
            assert flat, "int8 snapshot carries no (codes, scales) tuples"

        r, w = os.pipe()
        buf = wire.encode_frame(d)
        os.write(w, buf)
        os.close(w)
        with os.fdopen(r, "rb") as f:
            got = wire.read_frame(lambda n: f.read(n))
        back = wire.request_from_wire(got)
        dst.inject(back)
        done_dst = dst.run_until_complete()
        # the migrated continuation is BITWISE the single-engine serve
        assert done_dst[rid] == want
    finally:
        os.environ.clear()
        os.environ.update(env)


class TestSnapshotWireContract:
    def test_fp_kv_roundtrip_bitwise(self):
        _snapshot_roundtrip_over_pipe(int8=False)

    def test_int8_kv_roundtrip_bitwise(self):
        _snapshot_roundtrip_over_pipe(int8=True)

    def test_truncated_snapshot_raises(self):
        src = _engine(_tiny_model(seed=0))
        src.submit([1, 2, 3, 4])
        src.step()
        buf = wire.encode_frame(wire.request_to_wire(src.extract(0)))
        r, w = os.pipe()
        os.write(w, buf[:len(buf) // 2])
        os.close(w)
        with os.fdopen(r, "rb") as f:
            with pytest.raises(wire.FrameError):
                wire.read_frame(lambda n: f.read(n))

    def test_corrupt_snapshot_raises_not_injects(self):
        src = _engine(_tiny_model(seed=0))
        src.submit([1, 2, 3, 4])
        src.step()
        buf = bytearray(wire.encode_frame(
            wire.request_to_wire(src.extract(0))))
        buf[wire.HEADER_SIZE + 5] ^= 0x40
        with pytest.raises(wire.FrameError):
            wire.decode_frame(bytes(buf))


class TestIdempotencyBounds:
    def test_byte_bound_evicts_oldest_first(self):
        """The reply cache is bounded by retained payload BYTES, not
        only entry count: a burst of fat replies (extract/drain carry
        KV snapshots) must not pin unbounded memory.  Oldest entries
        go first; a re-sent evicted call re-executes (which is safe —
        idempotency only matters inside the retry window)."""
        eng = _engine(_tiny_model())
        # a ping reply frame is ~170 bytes; a 512-byte bound holds only
        # the three most recent replies
        server = ReplicaServer(eng, idempotency_window=64,
                               idempotency_bytes=512)
        for i in range(5):
            server.handle_frame(wire.encode_frame(
                {"id": 1000 + i, "m": "ping", "a": {}}))
        assert server.idem_evictions["bytes"] >= 1
        assert server._done_bytes <= 512
        # the oldest call ids were evicted, the newest survives
        assert 1000 not in server._done
        assert 1004 in server._done
        # a duplicate of a SURVIVING entry still replays from cache
        before = server.handled
        server.handle_frame(wire.encode_frame(
            {"id": 1004, "m": "ping", "a": {}}))
        assert server.handled == before
        assert server.duplicates == 1
        # an EVICTED call id re-executes rather than replaying
        server.handle_frame(wire.encode_frame(
            {"id": 1000, "m": "ping", "a": {}}))
        assert server.handled == before + 1
        assert server.duplicates == 1

    def test_count_window_still_applies(self):
        eng = _engine(_tiny_model())
        server = ReplicaServer(eng, idempotency_window=4)
        for i in range(7):
            server.handle_frame(wire.encode_frame(
                {"id": i, "m": "ping", "a": {}}))
        assert len(server._done) == 4
        assert server.idem_evictions["count"] == 3
        assert set(server._done) == {3, 4, 5, 6}
