"""Disaggregated prefill/decode serving.

Long prompts stall decode ticks: a chunked-prefill pass shares the tick
with decode, so every running request's inter-token latency absorbs the
prefill compute. Disaggregation (docs/SERVING.md) splits the work onto
two engines — in deployment, two meshes:

- the **prefill worker** (`prefill_only=True`) admits requests, runs
  chunked prefill, samples the first token, and owns the prefix cache
  (warm system prompts never leave it);
- the **decode worker** receives finished prefills over an explicit
  transfer seam and runs pure decode ticks (plus speculative decoding
  when a draft model is attached).

The seam is `ContinuousBatchingEngine.extract()` → `inject()`: the KV
pages + resume state move as a host snapshot (the swap-out machinery),
and the decode worker's swap-restore admission path scatters them into
its own pages. The transfer is bitwise — exact caches round-trip
unchanged through the host copy, int8 caches move raw codes+scales —
so greedy disaggregated output is IDENTICAL to the single-engine path
(asserted in tests/test_fleet.py). Each handoff is traced as a
per-request ``handoff`` mark and counted with its payload bytes.
"""
from __future__ import annotations

from ... import telemetry as _telemetry
from ...telemetry import trace as _trace
from ..serving import ContinuousBatchingEngine, _kv_nbytes

__all__ = ["DisaggregatedEngine"]

_HANDOFFS = _telemetry.counter(
    "serving_handoffs_total",
    "prefill->decode KV transfers (docs/SERVING.md)")
_HANDOFF_BYTES = _telemetry.counter(
    "serving_handoff_bytes_total",
    "KV snapshot bytes crossing the prefill->decode seam")


class DisaggregatedEngine:
    """Same surface as ContinuousBatchingEngine (submit/step/cancel/
    run_until_complete/load/prefix_match_pages), backed by a prefill
    worker + a decode worker; usable as a FleetRouter replica."""

    def __init__(self, model, prefill_slots=2, decode_slots=4,
                 page_size=64, max_seq_len=None, max_new_tokens=32,
                 eos_token_id=None, seed=0, prefill_chunk=32,
                 prefill_pages=None, decode_pages=None,
                 enable_prefix_cache=False, int8_kv=False,
                 draft_model=None, spec_tokens=4, rid_base=0):
        if prefill_chunk is None:
            raise ValueError("disaggregated prefill requires chunked "
                             "prefill (prefill_chunk=...)")
        # the prefill half: admissions + chunked prefill + prefix cache;
        # never decodes (prefill_only), so its pool only ever holds
        # prompt pages
        self.prefill = ContinuousBatchingEngine(
            model, max_slots=prefill_slots, page_size=page_size,
            num_pages=prefill_pages, max_seq_len=max_seq_len,
            max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
            seed=seed, prefill_chunk=prefill_chunk,
            enable_prefix_cache=enable_prefix_cache, int8_kv=int8_kv,
            prefill_only=True, rid_base=rid_base)
        # the decode half: restores handed-off snapshots and decodes;
        # keeps chunked prefill for preemption-recompute resumes
        self.decode = ContinuousBatchingEngine(
            model, max_slots=decode_slots, page_size=page_size,
            num_pages=decode_pages, max_seq_len=max_seq_len,
            max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
            seed=seed, prefill_chunk=prefill_chunk, int8_kv=int8_kv,
            draft_model=draft_model, spec_tokens=spec_tokens,
            rid_base=rid_base)
        if self.prefill.int8_kv != self.decode.int8_kv:
            raise RuntimeError("prefill/decode workers resolved different "
                               "KV modes — the handoff seam moves raw "
                               "pages and needs one format")
        self.max_slots = decode_slots      # router capacity signal
        self.handoffs = 0
        self.handoff_bytes = 0
        self._cancelled = {}

    # -- engine surface -----------------------------------------------------
    def submit(self, prompt_ids, **kwargs):
        # handed-off requests bypass the decode worker's submit()
        # validation — enforce its feasibility bounds here, or an
        # oversized request would head-of-line-block the decode queue
        # forever (its swap-restore admission can never allocate)
        total = len(prompt_ids) + self.decode.max_new_tokens
        if self.decode._draft is not None and (
                total + self.decode.spec_tokens > self.decode.max_seq):
            raise ValueError(
                f"request needs {total} tokens + "
                f"{self.decode.spec_tokens} spec headroom > "
                f"max_seq_len {self.decode.max_seq}")
        page = self.decode.page
        spec_pad = (self.decode.spec_tokens
                    if self.decode._draft is not None else 0)
        need = (total + spec_pad + page - 1) // page
        if need > self.decode.pool.num_pages:
            raise ValueError(
                f"request needs {need} pages > decode worker pool size "
                f"{self.decode.pool.num_pages}")
        return self.prefill.submit(prompt_ids, **kwargs)

    def cancel(self, rid, reason="user"):
        return (self.prefill.cancel(rid, reason=reason)
                or self.decode.cancel(rid, reason=reason))

    @property
    def cancelled(self):
        """PERSISTENT merged cancellation dict: the halves' dicts drain
        into it (the engines document theirs as drained-by-callers), so
        a FleetRouter popping entries here mutates real state instead
        of a per-call merged copy."""
        for src in (self.prefill.cancelled, self.decode.cancelled):
            while src:
                rid, reason = src.popitem()
                self._cancelled[rid] = reason
        return self._cancelled

    def prefix_match_pages(self, tokens):
        return self.prefill.prefix_match_pages(tokens)

    def load(self):
        """Router signal: queue depth spans BOTH halves (a request
        waiting anywhere delays first token); slots are the decode
        worker's (the throughput bound)."""
        p, d = self.prefill.load(), self.decode.load()
        return {
            "queue_depth": (p["queue_depth"] + p["occupied_slots"]
                            + d["queue_depth"]),
            "occupied_slots": d["occupied_slots"],
            "free_slots": d["free_slots"],
            "kv_free_fraction": min(p["kv_free_fraction"],
                                    d["kv_free_fraction"]),
        }

    def _handoff(self):
        """Move every finished prefill to the decode worker: extract
        (swap-out + release on the prefill side, prefix pages retained
        in its cache) → inject (decode-side swap-restore admission)."""
        eng = self.prefill
        for i, r in enumerate(list(eng._slots)):
            if (r is None or not r.generated
                    or r.prefill_pos < len(r.seq_tokens)):
                continue
            if eng._finished(r):
                # already complete (eos on the first token / max_new=1):
                # nothing to decode — leave it for the prefill worker's
                # own retire, whose result step() merges into the
                # returned completions
                continue
            req = eng.extract(i)
            size = sum(_kv_nbytes(req.swapped[name])
                       for name in eng.cache_names)
            self.handoffs += 1
            self.handoff_bytes += size
            _HANDOFFS.inc()
            _HANDOFF_BYTES.inc(size)
            _trace.async_instant(
                "handoff", req.rid,
                {"pages": req.swapped["n"], "bytes": size})
            self.decode.inject(req)

    def step(self):
        """One disaggregated tick: prefill tick → handoff sweep →
        decode tick. Completions come off the decode worker, PLUS any
        request the prefill worker retired itself (complete at first
        token, so it never crossed the seam)."""
        done = self.prefill.step()
        self._handoff()
        out = self.decode.step()
        out.update(done)
        return out

    def run_until_complete(self, max_ticks=10000):
        done = {}
        for _ in range(max_ticks):
            done.update(self.step())
            if (not self.prefill._waiting and not self.decode._waiting
                    and all(s is None for s in self.prefill._slots)
                    and all(s is None for s in self.decode._slots)):
                return done
        raise TimeoutError("disaggregated serving loop did not drain")

    def warmup(self, sample=False):
        b = self.prefill.warmup(sample=sample)
        b += self.decode.warmup(sample=sample)
        self.build_seconds = b
        return b
