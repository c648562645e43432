"""Draft-model speculative decoding for the serving engine.

The engine decode tick is HBM-bound: one full weight pass produces ONE
token per sequence. Speculative decoding (docs/SERVING.md) spends a
small draft model's FLOPs to propose K tokens, then verifies all of
them in ONE target forward (`ContinuousBatchingEngine._spec_verify`) —
the target emits the longest draft prefix matching its OWN greedy
choices plus a bonus token, so each target weight pass yields 1..K+1
tokens at plain-decode quality.

Numerics contract: every emitted token is **bitwise identical** to what
plain greedy decode would have produced. The verify pass guarantees its
half by running the same per-position paged-attention kernel plain
decode runs (row-local projections batch without changing row values);
the draft only gates WHICH positions get accepted, never their values.
Temperature>0 requests fall back to the plain sampled tick.

The DraftRunner rides the TARGET's page tables: draft KV lives in its
own stacked cache `[Ld, Hkv_d, num_pages+1, page, D_d]` addressed by
the same page ids, so there is no second allocator — a page's position
means the same token index in both caches. Draft KV is (re)built at
target prefill completion and at decode-phase snapshot restores (disagg
handoffs / swap-ins); each spec tick re-primes position ``length-1``
before proposing, which both heals the one-token hole a fully-accepted
window leaves and is a bitwise no-op otherwise.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["DraftRunner"]


class DraftRunner:
    """Owns the draft model's packed weights, paged KV cache, and the
    jitted propose/prefill programs for one engine."""

    def __init__(self, engine, draft_model):
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self.engine = engine
        cfg = draft_model.config
        if cfg.vocab_size != engine.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {cfg.vocab_size} != target vocab "
                f"{engine.cfg.vocab_size} — speculative decoding needs a "
                "shared tokenizer")
        self.cfg = cfg
        self.hd = cfg.hidden_size // cfg.num_heads
        self.hkv = cfg.num_kv_heads

        from ..serving import _pack_weights_stacked

        self._weights = _pack_weights_stacked(draft_model)
        dt = self._weights["embed"].dtype
        shape = (cfg.num_layers, self.hkv, engine.pool.num_pages + 1,
                 engine.page, self.hd)
        self.kc = jnp.zeros(shape, dt)
        self.vc = jnp.zeros(shape, dt)
        # one jitted program per window width C (2 for the re-prime
        # step, 1 for each subsequent draft) — both fixed-shape
        self._window_jit = jax.jit(self._window_step,
                                   donate_argnums=(4, 5))
        self.prefills = 0

    # -- compiled draft forward --------------------------------------------
    def _run_layers(self, x, layer_fn, kc, vc):
        """Layer walk over the DRAFT stack through the engine's shared
        :func:`serving._run_layer_stack` walker (one scan/unroll
        discipline for target and draft, the draft's pool carried whole
        and written in place like the target's; cold start flat in
        draft depth too)."""
        from ..serving import _run_layer_stack

        def over_pools(lp, li, x, cache):
            x, kc, vc = layer_fn(lp, li, x, *cache)
            return x, (kc, vc)

        x, (kc, vc) = _run_layer_stack(self.engine._scan_layers,
                                       self._weights["layers"], x,
                                       over_pools, (kc, vc))
        return x, kc, vc

    def _layer_forward(self, lp, x, pos0, attend):
        """THE draft decoder-layer body: projections + rope +
        ``attend(q, k, v)`` (which owns cache writes and the attention
        math) + MLP — shared by the compiled window step and the eager
        prefill, so their numerics can never drift (drift between them
        is exactly what collapses speculative acceptance)."""
        jax, jnp = self._jax, self._jnp
        from ...models.gpt import _rms_pure
        from ..serving import _rope

        ln1, wq, wk, wv, wo, ln2, wg, wu, wd = lp
        B, S = x.shape[:2]
        h = _rms_pure(x, ln1)
        q = (h @ wq).reshape(B, S, self.cfg.num_heads, self.hd)
        k = (h @ wk).reshape(B, S, self.hkv, self.hd)
        v = (h @ wv).reshape(B, S, self.hkv, self.hd)
        q, k = _rope(q, pos0), _rope(k, pos0)
        o = attend(q, k, v)                              # [B, S, Hq, D]
        x = x + o.reshape(B, S, -1).astype(x.dtype) @ wo
        h2 = _rms_pure(x, ln2)
        return x + (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ wd

    def _window_step(self, weights, toks, pos0, tables, kc, vc):
        """Draft forward over a C-token window at absolute positions
        pos0..pos0+C-1: writes draft KV for every window row, paged-
        attends per position, returns the greedy next token after the
        LAST position. C=1 is single-token decode; C=2 re-primes the
        previous position first (see module docstring)."""
        jnp = self._jnp
        from ...models.gpt import _rms_pure
        from ...ops.pallas.decode_attention import paged_attention
        from ..serving import _kv_write_run

        C = toks.shape[1]
        x = weights["embed"][toks]                       # [B, C, H]

        def layer_fn(lp, li, x, kc, vc):
            def attend(q, k, v):
                nonlocal kc, vc
                kc = _kv_write_run(kc, li, tables, pos0, C, k)
                vc = _kv_write_run(vc, li, tables, pos0, C, v)
                return jnp.stack(
                    [paged_attention(q[:, i], kc, vc, tables,
                                     pos0 + i + 1, layer=li)
                     for i in range(C)], 1)              # [B, C, Hq, D]

            x = self._layer_forward(lp, x, pos0, attend)
            return x, kc, vc

        x, kc, vc = self._run_layers(x, layer_fn, kc, vc)
        last = _rms_pure(x[:, -1], weights["fnorm"])     # [B, H]
        lg = (last @ weights["head"] if weights["head"] is not None
              else last @ weights["embed"].T)
        nxt = jnp.argmax(lg.astype(jnp.float32), -1).astype(jnp.int32)
        return nxt, kc, vc

    # -- engine-facing surface ---------------------------------------------
    def propose(self, prev, cur, lens, tables, K):
        """Greedily draft K tokens per row: one C=2 window step
        ([prev@len-1, cur@len] — the re-prime), then K-1 single-token
        steps. Returns np int32 [B, K]."""
        jnp = self._jnp
        d, self.kc, self.vc = self._window_jit(
            self._weights,
            jnp.asarray(np.stack([prev, cur], 1)), lens - 1, tables,
            self.kc, self.vc)
        drafts = [d]
        for j in range(1, K):
            d, self.kc, self.vc = self._window_jit(
                self._weights, drafts[-1][:, None], lens + j, tables,
                self.kc, self.vc)
            drafts.append(d)
        return np.stack([np.asarray(d) for d in drafts], 1)

    def prefill(self, reqs, tokens_list):
        """Write draft KV for whole token prefixes into the requests'
        pages as ONE padded batch — eager, mirroring the engine's group
        prefill op-for-op so a same-architecture draft's KV stays
        bitwise aligned with the target's (the acceptance-rate
        guarantee for self-drafting tests)."""
        jax, jnp = self._jax, self._jnp
        from ..serving import _kv_write_run

        eng = self.engine
        w = self._weights
        B = len(reqs)
        lens = np.asarray([len(t) for t in tokens_list])
        S = int(lens.max())
        ids_np = np.zeros((B, S), np.int32)
        for i, t in enumerate(tokens_list):
            ids_np[i, : lens[i]] = t
        x = w["embed"][jnp.asarray(ids_np)]              # [B, S, H]
        pos0 = jnp.zeros((B,), jnp.int32)
        scale = 1.0 / math.sqrt(self.hd)
        rep = self.cfg.num_heads // self.hkv
        mask = jnp.tril(jnp.ones((S, S), bool))

        tables = jnp.asarray(eng._table_rows(reqs))
        nvalid = jnp.asarray(lens, jnp.int32)

        for li in range(self.cfg.num_layers):
            def attend(q, k, v, li=li):
                ck = jnp.repeat(k, rep, 2) if rep > 1 else k
                cv = jnp.repeat(v, rep, 2) if rep > 1 else v
                logits = jnp.einsum("bthd,bshd->bhts",
                                    (q * scale).astype(jnp.float32),
                                    ck.astype(jnp.float32))
                logits = jnp.where(mask[None, None], logits, -1e30)
                probs = jax.nn.softmax(logits, -1)
                o = jnp.einsum("bhts,bshd->bthd", probs,
                               cv.astype(jnp.float32)).astype(q.dtype)
                self.kc = _kv_write_run(self.kc, li, tables, pos0,
                                        nvalid, k)
                self.vc = _kv_write_run(self.vc, li, tables, pos0,
                                        nvalid, v)
                return o

            x = self._layer_forward(
                tuple(wl[li] for wl in w["layers"]), x, pos0, attend)
        self.prefills += B

    def catch_up(self, tokens, lens, tables):
        """Write the draft-KV row for a plain (fallback) tick's carry
        token at position ``lens``; the proposal is discarded. Keeps
        the draft cache continuous across sampled ticks."""
        _d, self.kc, self.vc = self._window_jit(
            self._weights, tokens[:, None], lens, tables,
            self.kc, self.vc)

    def warmup(self, tables):
        """Compile the window widths serving will actually use (C=2
        always; C=1 only when spec_tokens >= 2) on dummy operands —
        writes land in the engine's scratch page, the compile time
        lands in the engine's gated cold-start number, and each width's
        bytes in the engine's ``program_bytes`` (``draft_window_c2``,
        ``draft_window_c1``)."""
        jnp = self._jnp
        b = self.engine.max_slots
        pos0 = jnp.zeros((b,), jnp.int32)
        for C in (2, 1)[:min(self.engine.spec_tokens, 2)]:
            d, self.kc, self.vc = self.engine._warm(
                f"draft_window_c{C}", self._window_jit, self._weights,
                jnp.zeros((b, C), jnp.int32), pos0, tables,
                self.kc, self.vc)
            np.asarray(d)
