"""Flagship decoder-only transformer family (GPT / LLaMA style).

Capability slot: the reference trains these through PaddleNLP on Fleet hybrid
parallel (BASELINE.md configs 4-5). Here the model is built from paddle_tpu
layers so the whole training step jit-compiles to one XLA program; parallel
training shards it over a Mesh via paddle_tpu.distributed.

Layout conventions are TPU-first: [batch, seq, heads, head_dim] attention
tensors feed the Pallas flash kernel; weights stay [in, out] so every matmul
is a single MXU dot_general.
"""
from __future__ import annotations

import math
import os

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.incubate.nn import functional as FF
from paddle_tpu.nn import functional as F


class GPTConfig:
    def __init__(
        self,
        vocab_size=50304,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        num_kv_heads=None,
        intermediate_size=None,
        max_seq_len=2048,
        norm_type="rmsnorm",
        act="swiglu",
        rope=True,
        dropout=0.0,
        tie_embeddings=True,
        dtype="float32",
        recompute=False,
        recompute_policy="full",
        pp_interleave=1,
        pp_schedule="1f1b",
        head_chunk=None,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.intermediate_size = intermediate_size or (
            int(8 * hidden_size / 3 / 128 + 1) * 128 if act == "swiglu" else 4 * hidden_size
        )
        self.max_seq_len = max_seq_len
        self.norm_type = norm_type
        self.act = act
        self.rope = rope
        self.dropout = dropout
        self.tie_embeddings = tie_embeddings
        self.dtype = dtype
        self.recompute = recompute
        # "full" = rerun the whole block in backward (lowest memory);
        # "dots" = save matmul/attention outputs, recompute elementwise only
        # (jax.checkpoint_policies selective remat — the standard single-chip
        # throughput/memory middle ground)
        self.recompute_policy = recompute_policy
        # virtual pipeline stages per device (VPP): bubble shrinks by 1/v
        self.pp_interleave = pp_interleave
        # "1f1b" (AD-reversed ring) or "zb" (zero-bubble: dgrad-only ring,
        # weight grads batched bubble-free after it — ZB-H1 analogue,
        # reference passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:62)
        self.pp_schedule = pp_schedule
        # vocab-chunk size of the fused CE head (None = PTPU_CE_VCHUNK or
        # the module default; a memory-planner plan dimension alongside
        # batch x remat — docs/PERF.md)
        self.head_chunk = head_chunk


def llama_config(size="7b", **overrides):
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=4, vocab_size=1024, max_seq_len=512),
        "125m": dict(hidden_size=768, num_layers=12, num_heads=12, vocab_size=50304),
        "350m": dict(hidden_size=1024, num_layers=24, num_heads=16, vocab_size=50304),
        "1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16, vocab_size=50304),
        "7b": dict(hidden_size=4096, num_layers=32, num_heads=32, vocab_size=32000),
    }
    cfg = presets[size]
    cfg.update(overrides)
    return GPTConfig(**cfg)


def compute_loss(hidden, weight, labels, *, config=None, transpose_y=True,
                 ignore_index=-100):
    """LM-head matmul + CE dispatch — the ONE loss-head entry every GPT
    variant shares. Paths (telemetry gauge ``loss_head_mode``):

    - **chunked** (default): blockwise-LSE fused head
      (`nn.functional.fused_cross_entropy`) — neither the fp32 logits nor
      the grad-logits ``[tokens, vocab]`` tensor ever exists in HBM.
    - **sharded**: the vocab-sharded variant, selected when the head
      weight carries a ``_vocab_shard_axis`` marker
      (:meth:`GPTForCausalLMPipe.shard_lm_head`) over a live mesh axis —
      each tp shard reduces (max, lse, gold) scalars per token, never a
      logits all-gather.
    - **dense**: the reference path (full logits + ``F.cross_entropy``),
      kept for A/B and as the parity oracle.

    ``PTPU_LOSS_HEAD`` forces a path; the int8 head rides on the chunked/
    sharded kernels via the parity-gated default
    (``fused_cross_entropy.int8_head_enabled``). The chunk size comes
    from ``config.head_chunk`` (a planner dimension) or ``PTPU_CE_VCHUNK``.
    """
    from paddle_tpu.nn.functional import fused_cross_entropy as FCE

    mode = os.environ.get("PTPU_LOSS_HEAD", "").strip().lower()
    if mode not in ("", "dense", "chunked", "sharded"):
        raise ValueError(
            f"PTPU_LOSS_HEAD={mode!r}: expected dense|chunked|sharded")
    chunk = getattr(config, "head_chunk", None) if config is not None else None
    vocab = weight.shape[0] if transpose_y else weight.shape[-1]

    axis = getattr(weight, "_vocab_shard_axis", None)
    mesh = None
    if axis is not None and mode in ("", "sharded"):
        # the mesh the head was SHARDED over (shard_lm_head records it in
        # the weight's dist_attr) — not the ambient global mesh, which can
        # be absent or a different object under an explicit
        # ShardedTrainStep(mesh=...)
        da = getattr(weight, "_dist_attr", None)
        mesh = da.process_mesh if da is not None else None
        if mesh is None:
            from paddle_tpu.distributed.fleet import active_mesh

            mesh = active_mesh()
        if (mesh is None or axis not in mesh.dim_names
                or mesh.get_dim_size(axis) <= 1):
            axis, mesh = None, None
    if mode == "sharded" and axis is None:
        raise ValueError(
            "PTPU_LOSS_HEAD=sharded but the head weight carries no live "
            "_vocab_shard_axis marker — call shard_lm_head(mesh, axis) "
            "(or ShardedTrainStep(shard_vocab_head=...)) first")
    if mode == "chunked":
        axis, mesh = None, None

    if mode == "dense":
        n_tokens = 1
        for s in labels.shape:
            n_tokens *= int(s)
        FCE.record_head_mode("dense", False, n_tokens, vocab)
        logits = (paddle.matmul(hidden, weight, transpose_y=True)
                  if transpose_y else paddle.matmul(hidden, weight))
        return F.cross_entropy(
            logits.reshape([-1, vocab]), labels.reshape([-1]),
            ignore_index=ignore_index)

    return FCE.fused_chunked_cross_entropy(
        hidden, weight, labels, transpose_y=transpose_y, vocab_chunk=chunk,
        ignore_index=ignore_index, mesh=mesh, tp_axis=axis)


class Attention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = h // config.num_heads
        self.q_proj = nn.Linear(h, self.num_heads * self.head_dim, bias_attr=False)
        self.k_proj = nn.Linear(h, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.v_proj = nn.Linear(h, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * self.head_dim, h, bias_attr=False)
        self.rope = config.rope
        self.dropout = config.dropout

    def forward(self, x, attn_mask=None):
        b, s, h = x.shape
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if self.rope:
            q, k, _ = FF.fused_rotary_position_embedding(q, k, None)
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = k.repeat_interleave(rep, axis=2)
            v = v.repeat_interleave(rep, axis=2)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.dropout,
            is_causal=True, training=self.training,
        )
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out)


class MLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.act = config.act
        if config.act == "swiglu":
            self.gate_proj = nn.Linear(h, m, bias_attr=False)
            self.up_proj = nn.Linear(h, m, bias_attr=False)
            self.down_proj = nn.Linear(m, h, bias_attr=False)
        else:
            self.fc1 = nn.Linear(h, m)
            self.fc2 = nn.Linear(m, h)

    def forward(self, x):
        if self.act == "swiglu":
            return self.down_proj(FF.swiglu(self.gate_proj(x), self.up_proj(x)))
        return self.fc2(F.gelu(self.fc1(x)))


class DecoderLayer(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        norm_cls = nn.RMSNorm if config.norm_type == "rmsnorm" else nn.LayerNorm
        self.input_norm = norm_cls(config.hidden_size)
        self.attn = Attention(config)
        self.post_attn_norm = norm_cls(config.hidden_size)
        self.mlp = MLP(config)
        self.dropout = config.dropout

    def forward(self, x, attn_mask=None):
        h = x + self.attn(self.input_norm(x), attn_mask)
        return h + self.mlp(self.post_attn_norm(h))


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig, layer_factory=None):
        super().__init__()
        self.config = config
        factory = layer_factory or (lambda: DecoderLayer(config))
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        if not config.rope:
            self.embed_pos = nn.Embedding(config.max_seq_len, config.hidden_size)
        self.layers = nn.LayerList([factory() for _ in range(config.num_layers)])
        norm_cls = nn.RMSNorm if config.norm_type == "rmsnorm" else nn.LayerNorm
        self.final_norm = norm_cls(config.hidden_size)
        # quant-compute amax state (docs/QUANT.md) — only threaded on the
        # shared-scan path (_run_stacked); the per-layer module loop
        # never quantizes (its matmuls live inside nn.Linear)
        amax0 = _quant_buffer_state(config)
        if amax0 is not None:
            self.register_buffer("quant_amax", amax0)

    def forward(self, input_ids, attn_mask=None):
        x = self.embed_tokens(input_ids)
        if not self.config.rope:
            pos = paddle.arange(input_ids.shape[1])
            x = x + self.embed_pos(pos)
        if self._shared_block_eligible(attn_mask):
            # scan-over-layers (docs/SCAN.md): the LayerList weights are
            # stacked [L, ...] at trace time and run through the SAME
            # _block_pure scan body as StackedDecoder — compile time and
            # program size flat in depth, remat anchors identical, and
            # float32-hex identical to the per-layer module loop below
            # (PTPU_SCAN_LAYERS=0 unrolls the shared body instead).
            x = self._run_stacked(x)
        elif self.config.recompute:
            from paddle_tpu.distributed.fleet.utils import recompute

            for layer in self.layers:
                x = recompute(layer, x, attn_mask)
        else:
            for layer in self.layers:
                x = layer(x, attn_mask)
        return self.final_norm(x)

    def _shared_block_eligible(self, attn_mask):
        """True when the stack can run through the shared _block_pure
        scan body: plain DecoderLayers of the rmsnorm+swiglu+rope family,
        no mask/dropout, no per-layer distributed placements (pp stage
        assignment and parallelize() marks operate on per-layer modules,
        which the stacked tree would silently drop)."""
        cfg = self.config
        if attn_mask is not None or cfg.dropout or not cfg.rope:
            return False
        if cfg.norm_type != "rmsnorm" or cfg.act != "swiglu":
            return False
        from paddle_tpu import amp as _amp

        if _amp.is_auto_cast_enabled():
            # the stack dispatches as ONE op here, which would bypass
            # amp's per-op white/black-list casting (the matmuls would
            # silently run fp32) — keep the module loop under autocast
            return False
        if any(type(l) is not DecoderLayer for l in self.layers):
            return False
        for l in self.layers:
            for _, p in l.named_parameters():
                if getattr(p, "_dist_attr", None) is not None:
                    return False
        from paddle_tpu.distributed.fleet import active_mesh

        mesh = active_mesh()
        if (mesh is not None and "pp" in mesh.dim_names
                and mesh.get_dim_size("pp") > 1):
            return False
        return True

    def _run_stacked(self, x):
        """Eligible LayerList stack through the shared scan body.

        Cost note (docs/SCAN.md): the per-layer weights are stacked
        INSIDE the program, so each step pays a decoder-weights
        concatenate the module loop never paid — the trade is steady-
        state copy bandwidth for depth-flat compile time, which is the
        right trade for the eager frontend's dev/CPU/small-model uses.
        Flagship-scale training stores weights stacked natively
        (StackedDecoder) and never restacks; if an eager model is
        compile-bound AND copy-sensitive, PTPU_SCAN_LAYERS=0 restores
        the copy-free unrolled program."""
        import jax.numpy as jnp
        from paddle_tpu.core.dispatch import apply_op

        cfg = self.config
        L = len(self.layers)
        flat = []
        for l in self.layers:
            obj = {"input_norm.weight": l.input_norm.weight,
                   "attn.q_proj.weight": l.attn.q_proj.weight,
                   "attn.k_proj.weight": l.attn.k_proj.weight,
                   "attn.v_proj.weight": l.attn.v_proj.weight,
                   "attn.o_proj.weight": l.attn.o_proj.weight,
                   "post_attn_norm.weight": l.post_attn_norm.weight,
                   "mlp.gate_proj.weight": l.mlp.gate_proj.weight,
                   "mlp.up_proj.weight": l.mlp.up_proj.weight,
                   "mlp.down_proj.weight": l.mlp.down_proj.weight}
            flat.extend(obj[suffix] for _, suffix in _BLOCK_PARAM_FIELDS)

        quant_buf = self._buffers.get("quant_amax")

        def _run(x, *params):
            amax = None
            if quant_buf is not None:
                amax = params[-1]
                params = params[:-1]
            policy, int8_names = (_resolve_remat(cfg) if cfg.recompute
                                  else (None, frozenset()))
            q_sites, q_dtype = _resolve_quant(cfg)
            if q_sites and amax is None:
                from paddle_tpu import quant as _quant

                amax = jnp.zeros((L, len(_quant.GEMM_SITES), 2,
                                  _quant.amax_hist_len()), jnp.float32)
            block = _make_block(cfg, int8_names=int8_names,
                                policy=policy, quant_sites=q_sites,
                                quant_dtype=q_dtype)
            n = len(_BLOCK_PARAM_FIELDS)
            per_layer = [params[i * n:(i + 1) * n] for i in range(L)]

            def _out(res, new_amax=None):
                if quant_buf is None:
                    return res
                return res, (amax if new_amax is None else new_amax)

            if scan_layers_enabled():
                stacked = tuple(jnp.stack([lp[k] for lp in per_layer])
                                for k in range(n))
                if q_sites:
                    out, new_amax = _scan_blocks(block, x, stacked,
                                                 amax=amax)
                    return _out(out, new_amax)
                return _out(_scan_blocks(block, x, stacked))
            if q_sites:
                out, new_amax = _unrolled_blocks(block, x, per_layer,
                                                 amax=amax)
                return _out(out, new_amax)
            return _out(_unrolled_blocks(block, x, per_layer))

        if quant_buf is not None:
            out = apply_op(_run, x, *flat, quant_buf,
                           _op_name="gpt_layer_stack")
            from paddle_tpu.core.tensor import Tensor

            out, new_amax = out
            quant_buf._data = (new_amax._data
                               if isinstance(new_amax, Tensor) else new_amax)
            return out
        return apply_op(_run, x, *flat, _op_name="gpt_layer_stack")


class GPTForCausalLM(nn.Layer):
    def __init__(self, config: GPTConfig, layer_factory=None):
        super().__init__()
        self.config = config
        self.model = GPTModel(config, layer_factory)
        if config.tie_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)

    def forward(self, input_ids, attn_mask=None):
        hidden = self.model(input_ids, attn_mask)
        if self.lm_head is None:
            return paddle.matmul(hidden, self.model.embed_tokens.weight, transpose_y=True)
        return self.lm_head(hidden)

    def loss(self, input_ids, labels):
        """Fused chunked-head LM loss: the [N, vocab] logits tensor never
        materializes (compute_loss dispatch; PTPU_LOSS_HEAD=dense restores
        the reference full-logits path)."""
        hidden = self.model(input_ids)
        if self.lm_head is None:
            return compute_loss(hidden, self.model.embed_tokens.weight,
                                labels, config=self.config, transpose_y=True)
        return compute_loss(hidden, self.lm_head.weight, labels,
                            config=self.config, transpose_y=False)


def causal_lm_loss(model, batch):
    input_ids, labels = batch
    return model.loss(input_ids, labels)


# ---------------------------------------------------------------------------
# Pipelined variant: stacked decoder parameters + compiled SPMD pipeline
# (parity: PaddleNLP GPTForCausalLMPipe over fleet PipelineLayer/1F1B;
#  reference runtime: fleet/meta_parallel/pipeline_parallel.py:242)
# ---------------------------------------------------------------------------
def _rope_at_positions(x, pos, base=10000.0):
    """Neox-style rope on [B, T, H, D] at absolute positions.

    ``pos``: [B] per-row start offsets (the kv-cache / paged-serving
    case) — every consumer (training forward, generate, the serving
    engine) shares THIS formula, so decode paths stay bit-identical to
    the training path."""
    import jax.numpy as jnp

    d = x.shape[-1]
    t = x.shape[1]
    p = (pos[:, None] + jnp.arange(t)[None, :]).astype(jnp.float32)
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = p[..., None] * inv                     # [B, T, d/2]
    sin = jnp.sin(freqs)[:, :, None, :]
    cos = jnp.cos(freqs)[:, :, None, :]
    return _rope_rotate(x, sin, cos)


def _rope_rotate(x, sin, cos):
    """Apply the half-split rotation given broadcast-ready sin/cos."""
    import jax.numpy as jnp

    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _rope_tables_at(p, d, base=10000.0):
    """sin/cos tables for an ARBITRARY position vector ``p`` [T],
    broadcast-ready for [B, T, H, D] activations: [1, T, 1, d/2] each.
    The frequency formula of :func:`_rope_at_positions`, for the
    ring-attention region's zigzag-global-position tables
    (collectives/ring_attention.RingContext.rope_tables), so an engaged
    ring step can never rotate by different angles than the
    single-device program."""
    import jax.numpy as jnp

    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = p.astype(jnp.float32)[:, None] * inv   # [T, d/2]
    return (jnp.sin(freqs)[None, :, None, :],
            jnp.cos(freqs)[None, :, None, :])


def _rope_pure(x, base=10000.0, tables=None):
    """Neox-style rope on [B, S, H, D] arrays (positions 0..S-1)."""
    if tables is not None:
        return _rope_rotate(x, *tables)
    import jax.numpy as jnp

    return _rope_at_positions(
        x, jnp.zeros((x.shape[0],), jnp.int32), base)


def _rms_pure(x, w, eps=1e-6):
    import os

    import jax
    import jax.numpy as jnp

    if os.environ.get("PTPU_PALLAS_RMS"):
        # A/B knob: the Pallas rms kernel saves its rstd residual (named
        # "rms_rstd") so selective-remat backward skips the variance
        # reduce instead of re-running it
        from ..ops.pallas import on_tpu_device

        rows = 1
        for s in x.shape[:-1]:
            rows *= s
        if on_tpu_device() and rows % 8 == 0:
            from ..ops.pallas.rms_norm import rms_norm

            return rms_norm(x, w, eps)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return ((x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * w


def scan_layers_enabled():
    """``PTPU_SCAN_LAYERS`` master switch (docs/SCAN.md): the default
    (unset/1) runs the decoder stack as ONE ``lax.scan`` body over a
    leading-axis-stacked weight tree — trace time, XLA compile time, and
    serialized program size stay flat in depth. ``0``/``off`` keeps the
    python-unrolled per-layer loop: linear compile cost, but a bitwise
    escape hatch (float32-hex-proven parity with the scanned path and
    with the pre-scan per-layer module loop)."""
    return os.environ.get("PTPU_SCAN_LAYERS", "").strip().lower() not in (
        "0", "off", "false")


def _fused_ffn_active(tp_seams):
    """norm→ffn seam megakernel gate (``PTPU_FUSED_FFN``). Precedence
    mirrors the PR 6 rules: engaged tp seams own the row/col matmul
    layouts (the megakernel's plain-matmul reads would force mid-block
    reshards against the seq-sharded residual)."""
    if tp_seams is not None:
        return False
    env = os.environ.get("PTPU_FUSED_FFN", "")
    if env in ("", "0"):
        return False
    # device gate (mirrors _sdpa_pure): off-TPU the kernel would run in
    # the Pallas INTERPRETER — orders of magnitude slower than the
    # unfused XLA seam. "interpret" opts in explicitly (parity tests
    # drive the real kernel code on the CPU mesh).
    from paddle_tpu.ops.pallas import on_tpu_device

    return on_tpu_device() or env == "interpret"


def _sdpa_pure(q, k, v, causal=True):
    """Flagship attention dispatch. Calls the pallas kernel DIRECTLY when
    `_use_pallas` holds (no silent try/except fallback: a kernel failure
    here must be loud, because the selective-remat anchors in `_block_pure`
    are chosen from the same predicate and a silent fallback would leave
    attention with no saved residual at all).

    Inside an ENGAGED ring-attention region (docs/ATTENTION.md) the
    local tensors are one sep shard's zigzag token slice: attention
    routes through the kv ring over ``sep`` — per-hop flash compute
    overlapped with the ppermute rotation — instead of a local-only
    kernel call that would silently drop cross-shard attention."""
    from paddle_tpu.nn.functional.flash_attention import (
        _constrain_heads_over_mp,
        _use_pallas,
        sdpa_arrays,
    )

    from paddle_tpu.distributed.collectives import ring_attention as _ringmod

    ctx = _ringmod.active_ring_context()
    if ctx is not None:
        return _ringmod.ring_attention(q, k, v, ctx, causal=causal)
    if _use_pallas(q.shape):
        from paddle_tpu.ops.pallas import flash_attention as _flash_kernel

        q, k, v = _constrain_heads_over_mp(q, k, v)
        return _flash_kernel(q, k, v, causal=causal)
    return sdpa_arrays(q, k, v, causal=causal)


def _block_pure(p, x, num_heads, num_kv_heads, use_rope=True,
                int8_names=frozenset(), tp_seams=None, quant=None):
    """One decoder block on arrays. p = (ln1, wq, wk, wv, wo, ln2, wg, wu, wd).

    ``int8_names``: anchors whose save point is routed through
    ``memory.int8_checkpoint`` (blockwise-int8 + fp32 scales) instead of
    a bf16 ``checkpoint_name`` — what an ``int8:<anchor>`` entry in a
    ``names:`` recompute_policy requests. Each int8-saved tensor holds
    ~half the HBM of its bf16 save, buying batch or more saves.

    ``tp_seams``: a ``collectives.TPSeamPlan`` routing the row/col-
    parallel matmuls through the fused compute-collective kernels —
    ``o @ wo`` / ``ffn @ wd`` become matmul+reduce-scatter (the residual
    stream between seams stays SEQUENCE-SHARDED over the tp axis) and
    the q/k/v/gate/up projections become all-gather+matmul
    (docs/COMMS.md). None (the default, and always under pp or inside
    the quantized dp-grad region) keeps the GSPMD-emitted seams.

    ``quant``: a ``paddle_tpu.quant.GemmQuantCtx`` holding this layer's
    delayed-scaling amax state — engaged GEMM sites run the scaled
    fp8/int8 forward (backward stays wide/exact, docs/QUANT.md) and the
    caller collects the updated amax histories via ``quant.collect()``.
    Mutually exclusive with ``tp_seams`` (the seams own their matmul
    layouts — the engagement resolver declines quant first)."""
    import jax
    import jax.numpy as jnp

    from jax.ad_checkpoint import checkpoint_name

    def _save(t, name):
        if name in int8_names:
            from paddle_tpu.memory import int8_checkpoint

            return int8_checkpoint(t, name)
        return checkpoint_name(t, name)

    def _col(xx, w, site):  # column-parallel seam (x may be seq-sharded)
        if tp_seams is not None:
            return tp_seams.all_gather_matmul(xx, w)
        if quant is not None:
            return quant.gemm(xx, w, site)
        return xx @ w

    def _row(xx, w, site):  # row-parallel seam (output seq-sharded)
        if tp_seams is not None:
            return tp_seams.matmul_reduce_scatter(xx, w)
        if quant is not None:
            return quant.gemm(xx, w, site)
        return xx @ w

    ln1, wq, wk, wv, wo, ln2, wg, wu, wd = p
    b, s, hdim = x.shape
    hd = hdim // num_heads
    h = _rms_pure(x, ln1)
    # head counts and the attention seq length derive from the SEAM
    # output, not the config: inside a composed manual region
    # (collectives/compose) the block runs per shard — `_col` gathers
    # the seq-sharded stream (sq = s * tp) and its mp-sharded weight
    # yields the LOCAL head slice (num_heads/tp), while the plain and
    # island-seam paths see sq == s and the full head count. `-1` in the
    # reshape covers both without branching.
    q = _col(h, wq, "wq")
    sq = q.shape[1]
    q = q.reshape(b, sq, -1, hd)
    k = _col(h, wk, "wk").reshape(b, sq, -1, hd)
    v = _col(h, wv, "wv").reshape(b, sq, -1, hd)
    # engaged ring-attention region (docs/ATTENTION.md): this block sees
    # ONE sep shard's zigzag token slice, so rope must rotate by the
    # GLOBAL positions of those tokens (from the region's sep ordinal),
    # not 0..s
    from paddle_tpu.distributed.collectives import ring_attention as _ringmod

    _ring_ctx = _ringmod.active_ring_context()
    if use_rope:
        rope_tables = (_ring_ctx.rope_tables(s, hd)
                       if _ring_ctx is not None else None)
        q = _rope_pure(q, tables=rope_tables)
        k = _rope_pure(k, tables=rope_tables)
    # remat anchors (inert under policies that don't name them): saving
    # post-rope q/k/v lets the flash backward skip re-running rms1 + the
    # three projections + rope
    q = _save(q, "attn_q")
    k = _save(k, "attn_k")
    v = _save(v, "attn_v")
    o = _sdpa_pure(q, k, v, causal=True).reshape(b, sq, -1)
    # selective-remat anchor for the XLA-fallback path: with
    # recompute_policy="attn" the backward reuses this tensor instead of
    # re-running attention (quadratic in seq). On the pallas path the
    # custom_vjp residuals carry their own "attn_res"/"attn_lse" names —
    # tagging here too would save the same activation twice, so skip.
    # The ring custom_vjp tags the same two names, so it skips too.
    from paddle_tpu.nn.functional.flash_attention import _use_pallas

    if _ring_ctx is None and not _use_pallas(q.shape):
        o = _save(o, "attn_out")
    # anchors: resid_mid skips the o-proj re-run; ln2_out feeds the
    # gate/up recompute without re-running rms2. On the fused-seam
    # path _row returns the attn output SEQ-SHARDED, so the
    # residual add and rms below run on 1/tp of the rows
    x = _save(x + _row(o, wo, "wo"), "resid_mid")
    h2 = _save(_rms_pure(x, ln2), "ln2_out")
    # per-projection anchors: saving gate/up outputs individually lets a
    # policy trade ~67MB/layer (b4) for skipping that matmul's re-run
    gate = _save(_col(h2, wg, "wg"), "ffn_gate")
    up = _save(_col(h2, wu, "wu"), "ffn_up")
    if _fused_ffn_active(tp_seams):
        from ..ops.pallas.swiglu_down import swiglu_down, swiglu_down_supported

        if swiglu_down_supported(gate.shape, wd.shape):
            # norm→ffn seam megakernel: (silu(gate) * up) @ wd streamed
            # through VMEM — the [tokens, intermediate] swiglu product
            # never round-trips HBM. No "ffn_out" anchor on this path
            # (the custom_vjp backward rebuilds silu*up from the saved
            # gate/up, mirroring the pallas-attention anchor rule above,
            # so a policy naming ffn_out simply saves nothing for it —
            # the silu*mul replay is elementwise; docs/SCAN.md).
            return x + swiglu_down(gate, up, wd)
    ffn = _save(jax.nn.silu(gate) * up, "ffn_out")
    return x + _row(ffn, wd, "wd")


# ---------------------------------------------------------------------------
# Shared scan-over-layers machinery (docs/SCAN.md). The ONE block
# implementation is _block_pure; the helpers below turn it into a remat-
# wrapped scan body (or python-unrolled loop) shared by BOTH decoder
# frontends — StackedDecoder (weights stored [L, ...]) and the eager
# GPTModel LayerList (weights stacked at trace time) — so remat-anchor
# names cannot drift between them.
# ---------------------------------------------------------------------------
#: _block_pure's parameter order, as (StackedDecoder attr, per-layer
#: DecoderLayer state_dict suffix) pairs — also the stacked<->per-layer
#: checkpoint layout contract (convert_decoder_state_dict below)
_BLOCK_PARAM_FIELDS = (
    ("ln1", "input_norm.weight"),
    ("wq", "attn.q_proj.weight"),
    ("wk", "attn.k_proj.weight"),
    ("wv", "attn.v_proj.weight"),
    ("wo", "attn.o_proj.weight"),
    ("ln2", "post_attn_norm.weight"),
    ("wg", "mlp.gate_proj.weight"),
    ("wu", "mlp.up_proj.weight"),
    ("wd", "mlp.down_proj.weight"),
)


def _zero_jit_gather():
    """JIT slab-gather closure over _BLOCK_PARAM_FIELDS, or None when no
    dim-sharded slab is deferred (docs/ZERO.md stage-3) — shared by the
    pure-data zero path and the composed region."""
    from paddle_tpu.distributed.collectives import zero as _zero

    info = _zero.active_jit_gathers()
    if not info:
        return None
    ents = tuple(info.get(attr) for attr, _ in _BLOCK_PARAM_FIELDS)
    if not any(e is not None for e in ents):
        return None

    def gather(p, _ents=ents):
        # per-layer slice of a dim-d-sharded slab is sharded at d-1
        return tuple(
            w if e is None else _zero.gather_shard(
                w, e[0], e[1] - 1, degree=e[2], quantized=e[3])
            for w, e in zip(p, _ents))
    return gather


def _resolve_remat(cfg):
    """(checkpoint policy, int8 anchor names) for ``cfg.recompute_policy``
    — the single parser both decoder frontends share."""
    import jax

    int8_names = frozenset()
    pol = getattr(cfg, "recompute_policy", "full")
    policy = None
    if isinstance(pol, str) and pol.startswith("names:"):
        # free-form selective remat: comma-separated checkpoint_name tags
        # (the available anchors are tagged in _block_pure). An
        # int8:<anchor> entry saves that anchor as blockwise int8 + fp32
        # scales (memory.int8_checkpoint) at ~half the bf16 bytes.
        # quant:<site> entries belong to the quantized-compute resolver
        # (paddle_tpu.quant, docs/QUANT.md) — stripped before the save
        # names parse, they name GEMM sites rather than remat anchors.
        from paddle_tpu.memory import parse_save_names
        from paddle_tpu.quant import split_quant_entries

        spec, _ = split_quant_entries(pol[len("names:"):])
        save_names, int8_names = parse_save_names(spec)
        policy = jax.checkpoint_policies.save_only_these_names(*save_names)
    elif pol == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif pol == "attn":
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_res", "attn_lse")
    elif pol == "attn_ffn":
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_res", "attn_lse", "ffn_out")
    return policy, int8_names


def _resolve_quant(cfg, *, tp_seams=None, composed=False, pipelined=False,
                   path="train"):
    """Trace-time quantized-compute engagement for the shared scan body
    (docs/QUANT.md): ``(engaged sites, narrow dtype)``, with every
    resolution recorded as a structured ``quant_gemm`` plan verdict.

    Precedence mirrors the PR 6/7 rules: engaged tp seams own the
    row/col matmul layouts; the pipeline stage_fn and composed manual
    region don't thread amax state; the fused FFN kernel
    (``swiglu_down``) owns its GEMM, dropping just that site; and with
    ``PTPU_QUANT_COMPUTE`` unset the int8-head-style parity gate (CPU
    default-off) must pass."""
    from paddle_tpu import quant as _quant
    from paddle_tpu.distributed.collectives import compose as _compose

    sites = _quant.requested_quant_sites(cfg)
    if not sites:
        return frozenset(), None
    note = _compose.note_plan_engagement

    def _decline(reason):
        note("quant_gemm", reason)
        _quant.note_gemm_mode(path, frozenset(), None)
        return frozenset(), None

    if composed:
        return _decline(_compose.Reason.QUANT_COMPOSED)
    if pipelined:
        return _decline(_compose.Reason.QUANT_PIPELINE)
    if tp_seams is not None:
        return _decline(_compose.Reason.QUANT_SEAM)
    if not _quant.quant_compute_enabled(requested=True):
        return _decline(_compose.Reason.QUANT_GATE)
    if _fused_ffn_active(tp_seams) and "wd" in sites:
        # the swiglu_down megakernel consumes wd (and declines
        # pre-quantized operands — its VMEM stream is bf16-shaped);
        # gate/up stay quantizable, they feed the kernel post-GEMM
        note("quant_gemm", _compose.Reason.QUANT_FUSED_FFN)
        sites = sites - {"wd"}
    if not sites:
        return frozenset(), None
    dtype = _quant.quant_dtype()
    note("quant_gemm", _compose.Reason.ENGAGED)
    h = cfg.hidden_size
    kv = cfg.num_kv_heads * (h // cfg.num_heads)
    m = cfg.intermediate_size
    dims = {"wq": h * h, "wk": h * kv, "wv": h * kv, "wo": h * h,
            "wg": h * m, "wu": h * m, "wd": m * h}
    flops_per_token = 2 * sum(dims[s] for s in sites) * cfg.num_layers
    _quant.note_gemm_mode(path, sites, dtype, flops_per_token)
    return frozenset(sites), dtype


def _quant_buffer_state(config):
    """The fresh stacked delayed-scaling buffer for ``config``, or None
    when quant-compute is not requested (buffer presence tracks the
    REQUEST — policy ``quant:`` entries or the env force — not the
    parity gate, so a gate flake can't change checkpoint layout)."""
    from paddle_tpu import quant as _quant

    if not _quant.requested_quant_sites(config):
        return None
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor

    return Tensor(jnp.asarray(_quant.init_amax_state(config.num_layers)))


def _make_block(cfg, int8_names=frozenset(), tp_seams=None, policy=None,
                gather=None, quant_sites=frozenset(), quant_dtype=None):
    """One remat-wrapped decoder block over arrays: the scan body. With
    ``cfg.recompute`` each body is a ``jax.checkpoint`` — the remat
    policy (including int8:<anchor> saves) applies PER LAYER whether the
    stack is scanned or unrolled.

    ``gather`` (ZeRO stage 3, docs/ZERO.md): a callable mapping the
    per-layer weight tuple of SHARDS to full weights (all-gather over
    the sharding axis). It runs INSIDE the ``jax.checkpoint`` wrapper,
    so the remat backward re-gathers each layer's weights instead of
    saving L full copies — the fsdp discipline that keeps resident
    decoder HBM at 1/degree.

    ``quant_sites`` (docs/QUANT.md): engaged scaled-GEMM sites. The body
    then takes ``p = (weights, amax_layer)`` and returns
    ``(x, new_amax_layer)`` — delayed-scaling state is an explicit
    input/output because ``jax.checkpoint`` demands a pure body (the
    scan threads it through the stacked ``[L, ...]`` amax buffer)."""
    import jax

    def block(x, p):
        qctx = None
        if quant_sites:
            from paddle_tpu.quant import GemmQuantCtx

            p, amax_l = p
            qctx = GemmQuantCtx(quant_sites, amax_l, quant_dtype)
        if gather is not None:
            p = gather(p)
        out = _block_pure(p, x, cfg.num_heads, cfg.num_kv_heads,
                          cfg.rope, int8_names=int8_names,
                          tp_seams=tp_seams, quant=qctx)
        if qctx is not None:
            return out, qctx.collect()
        return out

    if cfg.recompute:
        block = jax.checkpoint(block, policy=policy)
    return block


def _scan_blocks(block, x, stacked, min_unroll=1, amax=None):
    """Run ``block`` as a lax.scan over a [L, ...]-stacked weight tree —
    compile time and program size flat in depth.

    With ``amax`` (the stacked ``[L, sites, 2, H]`` delayed-scaling
    buffer, docs/QUANT.md) the scan carries it as a second xs leaf and
    collects each layer's updated histories as ys — returns
    ``(out, new_amax)``; the block must be quant-shaped
    (``_make_block(quant_sites=...)``)."""
    import jax

    # ``min_unroll``: the ZeRO just-in-time gather path asks for >= 2 so
    # consecutive (gather_l, block_l) pairs share one loop body and
    # XLA's scheduler can issue layer l+1's slab gather while layer l
    # computes (the fsdp prefetch, docs/ZERO.md).
    unroll = max(1, int(min_unroll))

    if amax is not None:
        def qstep(x, p):
            out, new_amax_l = block(x, p)
            return out, new_amax_l

        return jax.lax.scan(qstep, x, (tuple(stacked), amax), unroll=unroll)

    def step(x, p):
        return block(x, p), None

    out, _ = jax.lax.scan(step, x, tuple(stacked), unroll=unroll)
    return out


def _unrolled_blocks(block, x, layer_params, amax=None):
    """The ``PTPU_SCAN_LAYERS=0`` escape hatch: a python loop over
    per-layer weight tuples — program size linear in depth, float32-hex
    identical to the scanned path (tests/test_scan_layers.py proves it).
    With ``amax`` it mirrors the quant-shaped scan: returns
    ``(out, new_amax)`` with the per-layer histories restacked."""
    if amax is not None:
        import jax.numpy as jnp

        new_rows = []
        for i, p in enumerate(layer_params):
            x, new_amax_l = block(x, (tuple(p), amax[i]))
            new_rows.append(new_amax_l)
        return x, jnp.stack(new_rows)
    for p in layer_params:
        x = block(x, tuple(p))
    return x


class StackedDecoder(nn.Layer):
    """All decoder blocks as leading-axis-stacked parameters [L, ...].

    TPU-first: a single lax.scan over layers (constant compile time at any
    depth) when pp is off; when the active mesh has a "pp" axis > 1, the
    leading axis is stage-sharded and the compiled SPMD pipeline schedule
    (distributed/pipeline.py) runs microbatches through ppermute rotation.
    """

    def __init__(self, config: GPTConfig):
        super().__init__()
        if config.norm_type != "rmsnorm" or config.act != "swiglu":
            raise ValueError("StackedDecoder supports the rmsnorm+swiglu family")
        if not config.rope:
            raise ValueError("StackedDecoder requires rope positions "
                             "(learned embed_pos is not supported)")
        if config.dropout:
            raise ValueError("StackedDecoder does not support dropout")
        from paddle_tpu.nn.initializer import Constant, Normal

        L, h = config.num_layers, config.hidden_size
        hd = h // config.num_heads
        kv = config.num_kv_heads * hd
        m = config.intermediate_size
        self.config = config
        w = lambda *shape: self.create_parameter(
            list(shape), default_initializer=Normal(std=0.02)
        )
        one = Constant(1.0)
        self.ln1 = self.create_parameter([L, h], default_initializer=one)
        self.wq = w(L, h, h)
        self.wk = w(L, h, kv)
        self.wv = w(L, h, kv)
        self.wo = w(L, h, h)
        self.ln2 = self.create_parameter([L, h], default_initializer=one)
        self.wg = w(L, h, m)
        self.wu = w(L, h, m)
        self.wd = w(L, m, h)
        # delayed-scaling amax state [L, sites, 2, H] (docs/QUANT.md):
        # registered only when quant-compute is REQUESTED, so unrequested
        # builds are structurally identical to pre-quant programs (the
        # PTPU_QUANT_COMPUTE=0 hex-identity contract). As a persistable
        # buffer it rides TrainStep/ShardedTrainStep threading, StepGuard
        # skip/rollback, and CheckpointManager like the RNG-key chain.
        amax0 = _quant_buffer_state(config)
        if amax0 is not None:
            self.register_buffer("quant_amax", amax0)

    def _mesh_pp(self):
        from paddle_tpu.distributed.fleet import active_mesh

        mesh = active_mesh()
        if mesh is None or "pp" not in mesh.dim_names:
            return None, 1
        return mesh, mesh.get_dim_size("pp")

    # Megatron TP dims of the stacked weights: column-parallel projections
    # shard their OUTPUT dim, row-parallel ones their INPUT dim (the mp
    # collectives are GSPMD-inserted: the pipeline shard_map keeps only
    # 'pp' manual, every other mesh axis stays auto)
    _TP_DIMS = {"wq": 2, "wk": 2, "wv": 2, "wg": 2, "wu": 2,
                "wo": 1, "wd": 1}

    def apply_tp_placements(self, mesh=None, tp_axis="mp"):
        """Megatron TP placements on a PIPELINE-FREE mesh: shard the
        projection weights' column/row dims (_TP_DIMS) over ``tp_axis``,
        leaving the stacked layer dim replicated. The pp x mp hybrid
        keeps using :meth:`apply_pipeline_placements`; this is the entry
        for pure-TP / dp x mp meshes where the fused compute-collective
        seams (distributed/collectives.fused, docs/COMMS.md) can own the
        row/col-parallel matmuls."""
        from paddle_tpu.distributed.auto_parallel import (
            Replicate, Shard, TensorDistAttr)

        if mesh is None:
            from paddle_tpu.distributed.fleet import active_mesh

            mesh = active_mesh()
        if (mesh is None or tp_axis not in mesh.dim_names
                or mesh.get_dim_size(tp_axis) <= 1):
            return self
        tp = mesh.get_dim_size(tp_axis)
        cfg = self.config
        for what, n in (("num_heads", cfg.num_heads),
                        ("num_kv_heads", cfg.num_kv_heads),
                        ("intermediate_size", cfg.intermediate_size)):
            if n % tp != 0:
                raise ValueError(f"tp_axis={tp_axis!r} (size {tp}) must "
                                 f"divide {what} ({n})")
        ax = mesh.dim_names.index(tp_axis)
        for name, p in self.named_parameters():
            leaf = name.rsplit(".", 1)[-1]
            if leaf not in self._TP_DIMS:
                continue
            placements = [Replicate() for _ in mesh.dim_names]
            placements[ax] = Shard(self._TP_DIMS[leaf])
            p._dist_attr = TensorDistAttr(mesh, placements)
        return self

    def apply_pipeline_placements(self, mesh=None, tp_axis=None):
        """Mark every stacked param Shard(0) over the 'pp' mesh axis.

        tp_axis="mp" additionally shards the projection weights over the
        tensor-parallel axis (column/row-parallel dims per _TP_DIMS), so
        one placement pass yields the full pp x mp hybrid — the
        fleet 3-axis composition (reference: pp->mp->dp group nesting,
        fleet/base/topology.py:298) expressed as GSPMD placements."""
        from paddle_tpu.distributed.auto_parallel import (
            Replicate, Shard, TensorDistAttr)

        if mesh is None:
            mesh, pp = self._mesh_pp()
            if mesh is None:
                return self
        ax = mesh.dim_names.index("pp")
        tp_ax = None
        if (tp_axis is not None and tp_axis in mesh.dim_names
                and mesh.get_dim_size(tp_axis) > 1):
            tp_ax = mesh.dim_names.index(tp_axis)
            cfg = self.config
            tp = mesh.get_dim_size(tp_axis)
            for what, n in (("num_heads", cfg.num_heads),
                            ("num_kv_heads", cfg.num_kv_heads),
                            ("intermediate_size", cfg.intermediate_size)):
                if n % tp != 0:
                    raise ValueError(
                        f"tp_axis={tp_axis!r} (size {tp}) must divide "
                        f"{what} ({n})")
        for name, p in self.named_parameters():
            placements = [Replicate() for _ in mesh.dim_names]
            placements[ax] = Shard(0)
            leaf = name.rsplit(".", 1)[-1]
            if tp_ax is not None and leaf in self._TP_DIMS:
                placements[tp_ax] = Shard(self._TP_DIMS[leaf])
            p._dist_attr = TensorDistAttr(mesh, placements)
        return self

    def _run_composed(self, ctx, x, params):
        """Composed-region decoder body (collectives/compose,
        docs/COMMS.md lattice): runs PER SHARD inside the step's ONE
        fully-manual region. The residual stream is sequence-sharded
        over mp between the in-region seams (seq_split/seq_unsplit are
        the hand-written transpose pair), ZeRO slab gathers defer into
        the scan body exactly as in the pure-data zero mode, and a live
        pipeline axis runs the explicit inline 1F1B/zero-bubble
        schedule (distributed/pipeline.py) over this shard's stage
        slab with the stage ordinal from the region's sharded iota."""
        cfg = self.config
        plan = ctx.plan
        policy, int8_names = (_resolve_remat(cfg) if cfg.recompute
                              else (None, frozenset()))
        gather = _zero_jit_gather()

        seams = ctx.seams
        block = _make_block(cfg, int8_names=int8_names,
                            tp_seams=seams, policy=policy, gather=gather)
        ctx.decoder_calls += 1
        if seams is not None:
            x = seams.seq_split(x)
        if plan.pp_axis:
            x = ctx.pipeline_apply(block, x, params,
                                   gather=gather is not None)
        elif scan_layers_enabled():
            x = _scan_blocks(block, x, params,
                             min_unroll=2 if gather else 1)
        else:
            L = int(params[0].shape[0])
            x = _unrolled_blocks(
                block, x,
                (tuple(w[i] for w in params) for i in range(L)))
        if seams is not None:
            x = seams.seq_unsplit(x)
        return x

    def forward(self, x):
        import jax
        from paddle_tpu.core.dispatch import apply_op

        cfg = self.config
        mesh, pp = self._mesh_pp()
        quant_buf = self._buffers.get("quant_amax")

        def _run(x, *params):
            import os

            from paddle_tpu.distributed.collectives import (
                compose as _compose)

            # quant-compute amax state rides as the last operand when the
            # buffer exists; declined paths pass it through unchanged so
            # the output structure stays (x, amax) either way
            amax = None
            if quant_buf is not None:
                amax = params[-1]
                params = params[:-1]

            def _out(res, new_amax=None):
                if quant_buf is None:
                    return res
                return res, (amax if new_amax is None else new_amax)

            _ctx = _compose.active_composed_context()
            if _ctx is not None:
                _resolve_quant(cfg, composed=True)
                return _out(self._run_composed(_ctx, x, params))

            policy, int8_names = (_resolve_remat(cfg) if cfg.recompute
                                  else (None, frozenset()))

            # fused tp seams (docs/COMMS.md): owned matmul+reduce-scatter /
            # all-gather+matmul kernels replace the GSPMD-emitted mp
            # collectives at the row/col-parallel seams. Resolved per
            # trace — plan_tp_seams returns None under pp, inside the
            # quantized dp-grad manual region, with PTPU_TP_SEAM=0, or
            # when no tp placement is live on the stacked weights.
            tp_seams = None
            if pp <= 1:
                da = getattr(self.wq, "_dist_attr", None)
                if da is not None:
                    from paddle_tpu.distributed.auto_parallel import Shard
                    from paddle_tpu.distributed import collectives

                    # DATA axes are never tp axes: ZeRO stage-3 marks
                    # (shard_model_parameters) also land Shard(dim>0)
                    # placements over "sharding" — treating one as a
                    # Megatron tp placement built seam specs naming the
                    # same mesh axis twice (duplicate-axis ValueError)
                    tp_axes = [
                        a for a, pl in zip(da.process_mesh.dim_names,
                                           da.placements)
                        if isinstance(pl, Shard) and pl.dim > 0
                        and a not in ("dp", "sharding")]
                    if len(tp_axes) == 1:
                        tp_seams = collectives.plan_tp_seams(
                            da.process_mesh, tp_axis=tp_axes[0])

            # ZeRO stage-3 just-in-time slab gathers (docs/ZERO.md): the
            # ShardedTrainStep's manual region passes the stacked
            # weights in as their 1/degree dim shards and opens this
            # scope; each sharded slab gathers per layer INSIDE the
            # remat-wrapped scan body (backward re-gathers), and AD of
            # the gather reduce-scatters the slab grads.
            gather = (_zero_jit_gather()
                      if pp <= 1 and tp_seams is None else None)

            # quantized-compute engagement (docs/QUANT.md): resolved per
            # trace against the live path — engaged tp seams and the
            # pipeline stage_fn decline with a structured reason
            q_sites, q_dtype = _resolve_quant(cfg, tp_seams=tp_seams,
                                              pipelined=pp > 1)
            if q_sites and amax is None:
                # env-forced quant on a model built without the buffer:
                # run stateless (all-zero histories bootstrap from the
                # current step's amax — the inline-scaling recipe)
                import jax.numpy as jnp

                from paddle_tpu import quant as _quant

                amax = jnp.zeros(
                    (cfg.num_layers, len(_quant.GEMM_SITES), 2,
                     _quant.amax_hist_len()), jnp.float32)

            block = _make_block(cfg, int8_names=int8_names,
                                tp_seams=tp_seams, policy=policy,
                                gather=gather, quant_sites=q_sites,
                                quant_dtype=q_dtype)

            if pp <= 1:
                if scan_layers_enabled():
                    if q_sites:
                        out, new_amax = _scan_blocks(
                            block, x, params,
                            min_unroll=2 if gather else 1, amax=amax)
                        return _out(out, new_amax)
                    return _out(_scan_blocks(
                        block, x, params, min_unroll=2 if gather else 1))
                # PTPU_SCAN_LAYERS=0 escape hatch: python-unrolled loop
                # over constant-offset slices of the stacked weights —
                # program size linear in depth, numerics bitwise equal
                L = int(params[0].shape[0])
                per_layer = (tuple(w[i] for w in params) for i in range(L))
                if q_sites:
                    out, new_amax = _unrolled_blocks(block, x, per_layer,
                                                     amax=amax)
                    return _out(out, new_amax)
                return _out(_unrolled_blocks(block, x, per_layer))

            def step(x, p):
                return block(x, p), None

            # a hybrid pipeline mesh outside the composed path would
            # open a PARTIAL-manual shard_map over 'pp' — this
            # container's XLA hard-ABORTS the partitioner on
            # CollectivePermute with manual subgroups (docs/COMMS.md
            # runtime limits), killing the whole process instead of
            # raising. Refuse loudly first; the composed hybrid step
            # (collectives/compose) is the supported lowering here.
            live_others = [a for a in mesh.dim_names
                           if a != "pp" and mesh.get_dim_size(a) > 1]
            if live_others and jax.default_backend() == "cpu":
                raise RuntimeError(
                    "pipeline parallelism with other live mesh axes "
                    f"({'/'.join(live_others)}) cannot lower as a "
                    "partial-manual shard_map on this XLA build — use "
                    "the composed hybrid step (ShardedTrainStep over "
                    "the full mesh, docs/COMMS.md lattice) or a "
                    "pp-only mesh. If composition was declined, the "
                    "plan_engagement telemetry names the reason "
                    "(tools/telemetry_report.py -- plans --).")

            from paddle_tpu.distributed.pipeline import (
                microbatch, spmd_pipeline, spmd_pipeline_interleaved,
                spmd_pipeline_zero_bubble,
                spmd_pipeline_zero_bubble_interleaved, unmicrobatch)

            def stage_fn(stage_params, x):
                out, _ = jax.lax.scan(step, x, stage_params)
                return out

            from jax.sharding import PartitionSpec as P

            v = getattr(cfg, "pp_interleave", 1) or 1
            n_micro = getattr(cfg, "pp_microbatches", None) or pp
            zb = getattr(cfg, "pp_schedule", "1f1b") == "zb"
            if v > 1:
                if cfg.num_layers % (pp * v) != 0:
                    raise ValueError(
                        f"pp_interleave={v} needs num_layers "
                        f"({cfg.num_layers}) divisible by pp*v ({pp * v})")
                mk = (spmd_pipeline_zero_bubble_interleaved if zb
                      else spmd_pipeline_interleaved)
                pipe = mk(stage_fn, mesh.jax_mesh, pp, v,
                          remat=cfg.recompute)
            elif zb:
                pipe = spmd_pipeline_zero_bubble(
                    stage_fn, mesh.jax_mesh, pp,
                    params_spec=P("pp"), remat=cfg.recompute)
            else:
                pipe = spmd_pipeline(
                    stage_fn, mesh.jax_mesh, pp,
                    params_spec=P("pp"), remat=cfg.recompute,
                )
            return _out(unmicrobatch(pipe(tuple(params),
                                          microbatch(x, n_micro))))

        operands = [x, self.ln1, self.wq, self.wk, self.wv, self.wo,
                    self.ln2, self.wg, self.wu, self.wd]
        if quant_buf is not None:
            operands.append(quant_buf)
        out = apply_op(_run, *operands, _op_name="stacked_decoder")
        if quant_buf is not None:
            from paddle_tpu.core.tensor import Tensor

            out, new_amax = out
            quant_buf._data = (new_amax._data
                               if isinstance(new_amax, Tensor) else new_amax)
        return out


class GPTForCausalLMPipe(nn.Layer):
    """Decoder-only LM with the stacked/pipelined decoder core."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        if not config.tie_embeddings:
            raise ValueError("GPTForCausalLMPipe ties the lm head to the "
                             "token embedding (tie_embeddings=False is not "
                             "supported)")
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.decoder = StackedDecoder(config)
        self.final_norm = nn.RMSNorm(config.hidden_size)

    def forward(self, input_ids, attn_mask=None):
        x = self.embed_tokens(input_ids)
        x = self.decoder(x)
        x = self.final_norm(x)
        return paddle.matmul(x, self.embed_tokens.weight, transpose_y=True)

    def loss(self, input_ids, labels):
        """Fused tied-head LM loss: hidden @ embed^T + CE computed
        blockwise over VOCAB chunks (custom_vjp recomputes per-chunk
        logits in backward), so neither the fp32 logits nor the
        grad-logits [N, vocab] tensor ever hits HBM — ~1GB+1GB per
        microbatch at 1.3B/seq2048/batch4. With a vocab-sharded head
        (shard_lm_head) each tp shard reduces scalars per token instead
        of all-gathering logits."""
        x = self.embed_tokens(input_ids)
        x = self.decoder(x)
        x = self.final_norm(x)
        return compute_loss(x, self.embed_tokens.weight, labels,
                            config=self.config, transpose_y=True)

    def shard_lm_head(self, mesh, axis="mp"):
        """Last-stage-sharded pipeline output: place the tied
        head/embedding's VOCAB dim over the tensor-parallel axis instead
        of replicating it. The loss path (compute_loss) sees the marker
        and switches to the vocab-sharded CE — partial per-shard
        (max, lse, gold) combined with psum of scalars per token; on a
        pp mesh the last stage then holds 1/tp of the head instead of a
        full replica. Embedding lookups against the sharded table lower
        to GSPMD's gather+collective (the Megatron parallel-vocab
        recipe)."""
        from paddle_tpu.distributed.auto_parallel import (
            Replicate, Shard, TensorDistAttr)

        if axis not in mesh.dim_names or mesh.get_dim_size(axis) <= 1:
            return self
        if self.config.vocab_size % mesh.get_dim_size(axis) != 0:
            raise ValueError(
                f"the {axis!r} mesh axis (size {mesh.get_dim_size(axis)}) "
                f"must divide vocab_size ({self.config.vocab_size})")
        w = self.embed_tokens.weight
        placements = [Replicate() for _ in mesh.dim_names]
        placements[mesh.dim_names.index(axis)] = Shard(0)
        w._dist_attr = TensorDistAttr(mesh, placements)
        w._vocab_shard_axis = axis
        return self

    def _decode_params(self):
        """Per-layer slices of the stacked decoder weights — the serving/
        decode contract shared with LlamaForCausalLM (llama.py:66), so
        the flagship pipelined model serves through
        inference.ContinuousBatchingEngine unchanged.

        jnp indexing COPIES, so materializing every layer up front held a
        second full copy of the decoder for as long as the returned list
        lived — and a reload_weights() on a live engine transiently held
        THREE (stacked + old slices + new slices, ADVICE r5). Returns a
        lazy sequence instead: each layer is sliced on access and nothing
        is retained here, so consumers that process layers one at a time
        (the engine's _pack_weights) peak at stacked + one layer + their
        own copy. The engine's packed copy itself is inherent while the
        training model stays alive; for serving at flagship sizes, drop
        the training model after engine construction."""
        names = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")
        return _LazyLayerSlices(self.decoder, names, self.config.num_layers)


class _LazyLayerSlices:
    """Sequence of per-layer weight dicts over a StackedDecoder, sliced on
    access (each ``__getitem__`` copies ONE layer's weights; nothing is
    cached). Satisfies the ``_decode_params`` contract: len(), indexing,
    and iteration yield ``{name: obj-with-._data}`` per layer."""

    def __init__(self, decoder, names, num_layers):
        self._decoder = decoder
        self._names = names
        self._num_layers = num_layers

    def __len__(self):
        return self._num_layers

    def __getitem__(self, i):
        from types import SimpleNamespace

        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._num_layers))]
        if i < 0:
            i += self._num_layers
        if not 0 <= i < self._num_layers:
            raise IndexError(i)
        d = self._decoder
        return {n: SimpleNamespace(_data=getattr(d, n)._data[i])
                for n in self._names}

    def __iter__(self):
        for i in range(self._num_layers):
            yield self[i]


# ---------------------------------------------------------------------------
# Stacked <-> per-layer checkpoint layout conversion (docs/SCAN.md).
# The scanned flagship stores decoder weights [L, ...]-stacked
# (GPTForCausalLMPipe: "decoder.wq"), the eager LayerList family stores
# them per layer ("model.layers.{i}.attn.q_proj.weight"). A checkpoint
# written under either layout restores into the other BIT-FOR-BIT through
# these converters — old per-layer checkpoints keep working after a model
# is promoted to the stacked layout, and vice versa.
# ---------------------------------------------------------------------------
_SUFFIX_TO_ATTR = {suffix: attr for attr, suffix in _BLOCK_PARAM_FIELDS}
#: top-level (non-decoder) key mapping: stacked-side name -> per-layer name
_TOP_KEY_MAP = {"embed_tokens.weight": "model.embed_tokens.weight",
                "final_norm.weight": "model.final_norm.weight"}


def _raw_array(v):
    return v._data if hasattr(v, "_data") else v


def _split_opt_key(key):
    """("opt." or "", param-ish remainder). Optimizer entries are saved
    as "opt.<param_name>.<slot>" (distributed.checkpoint)."""
    return ("opt.", key[4:]) if key.startswith("opt.") else ("", key)


def _match_layer_key(rest):
    """per-layer decoder key -> (layer_index, attr, slot_suffix) or None.
    rest: "model.layers.3.attn.q_proj.weight[.slot]"."""
    prefix = "model.layers."
    if not rest.startswith(prefix):
        return None
    tail = rest[len(prefix):]
    idx, _, tail = tail.partition(".")
    if not idx.isdigit():
        return None
    for suffix, attr in _SUFFIX_TO_ATTR.items():
        if tail == suffix:
            return int(idx), attr, ""
        if tail.startswith(suffix + "."):
            return int(idx), attr, tail[len(suffix):]
    return None


def _match_stacked_key(rest):
    """stacked decoder key -> (attr, slot_suffix) or None.
    rest: "decoder.wq[.slot]"."""
    if not rest.startswith("decoder."):
        return None
    tail = rest[len("decoder."):]
    attr, _, slot = tail.partition(".")
    if attr not in _SUFFIX_TO_ATTR.values():
        return None
    return attr, ("." + slot if slot else "")


def decoder_state_layout(state):
    """"stacked" | "per_layer" | None for a LM state_dict's key set."""
    for key in state:
        _, rest = _split_opt_key(key)
        if _match_stacked_key(rest) is not None:
            return "stacked"
        if _match_layer_key(rest) is not None:
            return "per_layer"
    return None


def convert_decoder_state_dict(state, target):
    """Convert a GPT/LLaMA LM state_dict (params + optional
    "opt.<param>.<slot>" optimizer entries) to ``target`` ("stacked" |
    "per_layer"). Decoder weights are stacked/sliced along the leading
    layer axis bit-for-bit; param-shaped and factored slot entries follow
    their parameter, scalar slots (beta power accumulators) replicate on
    unstacking and must agree bitwise on stacking. Already-converted and
    unknown keys pass through unchanged (a strict restore then reports
    them). Blockwise-int8 moment slots do NOT convert exactly (their
    quant-block grid spans the stacked axis) — restore those under the
    layout that wrote them."""
    import numpy as np
    import jax.numpy as jnp

    if target not in ("stacked", "per_layer"):
        raise ValueError(f"target={target!r}: expected stacked|per_layer")
    out = {}
    if target == "stacked":
        pending = {}  # (pre, attr, slot) -> {layer_index: array}
        for key, v in state.items():
            pre, rest = _split_opt_key(key)
            m = _match_layer_key(rest)
            if m is None:
                new = rest
                for stacked_k, layer_k in _TOP_KEY_MAP.items():
                    if rest == layer_k:
                        new = stacked_k
                    elif rest.startswith(layer_k + "."):
                        new = stacked_k + rest[len(layer_k):]
                out[pre + new] = _raw_array(v)
                continue
            i, attr, slot = m
            pending.setdefault((pre, attr, slot), {})[i] = _raw_array(v)
        for (pre, attr, slot), by_layer in pending.items():
            L = max(by_layer) + 1
            missing = [i for i in range(L) if i not in by_layer]
            if missing:
                raise ValueError(
                    f"per-layer state is missing layers {missing} of "
                    f"{attr}{slot} (found {sorted(by_layer)})")
            arrs = [by_layer[i] for i in range(L)]
            if getattr(arrs[0], "ndim", 0) == 0:
                ref = np.asarray(arrs[0])
                for i, a in enumerate(arrs[1:], 1):
                    if np.asarray(a).tobytes() != ref.tobytes():
                        raise ValueError(
                            f"scalar slot {attr}{slot} differs between "
                            f"layers 0 and {i} — cannot collapse into one "
                            "stacked entry")
                out[pre + "decoder." + attr + slot] = arrs[0]
            else:
                out[pre + "decoder." + attr + slot] = jnp.stack(
                    [jnp.asarray(a) for a in arrs])
        return out

    # target == "per_layer"
    num_layers = None
    for key, v in state.items():
        _, rest = _split_opt_key(key)
        m = _match_stacked_key(rest)
        if m is not None and m[1] == "":
            num_layers = int(_raw_array(v).shape[0])
            break
    for key, v in state.items():
        pre, rest = _split_opt_key(key)
        m = _match_stacked_key(rest)
        if m is None:
            new = rest
            for stacked_k, layer_k in _TOP_KEY_MAP.items():
                if rest == stacked_k:
                    new = layer_k
                elif rest.startswith(stacked_k + "."):
                    new = layer_k + rest[len(stacked_k):]
            out[pre + new] = _raw_array(v)
            continue
        attr, slot = m
        suffix = dict(_BLOCK_PARAM_FIELDS)[attr]
        arr = _raw_array(v)
        if num_layers is None:
            raise ValueError("cannot infer num_layers: no stacked decoder "
                             "parameter entry in the state dict")
        for i in range(num_layers):
            per = (arr[i] if getattr(arr, "ndim", 0) >= 1
                   and arr.shape[0] == num_layers else arr)
            out[f"{pre}model.layers.{i}.{suffix}{slot}"] = per
    return out


def restore_decoder_any_layout(manager, model, optimizer=None, step=None,
                               strict=True):
    """``CheckpointManager.restore_training_state`` that also accepts a
    checkpoint written under the OTHER decoder layout: a per-layer
    (eager GPTForCausalLM / LLaMA) checkpoint restores into a stacked
    GPTForCausalLMPipe model bit-for-bit, and vice versa. A metadata-only
    layout peek routes same-layout checkpoints through the exact
    pre-existing native restore (reshard-on-load, the caller's
    ``strict``); other-layout checkpoints go through
    ``manager.read_state`` + :func:`convert_decoder_state_dict`.
    Returns the step restored."""
    import jax.numpy as jnp

    from paddle_tpu.distributed.checkpoint import (
        MissingKeysError, _training_state_target)

    # Metadata-only layout peek decides the path BEFORE loading
    # anything: a same-layout checkpoint (including a lenient
    # strict=False partial restore) keeps the exact native
    # reshard-on-load path; only a genuinely other-layout checkpoint
    # pays the convert. (Deciding by probing the native restore instead
    # would either let a non-strict cross-layout restore "succeed"
    # loading nothing, or reroute lenient same-layout restores through
    # the converter and lose their resharding.)
    want = decoder_state_layout(model.state_dict())
    have = decoder_state_layout(manager.saved_keys(step=step))
    if want is None or have is None or have == want:
        try:
            return manager.restore_training_state(model, optimizer,
                                                  step=step, strict=strict)
        except MissingKeysError:
            if want is None:
                raise
            # mixed-layout root: the newest good step (whose layout the
            # peek saw) failed payload validation and the native walk
            # fell back onto an OTHER-layout older step — convert that
            # one below. (Residual corner: under strict=False such a
            # walk cannot raise and loads nothing from the other-layout
            # step; mixed-layout roots should restore with strict=True.)
    state, s = manager.read_state(step=step)
    target, finalize = _training_state_target(model, optimizer)
    want = decoder_state_layout(target) or "per_layer"
    conv = convert_decoder_state_dict(state, want)
    missing = [k for k in target if k not in conv]
    if missing and strict:
        raise MissingKeysError(
            f"checkpoint step {s} (converted to {want} layout) holds no "
            f"payload for: {sorted(missing)[:8]}"
            + ("..." if len(missing) > 8 else ""))
    import jax

    for k, t in target.items():
        if k not in conv:
            continue
        arr = jnp.asarray(conv[k])
        if tuple(arr.shape) != tuple(t.shape):
            raise ValueError(
                f"{k}: converted shape {tuple(arr.shape)} != model shape "
                f"{tuple(t.shape)}")
        # keep the target's placement: a parameter already device_put on
        # a mesh must not silently degrade to a replicated host array
        t._data = jax.device_put(arr.astype(t._data.dtype),
                                 t._data.sharding)
    finalize()
    return s


# ---------------------------------------------------------------------------
# MoE variant (parity slot: PaddleNLP MoE GPT over incubate MoELayer)
# ---------------------------------------------------------------------------
class MoEDecoderLayer(nn.Layer):
    """Decoder block whose MLP is a mixture of experts."""

    def __init__(self, config: GPTConfig, num_experts=8, top_k=2,
                 gate="gshard", capacity_factor=2.0):
        super().__init__()
        from paddle_tpu.incubate.distributed.models.moe import (
            MoELayer, StackedExperts)

        norm_cls = nn.RMSNorm if config.norm_type == "rmsnorm" else nn.LayerNorm
        self.input_norm = norm_cls(config.hidden_size)
        self.attn = Attention(config)
        self.post_attn_norm = norm_cls(config.hidden_size)
        self.moe = MoELayer(
            config.hidden_size,
            StackedExperts(num_experts, config.hidden_size,
                           config.intermediate_size),
            gate={"type": gate, "top_k": top_k},
            capacity_factor=capacity_factor,
        )

    def forward(self, x, attn_mask=None):
        h = x + self.attn(self.input_norm(x), attn_mask)
        return h + self.moe(self.post_attn_norm(h))


class GPTForCausalLMMoE(GPTForCausalLM):
    """Decoder LM with MoE FFNs; aux losses summed into .loss().

    Reuses the GPTModel scaffolding (embed/pos/recompute/final-norm/tied
    head) via the layer factory — only the block type differs."""

    def __init__(self, config: GPTConfig, num_experts=8, top_k=2,
                 gate="gshard", aux_loss_weight=0.01, capacity_factor=2.0):
        if not config.tie_embeddings:
            raise ValueError("GPTForCausalLMMoE ties the lm head to the "
                             "token embedding")
        if gate == "switch" and top_k != 1:
            raise ValueError("switch gate is top-1: pass top_k=1")
        super().__init__(config, layer_factory=lambda: MoEDecoderLayer(
            config, num_experts, top_k, gate, capacity_factor))
        self.aux_loss_weight = aux_loss_weight

    @property
    def layers(self):
        return self.model.layers

    def aux_loss(self):
        total = None
        for layer in self.model.layers:
            la = layer.moe.l_aux
            if la is not None:
                total = la if total is None else total + la
        return total

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        lm = F.cross_entropy(
            logits.reshape([-1, self.config.vocab_size]),
            labels.reshape([-1]))
        aux = self.aux_loss()
        if aux is not None:
            lm = lm + self.aux_loss_weight * aux
        return lm

    def apply_expert_placements(self, mesh, axis="dp"):
        """Expert parallelism for every MoE layer."""
        from paddle_tpu.incubate.distributed.models.moe import (
            shard_expert_parameters)

        for layer in self.model.layers:
            shard_expert_parameters(layer.moe, mesh, axis)
        return self
