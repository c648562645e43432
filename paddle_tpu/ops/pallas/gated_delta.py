"""The gated delta rule's recurrent state (Gated DeltaNet, arXiv:
2412.06464), stepped in place: one position of one linear-attention layer
for a batch of rows, each row's state addressed by (layer, slot) in a
stacked store.

Per row and head, on the state ``S`` [d_k, d_v] (float32), with unit
``k`` and ``q`` (``q`` scaled), a decay ``alpha`` and a step ``beta``:

    aS = alpha S;  u = beta (v - aS^T k);  S' = aS + k u^T;  o = S'^T q

0.75 FLOP a byte: the step is bound by reading and writing ``S``.

**The store's layout.** ``[layers, slots + 1, H / g, d_k, g * d_v]``
float32: ``g`` heads lie side by side along the lanes, so that a row of
the state fills whole 128-lane tiles (d_v 192: g = 2, 384 lanes, nothing
padded; a lone head's 192 would lie in 256). Slot ``slots`` (the last) is
the trash slot: rows that are only padding name it, and the kernel
neither fetches nor writes anything for them. ``pack_state`` /
``unpack_state`` go between this layout and ``[..., H, d_k, d_v]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HI = jax.lax.Precision.HIGHEST

#: what one grid step's block of the state may take (the block is held
#: four times: in and out, double-buffered)
_BLOCK_BYTES = 1024 * 1024


def heads_per_lane_row(heads, dv):
    """``g``: the fewest heads whose values fill whole 128-lane tiles side
    by side, if that many divide the heads; else 1 (the tile is padded)."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * dv) % 128 == 0:
            return g
    return 1


def pack_state(s, g):
    """[..., H, d_k, d_v] -> [..., H/g, d_k, g*d_v]."""
    *lead, h, dk, dv = s.shape
    s = s.reshape(*lead, h // g, g, dk, dv)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // g, dk, g * dv)


def unpack_state(s, g):
    """[..., H/g, d_k, g*d_v] -> [..., H, d_k, d_v]."""
    *lead, hg, dk, w = s.shape
    s = s.reshape(*lead, hg, dk, g, w // g)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, hg * g, dk, w // g)


def gdn_decode_step_reference(q, k, v, alpha, beta, store, slots, layer, g):
    """The step in plain jax.numpy (the CPU's path, and what the kernel
    is tested against): q, k [B, H, d_k], v [B, H, d_v], alpha, beta
    [B, H], all float32 -> (o [B, H, d_v], the store). Rows that name the
    same slot (padding, on the trash slot) overwrite one another."""
    s = unpack_state(store[layer, slots], g)             # [B, H, dk, dv]
    s = alpha[..., None, None] * s
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                          precision=HI))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=HI)
    return o, store.at[layer, slots].set(pack_state(s, g))


def _kernel(fetch_ref, slot_ref, layer_ref, k_ref, q_ref, v_ref, a_ref,
            b_ref, s_ref, o_ref, s_out, *, hb, g, dv, trash):
    row = pl.program_id(1)

    @pl.when(slot_ref[row] != trash)       # padding: nothing is stepped
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape[1:], 1)
        kcols, qcols = k_ref[0, 0], q_ref[0, 0]          # [dk, hb * g]
        for p in range(hb):                # a lane row of g heads a time

            def across(cols):
                # [dk, g * dv]: column j of the row's heads over head j's
                # lanes
                out = cols[:, p * g:p * g + 1]
                for j in range(1, g):
                    out = jnp.where(lane >= j * dv,
                                    cols[:, p * g + j:p * g + j + 1], out)
                return jnp.broadcast_to(out, lane.shape)

            kb, qb = across(kcols), across(qcols)
            a_s = a_ref[0, p] * s_ref[p]                 # [dk, g * dv]
            u = b_ref[0, p] * (v_ref[0, p]
                               - jnp.sum(a_s * kb, 0, keepdims=True))
            new = a_s + kb * u
            s_out[p] = new
            o_ref[0, p] = jnp.sum(new * qb, 0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("g", "interpret"))
def _call(q, k, v, alpha, beta, store, slots, layer, *, g, interpret):
    b, h, dk = q.shape
    dv = v.shape[-1]
    _, nslots, hg, _, w = store.shape
    trash = nslots - 1
    # lane rows of g heads a grid step: the most that divide the layer's
    # and keep the block under _BLOCK_BYTES
    hb = max(d for d in range(1, hg + 1)
             if hg % d == 0 and (d == 1 or d * dk * w * 4 <= _BLOCK_BYTES))
    nj = hg // hb
    i32 = jnp.int32
    slots = slots.astype(i32)
    # a padded row names the block of the live row before it (the first
    # rows: their own, the trash slot's), so the pipeline sees an
    # unchanged index: nothing is fetched for it, nothing written
    live = slots != trash
    at = jax.lax.cummax(jnp.where(live, jnp.arange(b, dtype=i32), i32(0)), 0)
    fetch = slots[at]

    def cols(x):                           # [B, H, dk] -> [B, nj, dk, hb*g]
        return jnp.swapaxes(x.reshape(b, nj, hb * g, dk), -1, -2)

    def lanes(x):                          # [B, H, dv] -> [B, hg, 1, g*dv]
        return x.reshape(b, hg, 1, w)

    per_head = lambda x: lanes(jnp.broadcast_to(x[..., None], (b, h, dv)))
    col_spec = pl.BlockSpec((1, 1, dk, hb * g),
                            lambda j, r, fetch, slot, li: (r, j, 0, 0))
    lane_spec = pl.BlockSpec((1, hb, 1, w),
                             lambda j, r, fetch, slot, li: (r, j, 0, 0))
    state_spec = pl.BlockSpec(
        (None, None, hb, dk, w),
        lambda j, r, fetch, slot, li: (li[0], fetch[r], j, 0, 0))
    with jax.enable_x64(False):
        o, store = pl.pallas_call(
            functools.partial(_kernel, hb=hb, g=g, dv=dv, trash=trash),
            # the trace's name: kernel.gdn_decode_step_roofline.* and
            # kernel.gdn_decode_step_share.* read the operations that
            # start with it
            name="gdn_decode_step",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(nj, b),
                in_specs=[col_spec, col_spec, lane_spec, lane_spec,
                          lane_spec, state_spec],
                out_specs=[lane_spec, state_spec],
            ),
            out_shape=[jax.ShapeDtypeStruct((b, hg, 1, w), jnp.float32),
                       jax.ShapeDtypeStruct(store.shape, store.dtype)],
            # operand 8 (after the three prefetched scalars) is the store
            input_output_aliases={8: 1},
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=6 * b * h * dk * dv,
                bytes_accessed=2 * b * h * dk * dv * 4,
                transcendentals=0),
        )(fetch, slots, jnp.asarray(layer, i32).reshape(1), cols(k), cols(q),
          lanes(v), per_head(alpha), per_head(beta), store)
    return o.reshape(b, h, dv), store


def gdn_decode_step(q, k, v, alpha, beta, store, slots, layer, *, g,
                    interpret=None):
    """One position of one layer for ``B`` rows, the state written where
    it lies (``input_output_aliases``): q, k [B, H, d_k], v [B, H, d_v],
    alpha, beta [B, H], float32; ``store`` the stacked, packed store (see
    the module's head); ``slots`` [B] each row's slot, the last slot for
    padding; ``layer`` an int or a traced scalar -> (o [B, H, d_v], the
    store). A grid step is one row x as many lane rows of heads as
    :data:`_BLOCK_BYTES` holds; rows on the trash slot are skipped and
    name the block before them, so they cost no transfer."""
    from . import use_interpret

    return _call(q, k, v, alpha, beta, store, slots, layer, g=g,
                 interpret=use_interpret() if interpret is None
                 else bool(interpret))
