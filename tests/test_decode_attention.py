"""Pallas decode/paged attention kernels vs jnp reference.

Parity slot: fusion/gpu masked_multihead_attention (dense cache decode) and
block_multi_head_attention (paged KV). Runs in interpret mode on the CPU
mesh; the same kernels compile on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.decode_attention import (
    decode_attention,
    paged_attention,
)


def ref_decode(q, k, v, lengths, scale=None):
    """[B,Hq,D] x [B,Hkv,S,D] masked softmax reference in f32."""
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    rep = hq // hkv
    kf = jnp.repeat(k, rep, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, rep, axis=1).astype(jnp.float32)
    scale = scale or 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32) * scale, kf)
    valid = jnp.arange(s)[None, None, :] < lengths[:, None, None]
    probs = jax.nn.softmax(jnp.where(valid, logits, -1e30), -1)
    return jnp.einsum("bht,bhtd->bhd", probs, vf).astype(q.dtype)


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), dtype)


class TestDecodeAttention:
    @pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (4, 1)])
    def test_matches_reference_gqa(self, hq, hkv):
        b, s, d = 2, 1024, 128
        q = _rand((b, hq, d))
        k = _rand((b, hkv, s, d), seed=1)
        v = _rand((b, hkv, s, d), seed=2)
        lengths = jnp.array([1000, 321], jnp.int32)
        out = decode_attention(q, k, v, lengths)
        ref = ref_decode(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_realistic_kv_length_8k(self):
        b, hq, hkv, s, d = 1, 8, 2, 8192, 128
        q = _rand((b, hq, d))
        k = _rand((b, hkv, s, d), seed=1)
        v = _rand((b, hkv, s, d), seed=2)
        lengths = jnp.array([7531], jnp.int32)
        out = decode_attention(q, k, v, lengths)
        ref = ref_decode(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    def test_length_one_and_full(self):
        b, h, s, d = 2, 4, 256, 64
        q = _rand((b, h, d))
        k = _rand((b, h, s, d), seed=1)
        v = _rand((b, h, s, d), seed=2)
        lengths = jnp.array([1, s], jnp.int32)
        out = decode_attention(q, k, v, lengths)
        ref = ref_decode(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bfloat16(self):
        b, h, s, d = 2, 4, 512, 128
        q = _rand((b, h, d), jnp.bfloat16)
        k = _rand((b, h, s, d), jnp.bfloat16, seed=1)
        v = _rand((b, h, s, d), jnp.bfloat16, seed=2)
        lengths = jnp.array([400, 512], jnp.int32)
        out = decode_attention(q, k, v, lengths)
        ref = ref_decode(q, k, v, lengths)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=3e-2, atol=3e-2)


class TestBlockMultiheadAttention:
    """incubate.nn.functional.block_multihead_attention: prefill writes the
    paged cache, decode steps run the pallas paged kernel; both must match
    dense causal attention."""

    def _dense_causal(self, q, k, v):
        # q,k,v [T, H, D] -> [T, H*D]
        t, h, d = q.shape
        logits = jnp.einsum("thd,xhd->htx", q / np.sqrt(d), k)
        mask = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -1e30), -1)
        return jnp.einsum("htx,xhd->thd", probs, v).reshape(t, h * d)

    def test_prefill_then_decode_matches_dense(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import functional as FF

        h, d, bsz, blocks_per_seq = 4, 64, 64, 4
        b = 1
        prefill_len, decode_steps = 100, 3
        total = prefill_len + decode_steps
        rng = np.random.default_rng(0)
        all_qkv = rng.standard_normal((total, 3 * h * d)).astype(np.float32)

        kc = paddle.to_tensor(np.zeros((8, h, bsz, d), np.float32))
        vc = paddle.to_tensor(np.zeros((8, h, bsz, d), np.float32))
        tables = paddle.to_tensor(
            np.array([[5, 2, 7, 0]], np.int32))  # scattered pages

        def _lens(e, dd, tt):
            return (paddle.to_tensor(np.array([[e]], np.int32)),
                    paddle.to_tensor(np.array([[dd]], np.int32)),
                    paddle.to_tensor(np.array([[tt]], np.int32)))

        # prefill
        enc, dec, this = _lens(prefill_len, 0, prefill_len)
        out_p, _, kc, vc = FF.block_multihead_attention(
            paddle.to_tensor(all_qkv[:prefill_len]), kc, vc, enc, dec, this,
            None, None, None, None, tables, block_size=bsz)
        # decode steps
        outs = [np.asarray(out_p.numpy())]
        for step in range(decode_steps):
            cur = prefill_len + step
            enc, dec, this = _lens(0, cur, 1)
            out_d, _, kc, vc = FF.block_multihead_attention(
                paddle.to_tensor(all_qkv[cur:cur + 1]), kc, vc, enc, dec,
                this, None, None, None, None, tables, block_size=bsz)
            outs.append(np.asarray(out_d.numpy()))
        got = np.concatenate(outs, axis=0)

        flat = jnp.asarray(all_qkv).reshape(total, 3, h, d)
        want = self._dense_causal(flat[:, 0], flat[:, 1], flat[:, 2])
        np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4,
                                   atol=2e-4)

    def test_gqa_decode(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import functional as FF

        hq, hkv, d, bsz = 8, 2, 64, 64
        width = (hq + 2 * hkv) * d
        rng = np.random.default_rng(1)
        kc = paddle.to_tensor(
            rng.standard_normal((4, hkv, bsz, d)).astype(np.float32))
        vc = paddle.to_tensor(
            rng.standard_normal((4, hkv, bsz, d)).astype(np.float32))
        tables = paddle.to_tensor(np.array([[1, 3]], np.int32))
        cached = 50
        qkv = paddle.to_tensor(
            rng.standard_normal((1, width)).astype(np.float32))
        enc = paddle.to_tensor(np.array([[0]], np.int32))
        dec = paddle.to_tensor(np.array([[cached]], np.int32))
        this = paddle.to_tensor(np.array([[1]], np.int32))
        out, _, kc2, vc2 = FF.block_multihead_attention(
            qkv, kc, vc, enc, dec, this, None, None, None, None, tables,
            block_size=bsz)
        assert out.shape == [1, hq * d]
        # reference: dense over the first `cached+1` positions of the
        # sequence's pages (page 1 then 3), with the new k/v written in
        flat = np.asarray(qkv.numpy()).reshape(hq + 2 * hkv, d)
        q = jnp.asarray(flat[:hq])[None]                     # [1, hq, d]
        kd = jnp.concatenate([np.asarray(kc2.numpy())[1],
                              np.asarray(kc2.numpy())[3]], axis=1)[None]
        vd = jnp.concatenate([np.asarray(vc2.numpy())[1],
                              np.asarray(vc2.numpy())[3]], axis=1)[None]
        ref = ref_decode(q, kd, vd, jnp.array([cached + 1], jnp.int32))
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   np.asarray(ref).reshape(1, hq * d),
                                   rtol=2e-4, atol=2e-4)


class TestBlockMHAServingEdges:
    def _setup(self, b=2, h=2, d=64, bsz=64, pages=3):
        import paddle_tpu as paddle

        rng = np.random.default_rng(3)
        kc = paddle.to_tensor(
            rng.standard_normal((8, h, bsz, d)).astype(np.float32))
        vc = paddle.to_tensor(
            rng.standard_normal((8, h, bsz, d)).astype(np.float32))
        tables = paddle.to_tensor(
            rng.permutation(8)[: b * pages].reshape(b, pages).astype(np.int32))
        return paddle, kc, vc, tables

    def test_finished_slot_keeps_pallas_batch(self):
        """A finished slot (seq_lens_this_time == 0) is excluded; live rows
        still decode through the kernel and output has only live rows."""
        from paddle_tpu.incubate.nn import functional as FF

        paddle, kc, vc, tables = self._setup(b=2)
        h, d = 2, 64
        rng = np.random.default_rng(4)
        qkv = paddle.to_tensor(
            rng.standard_normal((1, 3 * h * d)).astype(np.float32))  # 1 live row
        enc = paddle.to_tensor(np.array([[0], [0]], np.int32))
        dec = paddle.to_tensor(np.array([[40], [90]], np.int32))
        this = paddle.to_tensor(np.array([[0], [1]], np.int32))  # slot 0 done
        out, _, kc2, vc2 = FF.block_multihead_attention(
            qkv, kc, vc, enc, dec, this, None, None, None, None, tables,
            block_size=64)
        assert out.shape == [1, h * d]
        # reference for the live slot (index 1)
        flat = np.asarray(qkv.numpy()).reshape(h * 3, d)
        q = jnp.asarray(flat[:h])[None]
        t1 = np.asarray(tables.numpy())[1]
        kd = jnp.concatenate([np.asarray(kc2.numpy())[p] for p in t1], 1)[None]
        vd = jnp.concatenate([np.asarray(vc2.numpy())[p] for p in t1], 1)[None]
        ref = ref_decode(q, kd, vd, jnp.array([91], jnp.int32))
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   np.asarray(ref).reshape(1, -1),
                                   rtol=2e-4, atol=2e-4)

    def test_rope_table_values_are_used(self):
        """A scaled rope table must change the output vs the default table
        (the kernel must read the table, not recompute theta-10000)."""
        from paddle_tpu.incubate.nn import functional as FF

        paddle, kc, vc, tables = self._setup(b=1)
        h, d, max_seq = 2, 64, 192
        rng = np.random.default_rng(5)
        qkv_np = rng.standard_normal((1, 3 * h * d)).astype(np.float32)
        enc = paddle.to_tensor(np.array([[0]], np.int32))
        dec = paddle.to_tensor(np.array([[50]], np.int32))
        this = paddle.to_tensor(np.array([[1]], np.int32))

        def table(scale):
            pos = np.arange(max_seq, dtype=np.float32) / scale
            inv = 10000.0 ** (-np.arange(0, d, 2, dtype=np.float32) / d)
            f = np.outer(pos, inv)
            t = np.stack([np.cos(f), np.sin(f)])  # [2, max_seq, d/2]
            return paddle.to_tensor(
                t.reshape(2, 1, max_seq, 1, d // 2).astype(np.float32))

        outs = []
        for scale in (1.0, 4.0):
            o, _, _, _ = FF.block_multihead_attention(
                paddle.to_tensor(qkv_np), kc, vc, enc, dec, this,
                None, None, None, None, tables, rope_emb=table(scale),
                block_size=64)
            outs.append(np.asarray(o.numpy()))
        assert not np.allclose(outs[0], outs[1])  # scaling reached the math

    def test_quantization_raises_loudly(self):
        from paddle_tpu.incubate.nn import functional as FF

        paddle, kc, vc, tables = self._setup(b=1)
        with pytest.raises(NotImplementedError):
            FF.block_multihead_attention(
                paddle.to_tensor(np.zeros((1, 3 * 2 * 64), np.float32)),
                kc, vc,
                paddle.to_tensor(np.array([[0]], np.int32)),
                paddle.to_tensor(np.array([[1]], np.int32)),
                paddle.to_tensor(np.array([[1]], np.int32)),
                None, None, None, None, tables,
                cache_k_quant_scales=paddle.to_tensor(
                    np.ones((2,), np.float32)))


class TestPagedAttention:
    def _paged_setup(self, b, hq, hkv, d, page, pages_per_seq, lengths,
                     seed=0):
        """Build a paged cache + the equivalent dense cache."""
        s = page * pages_per_seq
        num_pages = b * pages_per_seq + 3  # a few spare pages
        k_pages = _rand((hkv, num_pages, page, d), seed=seed + 1)
        v_pages = _rand((hkv, num_pages, page, d), seed=seed + 2)
        # each sequence owns a scattered set of pages
        rng = np.random.default_rng(seed + 3)
        tables = rng.permutation(num_pages)[: b * pages_per_seq]
        tables = jnp.asarray(tables.reshape(b, pages_per_seq), jnp.int32)
        # dense view: gather pages per sequence
        k_dense = jnp.stack([
            jnp.concatenate([k_pages[:, tables[i, p]] for p in
                             range(pages_per_seq)], axis=1)
            for i in range(b)])  # [B, Hkv, S, D]
        v_dense = jnp.stack([
            jnp.concatenate([v_pages[:, tables[i, p]] for p in
                             range(pages_per_seq)], axis=1)
            for i in range(b)])
        return k_pages, v_pages, tables, k_dense, v_dense, s

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
    def test_matches_dense_reference(self, hq, hkv):
        b, d, page, pps = 2, 128, 64, 8
        lengths = jnp.array([500, 129], jnp.int32)
        k_pages, v_pages, tables, k_dense, v_dense, s = self._paged_setup(
            b, hq, hkv, d, page, pps, lengths)
        q = _rand((b, hq, d))
        out = paged_attention(q, k_pages, v_pages, tables, lengths)
        ref = ref_decode(q, k_dense, v_dense, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow  # 4k-page soak; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
    def test_long_context_4k_pages(self):
        b, hq, hkv, d, page, pps = 1, 8, 8, 128, 128, 32  # 4096 ctx
        lengths = jnp.array([4000], jnp.int32)
        k_pages, v_pages, tables, k_dense, v_dense, s = self._paged_setup(
            b, hq, hkv, d, page, pps, lengths, seed=7)
        q = _rand((b, hq, d), seed=9)
        out = paged_attention(q, k_pages, v_pages, tables, lengths)
        ref = ref_decode(q, k_dense, v_dense, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    def test_garbage_table_entries_beyond_length_ignored(self):
        b, hq, hkv, d, page, pps = 1, 4, 4, 64, 64, 4
        lengths = jnp.array([64], jnp.int32)  # only first page valid
        k_pages, v_pages, tables, k_dense, v_dense, s = self._paged_setup(
            b, hq, hkv, d, page, pps, lengths)
        # poison the unused table entries with out-of-range page ids
        poisoned = tables.at[0, 2:].set(10**6)
        q = _rand((b, hq, d))
        out = paged_attention(q, k_pages, v_pages, poisoned, lengths)
        ref = ref_decode(q, k_dense, v_dense, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestPagedAttentionInt8:
    """int8-page variant (ISSUE 13 satellite, docs/SERVING.md): pages
    stored as (codes, scales) dequantize INSIDE the kernel — the
    serving ``int8_kv=True`` mode stops gathering+dequantizing in HBM."""

    def _int8_setup(self, b, hkv, d, page, pps, seed=3):
        from paddle_tpu.memory import quantize_rows_int8

        num_pages = 2 * pps
        k = _rand((hkv, num_pages, page, d), seed=seed)
        v = _rand((hkv, num_pages, page, d), seed=seed + 1)
        kq, ks = quantize_rows_int8(k)
        vq, vs = quantize_rows_int8(v)
        tables = jnp.asarray(
            np.random.default_rng(seed).choice(
                num_pages, (b, pps), replace=True).astype(np.int32))
        return (kq, ks, vq, vs, tables,
                kq.astype(jnp.float32) * ks, vq.astype(jnp.float32) * vs)

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
    def test_bitwise_vs_dequant_then_exact_kernel(self, hq, hkv):
        """The in-kernel dequant must be BITWISE the gather+dequant
        path feeding the exact kernel: both compute codes * scales in
        f32 and then the same online-softmax math."""
        from paddle_tpu.ops.pallas.decode_attention import (
            paged_attention_int8)

        b, d, page, pps = 2, 64, 8, 4
        kq, ks, vq, vs, tables, kd, vd = self._int8_setup(
            b, hkv, d, page, pps)
        q = _rand((b, hq, d), seed=11)
        lengths = jnp.array([29, 32], jnp.int32)
        out = paged_attention_int8(q, kq, ks, vq, vs, tables, lengths,
                                   interpret=True)
        ref = paged_attention(q, kd, vd, tables, lengths, interpret=True)
        a, r = np.asarray(out), np.asarray(ref)
        assert a.tobytes() == r.tobytes(), float(np.abs(a - r).max())

    @pytest.mark.parametrize("li", [0, 1, 2])
    def test_serving_paged_attend_kernel_vs_gather_path(self, monkeypatch,
                                                        li):
        """The dense decoder's int8 `paged_attend` with the kernel forced
        (PTPU_PAGED_INT8_KERNEL=interpret) matches the default HBM
        gather+dequant reference path on the same (codes, scales), at
        every layer of a stacked pool."""
        from paddle_tpu.inference.serving import (
            DenseDecoderServing, _int8_paged_kernel_active)

        assert not _int8_paged_kernel_active()  # CPU default: off
        monkeypatch.setenv("PTPU_PAGED_INT8_KERNEL", "interpret")
        assert _int8_paged_kernel_active()
        monkeypatch.setenv("PTPU_PAGED_INT8_KERNEL", "0")
        assert not _int8_paged_kernel_active()

        # drive the model kind's method directly on a synthetic cache
        from paddle_tpu.memory import quantize_rows_int8

        class _Shim:
            hkv, page, pages_per_seq = 2, 8, 4
            _paged_attend = DenseDecoderServing.paged_attend

        shim = _Shim()
        b, hq, d = 2, 4, 64
        num_pages = 8
        k = _rand((3, shim.hkv, num_pages, shim.page, d), seed=21)
        v = _rand((3, shim.hkv, num_pages, shim.page, d), seed=22)
        kq, ks = quantize_rows_int8(k)
        vq, vs = quantize_rows_int8(v)
        tables = jnp.asarray(np.random.default_rng(5).choice(
            num_pages, (b, shim.pages_per_seq)).astype(np.int32))
        lens = jnp.array([13, 30], jnp.int32)
        q = _rand((b, hq, d), seed=23)
        ref = shim._paged_attend(q, (kq, ks), (vq, vs), li, tables, lens)
        monkeypatch.setenv("PTPU_PAGED_INT8_KERNEL", "interpret")
        out = shim._paged_attend(q, (kq, ks), (vq, vs), li, tables, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestStackedPool:
    """The serving programs hand the kernels the WHOLE stacked pool
    [L, Hkv, P, page, D] and a layer (ISSUE 28): the pages are read
    where they lie, by (layer, page). The stacked call must be bitwise
    the four-dimensional call on that layer's pages, for a python-int
    layer (the unrolled walk) and a traced one (the scan's counter)."""

    L, B, HQ, HKV, D, PAGE, PPS = 3, 2, 4, 2, 64, 8, 4

    def _operands(self):
        num_pages = 2 * self.PPS
        shape = (self.L, self.HKV, num_pages, self.PAGE, self.D)
        k, v = _rand(shape, seed=31), _rand(shape, seed=32)
        tables = jnp.asarray(np.random.default_rng(33).choice(
            num_pages, (self.B, self.PPS)).astype(np.int32))
        lens = jnp.array([13, 30], jnp.int32)
        return _rand((self.B, self.HQ, self.D), seed=34), k, v, tables, lens

    def _kernel_and_pools(self, pool, k, v):
        from paddle_tpu.memory import quantize_rows_int8
        from paddle_tpu.ops.pallas.decode_attention import (
            paged_attention_int8)

        if pool == "exact":
            return paged_attention, (k, v)
        return paged_attention_int8, (*quantize_rows_int8(k),
                                      *quantize_rows_int8(v))

    @pytest.mark.parametrize("traced", [False, True],
                             ids=["int-layer", "traced-layer"])
    @pytest.mark.parametrize("li", [0, 1, 2])
    @pytest.mark.parametrize("pool", ["exact", "int8"])
    def test_stacked_equals_per_layer_bitwise(self, pool, li, traced):
        q, k, v, tables, lens = self._operands()
        kernel, pools = self._kernel_and_pools(pool, k, v)

        def stacked(layer):
            return kernel(q, *pools, tables, lens, layer=layer,
                          interpret=True)

        out = (jax.jit(stacked)(jnp.int32(li)) if traced else stacked(li))
        ref = kernel(q, *(p[li] for p in pools), tables, lens,
                     interpret=True)
        assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()

    @pytest.mark.parametrize("pool", ["exact", "int8"])
    def test_layer_and_rank_must_agree(self, pool):
        """A stacked pool without a layer, or one layer's pages with
        one, is a caller's mistake, not a default."""
        q, k, v, tables, lens = self._operands()
        kernel, pools = self._kernel_and_pools(pool, k, v)
        with pytest.raises(ValueError, match="needs layer="):
            kernel(q, *pools, tables, lens, interpret=True)
        with pytest.raises(ValueError, match="have none"):
            kernel(q, *(p[0] for p in pools), tables, lens, layer=0,
                   interpret=True)


class TestPagedWalk:
    """The dense walk's grid (ISSUE 31): one row x a group of pages x
    every kv head a step, pages past a row's length neither fetched nor
    computed. Exact and int8 pools against the dense float32 reference,
    at the edges of a page, of a group and of the table."""

    L, PAGE, D = 2, 8, 64

    def _pools(self, pool, hkv, num_pages, page=None, d=None, seed=40):
        from paddle_tpu.memory import quantize_rows_int8
        from paddle_tpu.ops.pallas.decode_attention import (
            paged_attention_int8)

        shape = (self.L, hkv, num_pages, page or self.PAGE, d or self.D)
        k, v = _rand(shape, seed=seed), _rand(shape, seed=seed + 1)
        if pool == "exact":
            return paged_attention, (k, v), k, v
        kq, ks = quantize_rows_int8(k)
        vq, vs = quantize_rows_int8(v)
        return (paged_attention_int8, (kq, ks, vq, vs),
                kq.astype(jnp.float32) * ks, vq.astype(jnp.float32) * vs)

    @staticmethod
    def _ref(q, k, v, tables, lengths):
        """One layer's [Hkv, P, page, D] float32 pages gathered dense;
        a row of length 0 reads zeros."""
        b = q.shape[0]
        hkv, num_pages, _, d = k.shape
        t = np.clip(np.asarray(tables), 0, num_pages - 1)
        kd = jnp.swapaxes(k[:, t].reshape(hkv, b, -1, d), 0, 1)
        vd = jnp.swapaxes(v[:, t].reshape(hkv, b, -1, d), 0, 1)
        out = ref_decode(q, kd, vd, lengths)
        return jnp.where(lengths[:, None, None] > 0, out, 0.0)

    def _check(self, kernel, pools, k, v, q, tables, lengths, li=1):
        out = kernel(q, *pools, tables, lengths, layer=li, interpret=True)
        ref = self._ref(q, k[li], v[li], tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("pool", ["exact", "int8"])
    @pytest.mark.parametrize("case", ["zero", "one", "page-1", "page",
                                      "page+1", "full-1", "full", "mixed"])
    def test_lengths_at_the_edges(self, case, pool):
        pps, hq, hkv = 6, 4, 2
        full = pps * self.PAGE
        lens = {"zero": [0, 0, 0], "one": [1, 1, 1],
                "page-1": [self.PAGE - 1] * 3, "page": [self.PAGE] * 3,
                "page+1": [self.PAGE + 1] * 3, "full-1": [full - 1] * 3,
                "full": [full] * 3,
                "mixed": [0, full, 1, self.PAGE + 1, 0, full - 1]}[case]
        b = len(lens)
        num_pages = b * pps + 2
        kernel, pools, k, v = self._pools(pool, hkv, num_pages)
        tables = jnp.asarray(np.random.default_rng(41).permutation(
            num_pages)[: b * pps].reshape(b, pps).astype(np.int32))
        self._check(kernel, pools, k, v, _rand((b, hq, self.D), seed=42),
                    tables, jnp.asarray(lens, jnp.int32))

    @pytest.mark.parametrize("pool", ["exact", "int8"])
    @pytest.mark.parametrize("pps", [5, 13])
    def test_table_not_a_multiple_of_the_group(self, pps, pool):
        """At 8 heads x 64 x 128 x float32 the rule takes 4 pages a
        step: 5 columns are 4 + 1, 13 are 3 x 4 + 1."""
        from paddle_tpu.ops.pallas.decode_attention import _pages_per_step

        b, hq, hkv, page, d = 2, 8, 8, 64, 128
        assert _pages_per_step(hkv, page, d, 4, pps) == 4
        num_pages = b * pps + 1
        kernel, pools, k, v = self._pools(pool, hkv, num_pages, page, d)
        tables = jnp.asarray(np.random.default_rng(43).permutation(
            num_pages)[: b * pps].reshape(b, pps).astype(np.int32))
        lens = jnp.asarray([pps * page - 3, 4 * page + 1], jnp.int32)
        self._check(kernel, pools, k, v, _rand((b, hq, d), seed=44),
                    tables, lens)

    @pytest.mark.parametrize("pool", ["exact", "int8"])
    @pytest.mark.parametrize("poison", [10**6, -7, 2**31 - 1])
    def test_garbage_table_entries_past_the_length(self, poison, pool):
        b, pps, hq, hkv = 3, 6, 4, 2
        num_pages = b * pps
        kernel, pools, k, v = self._pools(pool, hkv, num_pages)
        tables = np.random.default_rng(45).permutation(
            num_pages).reshape(b, pps).astype(np.int32)
        lens = np.asarray([self.PAGE, 0, 2 * self.PAGE + 3], np.int32)
        clean = jnp.asarray(tables)
        for i, n in enumerate(lens):
            tables[i, -(-n // self.PAGE):] = poison
        q = _rand((b, hq, self.D), seed=46)
        out = kernel(q, *pools, jnp.asarray(tables), jnp.asarray(lens),
                     layer=0, interpret=True)
        ref = self._ref(q, k[0], v[0], clean, jnp.asarray(lens))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("pool", ["exact", "int8"])
    @pytest.mark.parametrize("rep", [1, 2, 4, 8])
    def test_query_heads_a_kv_head(self, rep, pool):
        b, pps, hkv = 2, 4, 2
        num_pages = b * pps + 1
        kernel, pools, k, v = self._pools(pool, hkv, num_pages)
        tables = jnp.asarray(np.random.default_rng(47).permutation(
            num_pages)[: b * pps].reshape(b, pps).astype(np.int32))
        self._check(kernel, pools, k, v,
                    _rand((b, hkv * rep, self.D), seed=48), tables,
                    jnp.asarray([3 * self.PAGE + 5, 9], jnp.int32))

    @pytest.mark.parametrize("pool", ["exact", "int8"])
    @pytest.mark.parametrize("how", ["int", "scan", "four-dimensional"])
    def test_the_layer_named_three_ways(self, how, pool):
        b, pps, hq, hkv = 2, 4, 4, 2
        num_pages = b * pps
        kernel, pools, k, v = self._pools(pool, hkv, num_pages)
        tables = jnp.asarray(np.random.default_rng(49).permutation(
            num_pages).reshape(b, pps).astype(np.int32))
        lens = jnp.asarray([2 * self.PAGE, self.PAGE + 1], jnp.int32)
        q = _rand((b, hq, self.D), seed=50)
        if how == "scan":       # the serving walk: a traced layer counter
            _, outs = jax.lax.scan(
                lambda c, li: (c, kernel(q, *pools, tables, lens, layer=li,
                                         interpret=True)),
                0, jnp.arange(self.L, dtype=jnp.int32))
        elif how == "int":
            outs = [kernel(q, *pools, tables, lens, layer=li, interpret=True)
                    for li in range(self.L)]
        else:
            outs = [kernel(q, *(p[li] for p in pools), tables, lens,
                           interpret=True) for li in range(self.L)]
        for li in range(self.L):
            np.testing.assert_allclose(
                np.asarray(outs[li]),
                np.asarray(self._ref(q, k[li], v[li], tables, lens)),
                rtol=2e-5, atol=2e-5)

    def test_bfloat16_pool(self):
        b, pps, hq, hkv = 2, 4, 4, 2
        num_pages = b * pps
        shape = (self.L, hkv, num_pages, 16, 128)
        k = _rand(shape, jnp.bfloat16, seed=51)
        v = _rand(shape, jnp.bfloat16, seed=52)
        tables = jnp.asarray(np.random.default_rng(53).permutation(
            num_pages).reshape(b, pps).astype(np.int32))
        lens = jnp.asarray([61, 17], jnp.int32)
        q = _rand((b, hq, 128), jnp.bfloat16, seed=54)
        out = paged_attention(q, k, v, tables, lens, layer=1, interpret=True)
        ref = self._ref(q, k[1].astype(jnp.float32),
                        v[1].astype(jnp.float32), tables, lens)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=3e-2, atol=3e-2)

    @pytest.mark.parametrize("hkv,itemsize,pps,want", [
        (8, 2, 32, 8),      # the served pool: 128 KB a page, 4 MB a step
        (32, 2, 32, 2),     # a 32-head MHA pool
        (8, 4, 32, 4),      # float32 pages (and dequantized int8 ones)
        (8, 2, 5, 5),       # never more than the table has columns
        (64, 4, 32, 1),     # never less than one
    ])
    def test_pages_a_step_follow_the_shapes(self, hkv, itemsize, pps, want):
        from paddle_tpu.ops.pallas.decode_attention import _pages_per_step

        assert _pages_per_step(hkv, 64, 128, itemsize, pps) == want

    @pytest.mark.parametrize("group", [1, 3, 4])
    def test_fetch_table_names_no_new_page_past_a_length(self, group):
        """A live column names its page, clamped into the pool; a column
        past the length names what its operand named the grid step
        before (across rows), which is what makes the pipeline skip it."""
        from paddle_tpu.ops.pallas.decode_attention import _fetch_table

        page, num_pages, pps = 8, 50, 7
        lens = np.asarray([0, 17, 0, 56, 1, 0], np.int32)
        tables = np.random.default_rng(55).integers(
            -5, 80, (len(lens), pps)).astype(np.int32)
        got = np.asarray(_fetch_table(jnp.asarray(tables),
                                      jnp.asarray(lens), page, group,
                                      num_pages))
        steps = -(-pps // group)
        assert got.shape == (len(lens), steps * group)
        assert got.min() >= 0 and got.max() < num_pages
        flat = got.reshape(-1, group)
        for b, n in enumerate(lens):
            for c in range(steps * group):
                at, g = b * steps + c // group, c % group
                if c * page < n:
                    assert got[b, c] == np.clip(tables[b, c], 0,
                                                num_pages - 1)
                elif at:
                    assert got[b, c] == flat[at - 1, g], (b, c)
