"""Pallas kernel numerics vs pure-XLA references (interpret mode on CPU).

Mirrors the reference's OpTest pattern (test/legacy_test/op_test.py:418):
forward outputs and analytic gradients are checked against an independent
reference implementation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _ref_sdpa(q, k, v, causal):
    d = q.shape[-1]
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh / np.sqrt(d), kh)
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((s, t), bool)), logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 128, 2, 64), (1, 256, 4, 32)])
def test_flash_attention_forward(shape, causal):
    from paddle_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(0)
    b, s, h, d = shape
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    ref = _ref_sdpa(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # interpret-mode kernel grads; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    from paddle_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(1)
    b, s, h, d = 1, 128, 2, 32
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    def loss_fl(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, causal=causal)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(_ref_sdpa(q, k, v, causal)))

    g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)


def test_flash_attention_gqa():
    from paddle_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 128, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    ref = _ref_sdpa(q, kr, vr, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    from paddle_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = _ref_sdpa(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=5e-2, rtol=5e-2
    )


def test_rms_norm_forward_and_grad():
    from paddle_tpu.ops.pallas import rms_norm

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(4, 16, 256), jnp.float32)
    w = jnp.asarray(rng.randn(256), jnp.float32)

    def ref(x, w):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * w

    out = rms_norm(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, w)),
                               atol=1e-5, rtol=1e-5)

    g = jax.grad(lambda x, w: jnp.sum(jnp.sin(rms_norm(x, w))), argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w: jnp.sum(jnp.sin(ref(x, w))), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gr[0]), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gr[1]), atol=1e-5, rtol=1e-4)


def test_functional_flash_attention_uses_pallas_path():
    # the nn.functional entry must import the pallas module without error
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    x = paddle.randn([2, 128, 2, 32])
    out, _ = F.flash_attention(x, x, x, causal=True)
    assert tuple(out.shape) == (2, 128, 2, 32)


def test_sdpa_arrays_kernel_error_propagates(monkeypatch):
    """The flash-vs-XLA choice is `_use_pallas(shape)` and nothing else:
    an error from the kernel surfaces from sdpa_arrays, it never selects
    the XLA path."""
    import importlib

    import paddle_tpu.ops.pallas as pallas

    # (the package re-exports a function of the same name)
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")

    class KernelRefused(RuntimeError):
        pass

    def refuse(*a, **kw):
        raise KernelRefused("mosaic says no")

    monkeypatch.setattr(fa, "_use_pallas", lambda shape: True)
    monkeypatch.setattr(pallas, "flash_attention", refuse)
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    with pytest.raises(KernelRefused):
        fa.sdpa_arrays(q, q, q, causal=True)


def test_flash_attention_causal_decode_offset():
    # sq != sk: queries align to the END of the key sequence (kv-cache decode)
    from paddle_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 8, 2, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True)

    d = q.shape[-1]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh / np.sqrt(d), kh)
    s, t = logits.shape[-2], logits.shape[-1]
    logits = jnp.where(jnp.tril(jnp.ones((s, t), bool), t - s), logits, -jnp.inf)
    ref = jnp.swapaxes(
        jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(logits, -1), vh), 1, 2
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # interpret-mode kernel grads; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
def test_flash_attention_gqa_grads():
    from paddle_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 64, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)

    g = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(
            _ref_sdpa(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gr[0]), atol=5e-4, rtol=5e-4)
    # dk/dv from the repeat-reference sum over the shared q heads already
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gr[1]), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(g[2]), np.asarray(gr[2]), atol=5e-4, rtol=5e-4)


def test_fused_rms_norm_residual_tuple_contract():
    # reference returns (out, residual_out) when residual is passed
    # (incubate/nn/functional/fused_rms_norm.py:59 overloads)
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import functional as FF

    rng = np.random.RandomState(8)
    x = paddle.to_tensor(rng.randn(8, 32).astype(np.float32))
    res = paddle.to_tensor(rng.randn(8, 32).astype(np.float32))
    w = paddle.to_tensor(np.ones(32, np.float32))

    out_only = FF.fused_rms_norm(x, w)
    assert not isinstance(out_only, (tuple, list))

    out, res_out = FF.fused_rms_norm(x, w, residual=res)
    np.testing.assert_allclose(
        res_out.numpy(), x.numpy() + res.numpy(), atol=1e-6)
    ref = FF.fused_rms_norm(paddle.to_tensor(res_out.numpy()), w)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), atol=1e-6)

    out_ln, res_ln = FF.fused_layer_norm(x, w, None, residual=res)
    np.testing.assert_allclose(
        res_ln.numpy(), x.numpy() + res.numpy(), atol=1e-6)


@pytest.mark.slow  # interpret-mode kernel grads; tier-1 time budget (ISSUE 4): ~1110s suite vs 870s timeout
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq,block", [(256, None), (1024, 128)])
def test_flash_fused_bwd_matches_split(causal, seq, block, monkeypatch):
    """PTPU_FA_FUSED_BWD=1: the single-pass dq+dk+dv kernel must match
    the split kernels (forced =0). The (1024, block 128) case drives the
    MULTI-BLOCK machinery — cross-ki dq-scratch accumulation, dynamic
    row0 slicing, final-step flush, causal clamp — with nq=nk=8; the
    256 case covers the full-sequence-block degenerate."""
    from paddle_tpu.ops.pallas import flash_attention

    if block is not None:
        monkeypatch.setenv("PTPU_FA_BWD_BLOCK", str(block))
        monkeypatch.setenv("PTPU_FA_BWD_KBLOCK", str(block))
    rng = np.random.default_rng(0)
    for hq, hk in ((4, 4), (4, 2)):
        q = jnp.asarray(rng.normal(size=(1, seq, hq, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, seq, hk, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, seq, hk, 16)), jnp.float32)

        def loss(q_, k_, v_):
            return jnp.sum(jnp.sin(flash_attention(
                q_, k_, v_, causal=causal, interpret=True)))

        monkeypatch.setenv("PTPU_FA_FUSED_BWD", "0")  # force SPLIT
        g_split = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setenv("PTPU_FA_FUSED_BWD", "1")  # force FUSED
        g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.delenv("PTPU_FA_FUSED_BWD", raising=False)
        for a, b in zip(g_fused, g_split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
