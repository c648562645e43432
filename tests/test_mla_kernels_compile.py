"""The paged attention kernels at the served widths, compiled by the TPU's
own compiler for a DESCRIBED v5e: what Mosaic refuses (a slice off the
tiling, too much VMEM) shows here at no chip time. The latent kernels
(128 heads over one 640-lane row a token, rank 512, pages of 64) and the
dense walk, exact and int8 (32 rows, 16 query heads over 8 kv heads x 128,
832 pages of 64 in 24 layers, 32 table columns; and a 32-head MHA pool).
Nothing runs; the topology is described inside a fixture, in this one file
(the on-chip-measurement guide, section 2: a second such file could go to
another worker, which cannot load the compiler too)."""
import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


POOL = (5, 1, 2305, 64, 640)


def _shape(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_decode_kernel_compiles_at_the_served_widths(one_chip,
                                                     no_compile_cache):
    from paddle_tpu.ops.pallas.decode_attention import mla_paged_attention

    fn = jax.jit(lambda q, pool, t, l, li: mla_paged_attention(
        q, pool, t, l, layer=li[0], rank=512, scale=0.1, interpret=False))
    text = fn.lower(
        _shape(one_chip, (64, 128, 640)), _shape(one_chip, POOL),
        _shape(one_chip, (64, 36), jnp.int32),
        _shape(one_chip, (64,), jnp.int32),
        _shape(one_chip, (1,), jnp.int32)).compile().as_text()
    assert "mla_paged_attention" in text and "tpu_custom_call" in text


def test_prefill_kernel_compiles_at_the_served_widths(one_chip,
                                                      no_compile_cache):
    from paddle_tpu.ops.pallas.decode_attention import mla_paged_prefill

    fn = jax.jit(lambda q, pool, t, p, n, li: mla_paged_prefill(
        q, pool, t, p, n, layer=li[0], rank=512, scale=0.1, chunk=128,
        interpret=False))
    text = fn.lower(
        _shape(one_chip, (16, 128 * 128, 640)), _shape(one_chip, POOL),
        _shape(one_chip, (16, 36), jnp.int32),
        _shape(one_chip, (16,), jnp.int32),
        _shape(one_chip, (16,), jnp.int32),
        _shape(one_chip, (1,), jnp.int32)).compile().as_text()
    assert "mla_paged_prefill" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("pool,hq,hkv,layers,group", [
    ("exact", 16, 8, 24, 8),    # InternLM2-1.8B as served: 4 MB a step
    ("exact", 32, 8, 24, 8),    # Mistral-7B's heads: rep 4
    ("exact", 32, 32, 4, 2),    # MHA: a page is 512 KB
    ("int8", 16, 8, 24, 4),     # a page is float32 once dequantized
    ("int8", 32, 32, 4, 1),
])
def test_dense_walk_compiles_at_the_served_widths(one_chip, no_compile_cache,
                                                  pool, hq, hkv, layers,
                                                  group):
    from paddle_tpu.ops.pallas import decode_attention as da

    b, pages, page, d, pps = 32, 832, 64, 128, 32
    codes = (layers, hkv, pages, page, d)
    if pool == "exact":
        kernel, name, per_page = da.paged_attention, "paged_attention", 1
        pools = [_shape(one_chip, codes)] * 2
    else:
        kernel, name, per_page = (da.paged_attention_int8,
                                  "paged_attention_int8", 2)
        pools = [_shape(one_chip, codes, jnp.int8),
                 _shape(one_chip, codes[:-1] + (1,), jnp.float32)] * 2
    def walk(q, t, l, li, *pools):
        return kernel(q, *pools, t, l, layer=li[0], interpret=False)

    args = (_shape(one_chip, (b, hq, d)),
            _shape(one_chip, (b, pps), jnp.int32),
            _shape(one_chip, (b,), jnp.int32),
            _shape(one_chip, (1,), jnp.int32), *pools)
    # the rule's pick, read off the call: three prefetched scalars, the
    # queries, then a step's pages of K and of V
    def pallas_calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from pallas_calls(sub)

    call, = pallas_calls(jax.make_jaxpr(walk)(*args).jaxpr)
    assert len(call.invars) == 4 + 2 * group * per_page
    text = jax.jit(walk).lower(*args).compile().as_text()
    assert name in text and "tpu_custom_call" in text
