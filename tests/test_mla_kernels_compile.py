"""The latent-attention kernels at the served widths (128 heads over one
640-lane row a token, rank 512, pages of 64), compiled by the TPU's own
compiler for a DESCRIBED v5e: what Mosaic refuses (a slice off the
tiling, too much VMEM) shows here at no chip time. Nothing runs; the
topology is described inside a fixture, in this one file (the
on-chip-measurement guide, section 2)."""
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
