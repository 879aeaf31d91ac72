"""Compile the main path's Pallas kernels for a described TPU v5e (2x2).

Nothing runs: XLA's TPU compiler builds each kernel for a chip that is
described, not attached, so a kernel the Mosaic compiler refuses (an
unaligned block, too much VMEM) fails here in seconds instead of on the
chip. Shapes are the real ones:

  * the OTA kernel vmapped over seeds inside a scan, as the Monte Carlo
    engine calls it on the `large_chunked` workload (32 seeds, N=4096,
    d=24) and at the paper's Fig. 3 point (N=500, d=90);
  * the OTA kernel at a transport tile of repro-100m training (8 nodes,
    262144 columns, bf16 in, f32 out);
  * the attention kernel at repro-100m widths (batch 8 x 10 heads, seq 256,
    head_dim 64, f32), forward and the custom-VJP backward.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and the
test workers each import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention.ops import multi_head_attention
from repro.kernels.ota.ops import ota_edge_aggregate


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ota(g, h, w):
    return ota_edge_aggregate(g, h, w, noise_scale=1.0, impl="pallas",
                              out_dtype=jnp.float32)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seeds,n,d", [(32, 4096, 24), (32, 500, 90)])
def test_ota_kernel_as_the_engine_calls_it(one_chip, seeds, n, d):
    """vmap(seeds) of the kernel inside a scan over steps."""
    def sweep(g, h, w):
        def body(theta, xs):
            v = jax.vmap(_ota)(g * theta, xs[0], xs[1])
            return theta - 0.01 * jnp.mean(v), None
        return jax.lax.scan(body, jnp.float32(1.0), (h, w))[0]

    steps = 3
    compiled = jax.jit(sweep).lower(
        _spec((seeds, n, d), jnp.float32, one_chip),
        _spec((steps, seeds, n), jnp.float32, one_chip),
        _spec((steps, seeds, d), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)


def test_ota_kernel_at_the_transport_tile(one_chip):
    n, d = 8, 262144
    compiled = jax.jit(_ota).lower(
        _spec((n, d), jnp.bfloat16, one_chip),
        _spec((n,), jnp.float32, one_chip),
        _spec((d,), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)
    assert compiled.out_info.dtype == jnp.float32


@pytest.mark.parametrize("backward", [False, True])
def test_attention_kernel_at_repro_100m_widths(one_chip, backward):
    shape = (8, 10, 256, 64)

    def fwd(q, k, v):
        return multi_head_attention(q, k, v, scale=64**-0.5, impl="pallas")

    fn = fwd
    if backward:
        fn = jax.grad(lambda q, k, v: jnp.sum(fwd(q, k, v) ** 2),
                      argnums=(0, 1, 2))
    specs = [_spec(shape, jnp.float32, one_chip)] * 3
    _assert_kernel(jax.jit(fn).lower(*specs).compile())
