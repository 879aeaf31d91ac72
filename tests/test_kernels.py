"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.ops import multi_head_attention
from repro.kernels.ota.ops import ota_edge_aggregate
from repro.kernels.ota.ref import ota_edge_aggregate_ref
from repro.kernels.wkv.ops import wkv6


# ---------------------------------------------------------------- OTA kernel
@pytest.mark.parametrize("n,d", [(128, 512), (256, 1024), (100, 300),
                                 (64, 128), (8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ota_kernel_matches_ref(n, d, dtype):
    k = jax.random.key(n * d)
    g = jax.random.normal(k, (n, d), dtype=dtype)
    h = jnp.abs(jax.random.normal(jax.random.fold_in(k, 1), (n,)))
    w = jax.random.normal(jax.random.fold_in(k, 2), (d,))
    ref = ota_edge_aggregate_ref(g, h, w, noise_scale=0.37)
    ker = ota_edge_aggregate(g, h, w, noise_scale=0.37, impl="pallas",
                             interpret=True)
    atol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.array(ker, np.float32),
                               np.array(ref, np.float32), atol=atol,
                               rtol=1e-2)


@pytest.mark.parametrize("n,d", [(5, 7), (1, 90), (130, 513), (200, 90)])
def test_ota_kernel_padding_path_odd_shapes(n, d):
    """Regression for the non-divisible (N, d) path: padded node rows carry
    zero gain and the kernel normalizes by the TRUE N, so both the
    superposition normalization and the edge-noise scale must come out
    exact — no residual (N+pad)/N factor on either term."""
    k = jax.random.key(n * 1000 + d)
    kg, kh, kw = jax.random.split(k, 3)
    g = jax.random.normal(kg, (n, d))
    h = jax.random.uniform(kh, (n,))
    w = jax.random.normal(kw, (d,))
    ref = ota_edge_aggregate_ref(g, h, w, noise_scale=0.37)
    ker = ota_edge_aggregate(g, h, w, noise_scale=0.37, impl="pallas",
                             interpret=True)
    np.testing.assert_allclose(np.array(ker), np.array(ref), atol=1e-6,
                               rtol=1e-5)
    # noise-only probe: zero gradients isolate the noise term, which must be
    # exactly noise_scale * w (the old wrapper rescaled it by (N+pad)/N and
    # subtracted the excess after an output-dtype round-trip)
    noise_only = ota_edge_aggregate(jnp.zeros_like(g), h, w,
                                    noise_scale=0.37, impl="pallas",
                                    interpret=True)
    np.testing.assert_allclose(np.array(noise_only), 0.37 * np.array(w),
                               atol=1e-7)


def test_ota_noise_scale_is_traced_one_compile():
    """Regression: `noise_scale` is a traced operand, so sweeping noise
    levels (or N, whose edge-noise std depends on it) at fixed shapes must
    compile the wrapper exactly once per (shape, impl) — not once per
    float value. Values must still track the operand exactly."""
    from repro.kernels.ota import ops as ota_ops

    if not ota_ops.clear_cache():
        pytest.skip("jit cache clearing unsupported on this JAX")
    k = jax.random.key(3)
    g = jax.random.normal(k, (8, 64))
    h = jnp.abs(jax.random.normal(jax.random.fold_in(k, 1), (8,)))
    w = jax.random.normal(jax.random.fold_in(k, 2), (64,))
    outs = [np.array(ota_edge_aggregate(g, h, w, noise_scale=s,
                                        impl="pallas", interpret=True))
            for s in (0.0, 0.1, 0.37, 2.5)]
    assert ota_ops.trace_count() == 1, "noise_scale retriggered compilation"
    base = outs[0]
    for s, out in zip((0.1, 0.37, 2.5), outs[1:]):
        np.testing.assert_allclose(out - base, s * np.array(w), atol=1e-6)
    # a python float and a traced scalar hit the same compiled program
    ota_edge_aggregate(g, h, w, noise_scale=jnp.float32(1.3), impl="pallas",
                       interpret=True)
    assert ota_ops.trace_count() == 1
    # ref impl is its own (impl,) cache entry, also traced-once
    ota_ops.clear_cache()
    for s in (0.2, 0.9):
        ota_edge_aggregate(g, h, w, noise_scale=s, impl="ref")
    assert ota_ops.trace_count() == 1


# ---------------------------------------------------------- attention kernel
@pytest.mark.parametrize("b,hq,hkv,s,d,kw", [
    (2, 4, 4, 256, 64, {}),
    (1, 8, 2, 256, 64, {}),                      # GQA
    (1, 4, 4, 384, 128, {"window": 100}),        # sliding window
    (1, 4, 4, 256, 64, {"softcap": 30.0}),       # gemma2 softcap
    (1, 2, 2, 200, 64, {}),                      # padding path
    (1, 2, 2, 256, 32, {"causal": False}),
    (1, 4, 4, 512, 256, {"window": 128, "softcap": 50.0}),
])
def test_attention_kernel_matches_ref(b, hq, hkv, s, d, kw):
    kw = dict(kw)
    kw.setdefault("causal", True)
    ks = jax.random.split(jax.random.key(s + d), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    ref = multi_head_attention(q, k, v, scale=d**-0.5, impl="ref", **kw)
    ker = multi_head_attention(q, k, v, scale=d**-0.5, impl="pallas",
                               interpret=True, **kw)
    np.testing.assert_allclose(np.array(ker), np.array(ref), atol=5e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("kw", [{}, {"window": 64}, {"softcap": 20.0}])
def test_attention_kernel_grad_matches_ref(kw):
    """The Pallas path's custom VJP (kernel forward + flash backward)
    gives the oracle's gradients, padding and GQA included."""
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (1, 4, 200, 64))
    k = jax.random.normal(ks[1], (1, 2, 200, 64))
    v = jax.random.normal(ks[2], (1, 2, 200, 64))
    w = jax.random.normal(ks[3], (1, 4, 200, 64))

    def loss(impl, **extra):
        return lambda q, k, v: jnp.sum(w * multi_head_attention(
            q, k, v, scale=0.125, impl=impl, **extra, **kw))

    ref = jax.grad(loss("ref"), (0, 1, 2))(q, k, v)
    ker = jax.grad(loss("pallas", interpret=True), (0, 1, 2))(q, k, v)
    for a, b in zip(ker, ref):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-5,
                                   rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_attention_kernel_bf16(dtype):
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), dtype=dtype)
    k = jax.random.normal(ks[1], (1, 4, 256, 64), dtype=dtype)
    v = jax.random.normal(ks[2], (1, 4, 256, 64), dtype=dtype)
    ref = multi_head_attention(q, k, v, scale=0.125, impl="ref")
    ker = multi_head_attention(q, k, v, scale=0.125, impl="pallas",
                               interpret=True)
    np.testing.assert_allclose(np.array(ker, np.float32),
                               np.array(ref, np.float32), atol=3e-2)


# ---------------------------------------------------------------- wkv kernel
@pytest.mark.parametrize("b,h,t,d", [(2, 2, 128, 64), (1, 4, 100, 32),
                                     (2, 1, 64, 64), (1, 2, 256, 16)])
def test_wkv6_kernel_matches_scan(b, h, t, d):
    ks = jax.random.split(jax.random.key(t * d), 6)
    r = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, h, t, d))))
    u = 0.5 * jax.random.normal(ks[4], (h, d))
    s0 = 0.1 * jax.random.normal(ks[5], (b, h, d, d))
    o_ref, s_ref = wkv6(r, k, v, w, u, s0, impl="ref")
    o_ker, s_ker = wkv6(r, k, v, w, u, s0, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.array(o_ker), np.array(o_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.array(s_ker), np.array(s_ref), atol=1e-4,
                               rtol=1e-4)


def test_wkv6_state_chaining_matches_full_sequence():
    """Running two halves with state carry == one full pass (decode vs
    prefill consistency)."""
    b, h, t, d = 1, 2, 64, 32
    ks = jax.random.split(jax.random.key(4), 5)
    r = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, h, t, d))))
    u = 0.5 * jax.random.normal(ks[4], (h, d))
    o_full, s_full = wkv6(r, k, v, w, u, impl="ref")
    half = t // 2
    o1, s1 = wkv6(r[:, :, :half], k[:, :, :half], v[:, :, :half],
                  w[:, :, :half], u, impl="ref")
    o2, s2 = wkv6(r[:, :, half:], k[:, :, half:], v[:, :, half:],
                  w[:, :, half:], u, s1, impl="ref")
    np.testing.assert_allclose(np.array(jnp.concatenate([o1, o2], axis=2)),
                               np.array(o_full), atol=1e-5)
    np.testing.assert_allclose(np.array(s2), np.array(s_full), atol=1e-5)
