"""Jitted public wrapper for the OTA edge-aggregation kernel.

`impl='auto'` dispatches to the Pallas TPU kernel on TPU backends and to
the jnp oracle otherwise; the Pallas interpreter runs only when a caller
passes `interpret=True` (the CPU tests). Pads N and d to tile boundaries.

`noise_scale` is a TRACED scalar operand: sweeping noise levels (or N,
whose edge-noise std depends on it) reuses one compiled program per
(shape, impl) pair instead of recompiling per float value. Only `impl`,
`interpret` and `out_dtype` remain static. `trace_count()` /
`clear_cache()` mirror the Monte Carlo engine's compile-counting surface
(`repro.core.mc.trace_count` / `clear_cache`) so tests can assert the
wrapper's compile behaviour.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.ota.kernel import ota_edge_aggregate_kernel
from repro.kernels.ota.ref import ota_edge_aggregate_ref

_TRACES = obs.Counter()  # wrapper traces (== XLA compiles)


def trace_count(reset: bool = False) -> int:
    """Times the jitted wrapper body has been traced (== XLA compiles)
    since import or the last reset; `clear_cache()` also zeroes it."""
    return _TRACES.value(reset)


def clear_cache() -> bool:
    """Drop the wrapper's compiled cache and reset the trace counter.
    Returns False on JAX versions without jit clear_cache support."""
    _TRACES.value(reset=True)
    if hasattr(_ota_edge_aggregate, "clear_cache"):
        _ota_edge_aggregate.clear_cache()
        return True
    return False


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("impl", "interpret", "out_dtype"))
def _ota_edge_aggregate(grads, gains, noise, noise_scale, *, impl, interpret,
                        out_dtype):
    _TRACES.add()  # python side effect: runs once per trace/compile
    if impl == "ref":
        return ota_edge_aggregate_ref(grads, gains, noise,
                                      noise_scale=noise_scale,
                                      out_dtype=out_dtype)

    n, d = grads.shape
    node_blk = 128 if n >= 128 else max(8, 1 << (n - 1).bit_length())
    lane_blk = 512 if d >= 512 else 128
    pad_n = (-n) % node_blk
    pad_d = (-d) % lane_blk
    g = jnp.pad(grads, ((0, pad_n), (0, pad_d)))
    h = jnp.pad(gains, (0, pad_n))
    # the traced noise_scale folds into the noise operand in f32 — the
    # kernel's static scale stays 1.0 (bit-identical: the kernel upcast the
    # noise to f32 before its own multiply anyway, so the product is the
    # same f32 op either way, and 1.0*w is exact)
    w = jnp.pad(noise_scale * noise.astype(jnp.float32), (0, pad_d))
    # padded rows have zero gain -> contribute nothing to the superposition;
    # the kernel normalizes by the TRUE n (not n + pad_n), so no host-side
    # un-scaling of the noise term is needed (the old rescale-then-subtract
    # double-rounded the noise through the output dtype — lossy for bf16).
    out = ota_edge_aggregate_kernel(
        g, h, w,
        noise_scale=1.0,
        n_nodes=n,
        node_blk=node_blk,
        lane_blk=lane_blk,
        interpret=interpret,
        out_dtype=out_dtype,
    )
    return out[:d]


def ota_edge_aggregate(
    grads: jax.Array,
    gains: jax.Array,
    noise: jax.Array,
    *,
    noise_scale,
    impl: str = "auto",  # 'auto' | 'pallas' | 'ref'
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """One OTA edge aggregation v = (1/N) Σ h_n g_n + noise_scale·w.

    `noise_scale` may be a python float or a traced f32 scalar — it is a
    traced operand either way (one compile covers every value).
    `out_dtype` (static; default grads.dtype) picks the emission dtype of
    the f32 accumulation — f32 out for bf16 grads is the mixed-precision
    transmit path."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if out_dtype is None:
        out_dtype = grads.dtype
    return _ota_edge_aggregate(
        grads, gains, noise, jnp.asarray(noise_scale, jnp.float32),
        impl=impl, interpret=interpret, out_dtype=jnp.dtype(out_dtype))
