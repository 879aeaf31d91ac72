"""Jitted public wrapper for the OTA edge-aggregation kernels.

`impl='auto'` dispatches to the Pallas TPU kernels on TPU backends and to
the jnp oracle otherwise; the Pallas interpreter runs only when a caller
passes `interpret=True` (the CPU tests).

Two routes, picked by how the call is batched:

  * An un-vmapped call runs the 2-D tiled kernel: N and d are padded to
    its tile boundaries (padded rows carry zero gain; the kernel
    normalizes by the true N).
  * A vmapped call whose gradients are batched (the Monte Carlo engine's
    slot under vmap(rows) and vmap(seeds)) runs as ONE batched kernel over
    the whole (B, N, d) stack, read as the producer wrote it: no padding,
    the gains lane-dense as (B, N). `jax.custom_batching.custom_vmap`
    routes it, so callers change nothing; nested vmaps fold their batch
    axes into B. The grid steps over blocks of `tb` trajectories, sized
    from the shape (`_batch_block`). A vmap that shares one gradient stack
    across its batch (multi-antenna MRC), or a stack too large for VMEM,
    keeps the vmapped tiled kernel, which Pallas batches by grid axes.

The named counters `repro.ota.batched_routes` and
`repro.ota.tiled_routes` count, at trace time, the vmapped calls sent to
each route (`repro.obs.counters()`).

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
from jax.custom_batching import custom_vmap

from repro import obs
from repro.kernels.ota.kernel import (ota_edge_aggregate_batched,
                                      ota_edge_aggregate_kernel)
from repro.kernels.ota.ref import ota_edge_aggregate_ref

_TRACES = obs.Counter()  # wrapper traces (== XLA compiles)
_BATCHED_ROUTES = obs.Counter("repro.ota.batched_routes")
_TILED_ROUTES = obs.Counter("repro.ota.tiled_routes")

# The batched kernel's block of trajectories: the largest multiple of 8
# whose two g buffers (double-buffered) fit _G_BUFFERS_BYTES, at least 8,
# and the whole stack where it is smaller. Its g buffers may take at most
# half of _VMEM_LIMIT, the kernel's scoped VMEM (of v5e's 128 MiB); the
# rest holds the f32 product and the small operands.
_G_BUFFERS_BYTES = 8 << 20
_VMEM_LIMIT = 64 << 20


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

    # the traced noise_scale folds into the noise operand in f32 — the
    # kernels add it unscaled (bit-identical: the kernel upcast the noise to
    # f32 before its own multiply anyway, so the product is the same f32 op
    # either way)
    w = noise_scale * noise.astype(jnp.float32)
    return _routes(interpret, out_dtype)(grads, gains, w)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _batch_block(b: int, n: int, d: int, dtype) -> int | None:
    """Trajectories per block of the batched kernel for a (b, n, d) stack
    of `dtype`, or None where even the smallest block does not fit."""
    itemsize = jnp.dtype(dtype).itemsize
    # one trajectory's (n, d) block in VMEM, tiled (32 / itemsize, 128)
    per = _round_up(n, 32 // itemsize) * _round_up(d, 128) * itemsize
    tb = max(8, _G_BUFFERS_BYTES // (2 * per) // 8 * 8)
    if tb >= b:
        tb = b
    return tb if 2 * tb * per <= _VMEM_LIMIT // 2 else None


def _tiled(grads, gains, w, *, interpret, out_dtype):
    """One aggregation on the 2-D tiled kernel, N and d padded to tiles."""
    n, d = grads.shape
    node_blk = 128 if n >= 128 else max(8, 1 << (n - 1).bit_length())
    lane_blk = 512 if d >= 512 else 128
    pad_n = (-n) % node_blk
    pad_d = (-d) % lane_blk
    # padded rows have zero gain -> contribute nothing to the superposition;
    # the kernel normalizes by the TRUE n (not n + pad_n), so no host-side
    # un-scaling of the noise term is needed (the old rescale-then-subtract
    # double-rounded the noise through the output dtype — lossy for bf16).
    out = ota_edge_aggregate_kernel(
        jnp.pad(grads, ((0, pad_n), (0, pad_d))),
        jnp.pad(gains, (0, pad_n)),
        jnp.pad(w, (0, pad_d)),
        noise_scale=1.0,
        n_nodes=n,
        node_blk=node_blk,
        lane_blk=lane_blk,
        interpret=interpret,
        out_dtype=out_dtype,
    )
    return out[:d]


def _batched(grads, gains, w, *, interpret, out_dtype):
    """B aggregations of a (B, N, d) stack on the batched kernel."""
    b, n, d = grads.shape
    return ota_edge_aggregate_batched(
        grads, gains, w, tb=_batch_block(b, n, d, grads.dtype),
        vmem_limit=_VMEM_LIMIT, interpret=interpret, out_dtype=out_dtype)


def _bcast(x, batched: bool, size: int):
    return x if batched else jnp.broadcast_to(x, (size,) + x.shape)


@functools.lru_cache(maxsize=None)
def _routes(interpret: bool, out_dtype):
    """The aggregation (g (N, d), gains (N,), scaled noise (d,)) -> (d,)
    for these static choices, with the vmap rules that route it."""
    tiled = functools.partial(_tiled, interpret=interpret,
                              out_dtype=out_dtype)
    batched_raw = functools.partial(_batched, interpret=interpret,
                                    out_dtype=out_dtype)

    @custom_vmap
    def one(g, h, w):
        return tiled(g, h, w)

    @one.def_vmap
    def _one_rule(size, in_batched, g, h, w):
        g_b, h_b, w_b = in_batched
        if g_b and _batch_block(size, *g.shape[1:], g.dtype) is not None:
            _BATCHED_ROUTES.add()
            return batched(g, _bcast(h, h_b, size), _bcast(w, w_b, size)), True
        # g shared by the batch (never broadcast), or a stack too large
        # for VMEM: the tiled kernel, which Pallas batches by grid axes
        _TILED_ROUTES.add()
        axes = tuple(0 if x else None for x in in_batched)
        return jax.vmap(tiled, in_axes=axes)(g, h, w), True

    @custom_vmap
    def batched(g, h, w):
        return batched_raw(g, h, w)

    @batched.def_vmap
    def _batched_rule(size, in_batched, g, h, w):
        g_b, h_b, w_b = in_batched
        if g_b and _batch_block(size * g.shape[1], *g.shape[2:],
                                g.dtype) is not None:
            # an outer vmap: fold its axis into B
            b, n, d = g.shape[1:]
            out = batched(g.reshape(size * b, n, d),
                          _bcast(h, h_b, size).reshape(size * b, n),
                          _bcast(w, w_b, size).reshape(size * b, d))
            return out.reshape(size, b, d), True
        axes = tuple(0 if x else None for x in in_batched)
        return jax.vmap(batched_raw, in_axes=axes)(g, h, w), True

    return one


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
