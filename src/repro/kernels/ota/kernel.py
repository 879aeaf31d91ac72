"""Pallas TPU kernels for the fused OTA-MAC edge aggregation (paper Eq. 8).

Computes  v = (1/N) * sum_n h[n] * g[n, :] + noise_scale * w, fusing the
per-node gain scaling, the MAC superposition (the reduction), the 1/N
matched-filter normalization and the edge-noise add. The (N, d) matrix of
*scaled* gradients is never materialized in HBM. Two kernels:

  * `ota_edge_aggregate_kernel`, one aggregation of an (N, d) stack over a
    tile grid: node blocks stream through VMEM and accumulate into a
    d-tile resident accumulator (the reduction dimension is tiled
    innermost, so each d-tile of the output is written once). Tiles are
    (NODE_BLK, LANE_BLK) with LANE_BLK a multiple of 128 (the VPU lane
    width); the caller pads N and d to them and passes the gains as an
    (N, 1) column broadcast across lanes.
  * `ota_edge_aggregate_batched`, B independent aggregations of a
    (B, N, d) stack, as a vmapped caller (the Monte Carlo engine's
    trajectories) produces them: the grid runs over blocks of `tb`
    trajectories, each block holds every node and every coordinate at
    their true extent (no padding, no node-block reduction axis), and the
    gains arrive lane-dense as (tb, N) and move to sublanes in VMEM. A
    partial last block is fine: its rows are independent and the rows
    past B are never written back.

Accumulation is fp32 regardless of input dtype (bf16 gradients are upcast
on load), matching the MXU/VPU-native mixed-precision idiom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_NODE_BLK = 128
DEFAULT_LANE_BLK = 512


def _ota_kernel(g_ref, h_ref, w_ref, o_ref, acc_ref, *, n_nodes: int,
                noise_scale: float, n_node_blocks: int):
    """Grid: (d_blocks, node_blocks); node dim innermost (sequential)."""
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)  # (NODE_BLK, LANE_BLK)
    h = h_ref[...].astype(jnp.float32)  # (NODE_BLK, 1)
    acc_ref[...] += jnp.sum(h * g, axis=0, keepdims=True)  # (1, LANE_BLK)

    @pl.when(nb == n_node_blocks - 1)
    def _finalize():
        v = acc_ref[...] / n_nodes
        w = w_ref[...].astype(jnp.float32)
        o_ref[...] = (v + noise_scale * w).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("noise_scale", "n_nodes", "node_blk", "lane_blk",
                     "interpret", "out_dtype"),
)
def ota_edge_aggregate_kernel(
    grads: jax.Array,  # (N, d)
    gains: jax.Array,  # (N,)
    noise: jax.Array,  # (d,) standard-normal draws (edge noise, pre-scaled by 1)
    *,
    noise_scale: float,
    n_nodes: int | None = None,
    node_blk: int = DEFAULT_NODE_BLK,
    lane_blk: int = DEFAULT_LANE_BLK,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """`n_nodes` is the matched-filter normalization N (Eq. 8). Callers that
    zero-pad the node dimension pass the TRUE node count here: padded rows
    have zero gain and add nothing to the superposition, so normalizing by
    the true N inside the kernel is exact — no host-side rescaling (which
    would double-round the noise term through the output dtype).

    `out_dtype` (default: grads.dtype) is the emission dtype of the f32
    VMEM accumulator — the bf16-transmit/f32-accumulate transport path
    streams bf16 gradient tiles but keeps the received update in f32."""
    n, d = grads.shape
    if n_nodes is None:
        n_nodes = n
    if out_dtype is None:
        out_dtype = grads.dtype
    node_blk = min(node_blk, n)
    lane_blk = min(lane_blk, d)
    if n % node_blk or d % lane_blk:
        raise ValueError(f"(N={n}, d={d}) must tile by ({node_blk}, {lane_blk})")
    n_node_blocks = n // node_blk
    grid = (d // lane_blk, n_node_blocks)

    kernel = functools.partial(
        _ota_kernel,
        n_nodes=n_nodes,
        noise_scale=noise_scale,
        n_node_blocks=n_node_blocks,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((node_blk, lane_blk), lambda i, j: (j, i)),  # grads
            pl.BlockSpec((node_blk, 1), lambda i, j: (j, 0)),  # gains
            pl.BlockSpec((1, lane_blk), lambda i, j: (0, i)),  # noise
        ],
        out_specs=pl.BlockSpec((1, lane_blk), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((1, lane_blk), jnp.float32)],
        interpret=interpret,
    )(grads, gains.reshape(n, 1), noise.reshape(1, d))
    return out.reshape(d)


def _ota_batched_kernel(g_ref, h_ref, w_ref, o_ref, *, n_nodes: int):
    """One block of trajectories: g (tb, N, d), h (tb, N), w and o (tb, d)."""
    g = g_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)[:, :, None]  # gains onto sublanes
    v = jnp.sum(h * g, axis=1) / n_nodes
    o_ref[...] = (v + w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tb", "vmem_limit", "interpret", "out_dtype"))
def ota_edge_aggregate_batched(
    grads: jax.Array,  # (B, N, d)
    gains: jax.Array,  # (B, N)
    noise: jax.Array,  # (B, d) edge noise, already scaled
    *,
    tb: int,
    vmem_limit: int,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """v[b] = (1/N) sum_n gains[b, n] grads[b, n, :] + noise[b] for every
    b, in one `pallas_call` whose grid runs over ceil(B / tb) blocks of
    `tb` trajectories (a multiple of 8, or B itself). `vmem_limit` is the
    scoped VMEM the kernel may use. `out_dtype` (default: grads.dtype) is
    the emission dtype of the f32 sum."""
    b, n, d = grads.shape
    if out_dtype is None:
        out_dtype = grads.dtype
    if tb != b and tb % 8:
        raise ValueError(f"tb={tb} must be a multiple of 8 or B={b}")
    return pl.pallas_call(
        functools.partial(_ota_batched_kernel, n_nodes=n),
        grid=(pl.cdiv(b, tb),),
        in_specs=[
            pl.BlockSpec((tb, n, d), lambda i: (i, 0, 0)),  # grads
            pl.BlockSpec((tb, n), lambda i: (i, 0)),  # gains
            pl.BlockSpec((tb, d), lambda i: (i, 0)),  # noise
        ],
        out_specs=pl.BlockSpec((tb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="ota_edge_aggregate_batched",
    )(grads, gains, noise)
