"""Pallas TPU flash-attention kernel (online-softmax, VMEM-tiled).

Supports the attention variants required by the assigned architectures:
  * causal masking                       (all decoder stacks)
  * sliding-window masking               (gemma2 local layers, hymba, llama4-chunked)
  * logit soft-capping cap*tanh(x/cap)   (gemma2)
  * GQA via head-group reshape in ops.py (all GQA/MQA archs)

TPU adaptation: the (Sq, Skv) score matrix is never materialized in HBM —
the grid walks (batch*heads, q_blocks, kv_blocks) with the kv dimension
innermost/sequential; running max/denominator and the output accumulator live
in VMEM scratch. Block shapes are (128, head_dim) / (128, head_dim), keeping
the MXU matmul dims at the native 128 alignment. Accumulation is fp32.

With `with_lse=True` the kernel also emits each row's log-sum-exp
(m + log l), the one residual the flash backward
(`repro.models.flash_vjp`) needs besides the output — the custom VJP in
`ops.py` pairs this forward with that backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _attn_kernel(q_ref, k_ref, v_ref, *refs, scale: float, causal: bool,
                 window: int | None, softcap: float | None, block_q: int,
                 block_k: int, n_kv_blocks: int, with_lse: bool):
    if with_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_idx = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
    if causal:
        mask &= q_idx >= k_idx
    if window is not None:
        mask &= (q_idx - k_idx) < window

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal or window is not None:
        # skip fully-masked kv blocks entirely (their columns can't contribute)
        first_q = qi * block_q
        last_q = first_q + block_q - 1
        first_k = kj * block_k
        last_k = first_k + block_k - 1
        live = jnp.bool_(True)
        if causal:
            live &= last_q >= first_k
        if window is not None:
            live &= (first_q - last_k) < window

        @pl.when(live)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, ...] = m_ref[...] + jnp.log(l)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "block_q",
                     "block_k", "interpret", "with_lse"),
)
def flash_attention_kernel(
    q: jax.Array,  # (BH, Sq, d)
    k: jax.Array,  # (BH, Skv, d)
    v: jax.Array,  # (BH, Skv, d)
    *,
    scale: float,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    with_lse: bool = False,
):
    """(BH, Sq, d) attention output; with `with_lse` also the (BH, Sq, 1)
    f32 row log-sum-exp."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(f"seq lens ({sq},{skv}) must tile by ({block_q},{block_k})")
    n_kv_blocks = skv // block_k
    grid = (bh, sq // block_q, n_kv_blocks)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_kv_blocks,
        with_lse=with_lse,
    )
    out_specs = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
    if with_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return tuple(out) if with_lse else out
