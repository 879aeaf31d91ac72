"""Jitted public wrapper around the flash-attention kernel.

Handles GQA head grouping ((B, Hq, S, d) queries vs (B, Hkv, S, d) kv),
backend dispatch (Pallas on TPU, blockwise-jnp on CPU), and padding of
sequence lengths to block boundaries.

Autodiff cannot see through a `pallas_call`, so the Pallas path carries a
custom VJP: the forward is the kernel (which also emits the row
log-sum-exp), the backward is the flash backward of
`repro.models.flash_vjp` — the same one the `opt_flash_vjp` jnp path uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.attention.kernel import flash_attention_kernel
from repro.kernels.attention.ref import attention_ref
from repro.models.flash_vjp import _flash_bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _pallas_attention(q, k, v, scale, causal, window, softcap, block_q,
                      block_k, interpret):
    """(BH, S, d) flash attention through the Pallas kernel."""
    return flash_attention_kernel(
        q, k, v, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret)


def _pallas_attention_fwd(q, k, v, scale, causal, window, softcap, block_q,
                          block_k, interpret):
    out, lse = flash_attention_kernel(
        q, k, v, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _pallas_attention_bwd(scale, causal, window, softcap, block_q, block_k,
                          interpret, res, d_out):
    # flash_vjp works on the grouped (b, h, g, s, d) layout: view each of
    # the BH rows as its own (h=1, g=1) head
    q, k, v, out, lse = res
    grouped = (q[:, None, None], k[:, None], v[:, None], out[:, None, None],
               lse[:, None, None])
    dq, dk, dv = _flash_bwd(scale, causal, window, softcap, 0, block_q,
                            block_k, grouped, d_out[:, None, None])
    return dq[:, 0, 0], dk[:, 0], dv[:, 0]


_pallas_attention.defvjp(_pallas_attention_fwd, _pallas_attention_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "impl", "interpret"),
)
def multi_head_attention(
    q: jax.Array,  # (B, Hq, Sq, d)
    k: jax.Array,  # (B, Hkv, Skv, d)
    v: jax.Array,  # (B, Hkv, Skv, d)
    *,
    scale: float,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    impl: str = "auto",  # 'auto' | 'pallas' | 'ref'
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hq % hkv:
        raise ValueError(f"query heads {hq} must be a multiple of kv heads {hkv}")
    group = hq // hkv
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"

    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)

    if impl == "ref":
        out = attention_ref(
            q.reshape(b * hq, sq, d),
            k.reshape(b * hq, -1, d),
            v.reshape(b * hq, -1, d),
            scale=scale, causal=causal, window=window, softcap=softcap,
        )
        return out.reshape(b, hq, sq, d)

    skv = k.shape[2]
    blk_q = min(128, sq) if sq >= 128 else sq
    blk_k = min(128, skv) if skv >= 128 else skv
    pad_q = (-sq) % blk_q
    pad_k = (-skv) % blk_k
    if pad_k and not causal:
        # zero-padded kv columns would attend under a non-causal mask;
        # non-causal callers (cross-attention) fall back to the oracle path
        out = attention_ref(
            q.reshape(b * hq, sq, d), k.reshape(b * hq, skv, d),
            v.reshape(b * hq, skv, d),
            scale=scale, causal=causal, window=window, softcap=softcap,
        )
        return out.reshape(b, hq, sq, d)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # padded kv columns must not attend: push them outside the causal frontier
    # by relying on causal mask when enabled; otherwise mask via big negative k
    out = _pallas_attention(
        qp.reshape(b * hq, sq + pad_q, d),
        kp.reshape(b * hq, skv + pad_k, d),
        vp.reshape(b * hq, skv + pad_k, d),
        scale, causal, window, softcap, blk_q, blk_k, interpret)
    return out.reshape(b, hq, sq + pad_q, d)[:, :, :sq, :]
