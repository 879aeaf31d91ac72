"""Plain reference of GBMA gradient descent over a Rayleigh-fading
multiple-access channel on regularized least squares (arXiv:1908.07463,
Eqs. 8, 9 and 27), one trajectory per seed.

It imports nothing of the program. Its random draws follow the stream
the program documents for the single-antenna GBMA slot: seed s starts
from `jax.random.key(s)`, which splits into one key per step; a step key
splits into (k_h, k_w); k_h splits into (k_mag, k_phase) and the node
gains are `scale * sqrt(-2 log u)` with u uniform on [1e-12, 1) drawn
from k_mag (no phase error, so k_phase is unused); the edge noise is a
standard normal (d,) draw from k_w, scaled by
noise_std / (N sqrt(E_N)). Each step records the excess risk
0.5 (theta - theta*)' H (theta - theta*) of the current iterate and the
energy E_N sum_n ||g_n||^2 spent, then moves
theta <- theta - beta ((1/N) sum_n h_n g_n + w).

Every matrix product runs at the precision asked for: "highest" (float32)
for the reference; "bf16x3" for the control, which spells out the three
bfloat16 passes of JAX's "high" precision (each float32 operand split
into a bfloat16 high part and a bfloat16 remainder; hi*hi + hi*lo + lo*hi
accumulated in float32), so that it is the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _split_bf16(a):
    # reduce_precision, not a round trip through bfloat16: XLA may drop a
    # convert pair as excess precision, and the TPU's compiler does
    bf16 = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                             mantissa_bits=7)
    hi = bf16(a)
    return hi, bf16(a - hi)


def _dot(a, b, precision: str):
    if precision == "highest":
        return jnp.dot(a, b, precision="highest")
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split_bf16(a), _split_bf16(b)
    d = functools.partial(jnp.dot, precision="highest")  # exact products
    return d(ah, bh) + d(ah, bl) + d(al, bh)


@functools.partial(jax.jit, static_argnames=("steps", "precision"))
def _block(X, y, H, theta_star, lam, beta, energy, noise_std, scale, seeds,
           *, steps, precision):
    n, d = X.shape
    std = noise_std / (n * jnp.sqrt(energy))

    def trajectory(seed):
        def step(carry, k):
            theta, spent = carry
            diff = theta - theta_star
            risk = 0.5 * _dot(diff, _dot(H, diff, precision), precision)
            resid = _dot(X, theta, precision) - y
            g = resid[:, None] * X + lam * theta[None, :]
            spent = spent + energy * jnp.sum(g * g)
            k_h, k_w = jax.random.split(k)
            k_mag, _ = jax.random.split(k_h)
            u = jax.random.uniform(k_mag, (n,), minval=1e-12, maxval=1.0)
            h = scale * jnp.sqrt(-2.0 * jnp.log(u))
            w = jax.random.normal(k_w, (d,), jnp.float32)
            v = _dot(h, g, precision) / n + std * w
            return (theta - beta * v, spent), (risk, spent)

        keys = jax.random.split(jax.random.key(seed), steps)
        (theta, _), (risks, spent) = jax.lax.scan(
            step, (jnp.zeros((d,), jnp.float32), jnp.float32(0.0)), keys)
        diff = theta - theta_star
        last = 0.5 * _dot(diff, _dot(H, diff, precision), precision)
        return jnp.concatenate([risks, last[None]]), spent

    return jax.vmap(trajectory)(seeds)


def row(prob: dict, *, beta: float, energy: float, noise_std: float,
        scale: float, steps: int, seed_ints: np.ndarray, block: int,
        precision: str = "highest") -> tuple:
    """(risks (S, steps+1), cumulative energy (S, steps)) of one sweep row,
    float32, computed `block` seeds at a time."""
    X = np.asarray(prob["X"], np.float64)
    n = X.shape[0]
    H = X.T @ X / n + prob["lam"] * np.eye(X.shape[1])
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    args = (f32(X), f32(prob["y"]), f32(H), f32(prob["theta_star"]),
            f32(prob["lam"]), f32(beta), f32(energy), f32(noise_std),
            f32(scale))
    risks, spent = [], []
    for lo in range(0, len(seed_ints), block):
        r, e = _block(*args, jnp.asarray(seed_ints[lo:lo + block], jnp.int32),
                      steps=steps, precision=precision)
        risks.append(np.asarray(r))
        spent.append(np.asarray(e))
    return np.concatenate(risks), np.concatenate(spent)


def seed_stats(risks: np.ndarray) -> tuple:
    """(mean, 95% half-width) over the seed axis (axis -2), in float64."""
    r = np.asarray(risks, np.float64)
    s = r.shape[-2]
    mean = r.mean(axis=-2)
    ci95 = 1.96 * r.std(axis=-2, ddof=1) / np.sqrt(s) if s > 1 \
        else np.zeros_like(mean)
    return mean, ci95


def energy_to_target(risks: np.ndarray, spent: np.ndarray,
                     target: float) -> np.ndarray:
    """Per row, the seed mean of the energy spent before the first iterate
    whose risk is at or below `target` (nothing when the initial iterate
    already is; the whole horizon's energy when none is)."""
    out = []
    for r, e in zip(risks, spent):  # rows
        per_seed = []
        for rs, es in zip(r, e):
            hits = np.nonzero(rs <= target)[0]
            k = hits[0] if len(hits) else len(rs) - 1
            per_seed.append(0.0 if k == 0 else float(es[k - 1]))
        out.append(np.mean(per_seed))
    return np.asarray(out)
