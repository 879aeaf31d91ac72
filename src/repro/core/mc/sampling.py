"""Random-draw machinery for the Monte Carlo engine.

Everything here is a *traceable twin* of a host-side reference sampler
(`repro.core.channel.sample_gains` / `sample_complex_gains`,
`jax.random.split`, `jax.random.normal`) — same key-split order, same draw
shapes — so engine trajectories reproduce the reference simulators under a
fixed seed.

Node-count and antenna-count sweeps pad every row to N_max (M_max). The
partitionable threefry layout (`jax_threefry_partitionable`, on by
default) hashes each element from its own flat index, so draws are
prefix-stable: `normal(k, (n, d)) == normal(k, (n_max, d))[:n]`,
`uniform(k, (n,)) == uniform(k, (n_max,))[:n]` and
`split(k, m) == split(k, m_max)[:m]`. A row's draw at its true count is
therefore the N_max draw with the lanes beyond it masked — one static-shape
program for every count in the sweep (`_row_gains`, `_row_complex_gains`,
`_row_normal`, `_antenna_keys`). `_check_prefix_stable` refuses a
configuration in which that equality would not hold.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _sample_magnitude(k_mag: Array, fading: str, p: dict,
                      shape: tuple) -> Array:
    """Traceable twin of `channel._sample_magnitude` over dynamic scalar
    params: the per-family |h~| draw, shared by the precoded sampler
    (`_sample_gains`) and the complex no-CSI one (`_sample_complex_gains`)."""
    scale = p["scale"]
    if fading == "equal":
        return jnp.broadcast_to(scale.astype(jnp.float32), shape)
    if fading == "rayleigh":
        u = jax.random.uniform(k_mag, shape, minval=1e-12, maxval=1.0)
        return scale * jnp.sqrt(-2.0 * jnp.log(u))
    if fading == "rician":
        nu = jnp.sqrt(p["rician_k"] * 2.0) * scale
        xy = jax.random.normal(k_mag, shape + (2,)) * scale
        return jnp.sqrt((xy[..., 0] + nu) ** 2 + xy[..., 1] ** 2)
    if fading == "lognormal":
        return jnp.exp(scale * jax.random.normal(k_mag, shape))
    raise ValueError(f"unknown fading model: {fading}")


def _magnitude_m2(fading: str, p: dict) -> Array:
    """Traceable twin of `ChannelConfig.magnitude_m2`: E[h²] of the raw
    magnitude gain — the blind-MRC combiner's normalizer."""
    scale = p["scale"]
    if fading == "equal":
        return scale**2
    if fading == "rayleigh":
        return 2.0 * scale**2
    if fading == "rician":
        return 2.0 * scale**2 * (1.0 + p["rician_k"])
    if fading == "lognormal":
        return jnp.exp(2.0 * scale**2)
    raise ValueError(f"unknown fading model: {fading}")


def _sample_gains(key: Array, fading: str, p: dict, shape: tuple,
                  phase_zero: bool = False) -> Array:
    """Traceable twin of `channel.sample_gains` over dynamic scalar params.

    Split order and draw shapes match `sample_gains` exactly, so a fixed key
    yields the same random draws as the reference simulators (trajectories
    then agree to f32 rounding). The phase factor is applied
    unconditionally: with phase_error_max == 0 the uniform draw is 0 and
    cos(0) == 1, identical to the skipped branch.

    `phase_zero` (static) asserts that every row's phase_error_max is 0 and
    skips the phase draw entirely — value-identical (h · cos(0) == h
    bit-for-bit, and the phase stream hashes its own key half, so no other
    draw shifts) but half the per-gain threefry work. The execution layer's
    hoisted RNG plan sets it from the batch's configs.
    """
    k_mag, k_ph = jax.random.split(key)
    h = _sample_magnitude(k_mag, fading, p, shape)
    if phase_zero:
        return h.astype(jnp.float32)
    phi = jax.random.uniform(k_ph, shape, minval=-p["phase_error_max"],
                             maxval=p["phase_error_max"])
    return (h * jnp.cos(phi)).astype(jnp.float32)


def _sample_complex_gains(key: Array, fading: str, p: dict,
                          shape: tuple) -> tuple:
    """Traceable twin of `channel.sample_complex_gains`: (real, imag) parts
    of h~ = h e^{jφ} with the FULL uniform phase φ ~ Unif[-π, π) — no
    precoding in the blind-transmitter setting, so nothing bounds the
    phase. Same split order as the reference."""
    k_mag, k_ph = jax.random.split(key)
    h = _sample_magnitude(k_mag, fading, p, shape)
    phi = jax.random.uniform(k_ph, shape, minval=-np.pi, maxval=np.pi)
    return ((h * jnp.cos(phi)).astype(jnp.float32),
            (h * jnp.sin(phi)).astype(jnp.float32))


def _check_prefix_stable() -> None:
    """Padded sweeps reproduce per-count runs only under the partitionable
    threefry layout; refuse any other PRNG configuration."""
    if not (jax.config.jax_threefry_partitionable
            and jax.config.jax_default_prng_impl == "threefry2x32"):
        raise ValueError(
            "padded node/antenna-count sweeps need the default threefry "
            "PRNG with jax_threefry_partitionable=True (prefix-stable "
            "draws); run one count per call otherwise")


def _node_mask(x: Array, p: dict, n_sizes: tuple, n_max: int) -> Array:
    """Zero the lanes of a node-leading (n_max, ...) draw beyond this row's
    true node count `p['n_nodes']` — a no-op for single-N calls, where
    every row transmits at n_max."""
    if len(n_sizes) == 1:
        return x
    _check_prefix_stable()
    lane = jnp.arange(n_max) < p["n_nodes"]
    return jnp.where(lane.reshape((n_max,) + (1,) * (x.ndim - 1)), x,
                     jnp.zeros((), x.dtype))


def _row_gains(key: Array, fading: str, p: dict, n_sizes: tuple,
               n_max: int, phase_zero: bool = False) -> Array:
    """This row's (n_max,) slot gains, zero beyond its true node count:
    the n_max-shaped draw, masked (equal to the (n,)-shaped draw in lanes
    [0, n) — see the module docstring)."""
    return _node_mask(_sample_gains(key, fading, p, (n_max,), phase_zero),
                      p, n_sizes, n_max)


def _row_complex_gains(key: Array, fading: str, p: dict, n_sizes: tuple,
                       n_max: int) -> tuple:
    """Complex counterpart of `_row_gains` for the blind family."""
    a, b = _sample_complex_gains(key, fading, p, (n_max,))
    return (_node_mask(a, p, n_sizes, n_max),
            _node_mask(b, p, n_sizes, n_max))


def _row_normal(key: Array, p: dict, n_sizes: tuple, n_max: int, d: int,
                dtype) -> Array:
    """(n_max, d) standard normals, rows beyond the true node count zeroed
    (the fdm per-node noise)."""
    return _node_mask(jax.random.normal(key, (n_max, d), dtype=dtype),
                      p, n_sizes, n_max)


def _antenna_keys(key: Array, m_sizes: tuple) -> Array:
    """(m_max,) antenna keys whose first m entries equal
    `jax.random.split(key, m)` for every m <= m_max (split is prefix-stable
    too). Lanes >= a row's true antenna count hold well-formed keys that
    callers mask out on the antenna axis."""
    if len(m_sizes) > 1:
        _check_prefix_stable()
    return jax.random.split(key, max(m_sizes))
