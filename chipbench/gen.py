"""Traffic generation kept with the benchmark, so that a change to the
program cannot move the inputs it is measured on.

* `msd_like_regression` and `msd_problem` are copies of the program's
  MSD-like regression set (`repro.data.synthetic.msd_like_regression`,
  `benchmarks.common.MSDProblem.make`, paper arXiv:1908.07463 §VI-A):
  one sample per node, 90 standardized correlated features, a linear
  target plus noise, the regularized least-squares optimum and the
  constants of Theorem 1.
* `theorem1_stepsize` is a copy of `repro.core.theory.stepsize_theorem1`
  for Rayleigh fading.
* `token_batch` draws the synthetic language-model batches of
  `repro.data.synthetic.SyntheticTokens` (power-law unigrams over a
  permuted vocabulary; with probability `carry` a token is its
  predecessor plus one) on the device, from a key.
"""
from __future__ import annotations

import math

import numpy as np


def msd_like_regression(n_samples: int, dim: int, seed, noise_std: float):
    """(X, y): standardized correlated features and a linear target."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((dim, dim)))
    scales = np.exp(rng.uniform(-1.0, 1.0, size=dim))
    X = rng.standard_normal((n_samples, dim)) * scales[None]
    X = X @ q.T
    X /= X.std(axis=0, keepdims=True)
    theta = rng.standard_normal(dim) / np.sqrt(dim)
    y = X @ theta + noise_std * rng.standard_normal(n_samples)
    return X, y


def msd_problem(n_nodes: int, dim: int, lam: float, delta: float, seed,
                noise_std: float) -> dict:
    """Data, optimum and Theorem-1 constants of one N-node problem."""
    X, y = msd_like_regression(n_nodes, dim, seed, noise_std)
    A = X.T @ X / n_nodes
    theta_star = np.linalg.solve(A + lam * np.eye(dim), X.T @ y / n_nodes)
    eig = np.linalg.eigvalsh(A)
    return {
        "X": X, "y": y, "theta_star": theta_star, "lam": lam,
        "mu": float(eig[0] + lam), "L": float(eig[-1] + lam),
        "L_bar": float(np.max(np.sum(X**2, axis=1)) + lam),
        "delta": delta, "r0_sq": float(np.sum(theta_star**2)), "dim": dim,
    }


def rayleigh_moments(scale: float) -> tuple:
    """(E[h], Var[h]) of a Rayleigh gain of this scale."""
    mu_h = scale * math.sqrt(math.pi / 2.0)
    return mu_h, (2.0 - math.pi / 2.0) * scale**2


def theorem1_stepsize(prob: dict, scale: float, n_nodes: int,
                      safety: float) -> float:
    """Largest stepsize inside Eq. (15) of Theorem 1, times `safety`."""
    mu_h, sh2 = rayleigh_moments(scale)
    mu, L, L_bar, delta = prob["mu"], prob["L"], prob["L_bar"], prob["delta"]
    b1 = 2.0 / (mu_h * (mu + L))
    b2 = (2.0 * mu_h * mu * L * n_nodes) / (
        sh2 * L_bar**2 * (1.0 + 2.0 * delta) * (mu + L))
    return safety * min(b1, b2)


def token_batch(key, *, batch: int, seq_len: int, vocab: int, zipf_a: float,
                carry: float):
    """(batch, seq_len + 1) int32 tokens drawn on the device from `key`."""
    import jax
    import jax.numpy as jnp

    k_perm, k_iid, k_stay = jax.random.split(key, 3)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    probs = ranks ** (-zipf_a)
    probs = jax.random.permutation(k_perm, probs / jnp.sum(probs))
    cdf = jnp.cumsum(probs)
    u = jax.random.uniform(k_iid, (batch, seq_len + 1)) * cdf[-1]
    iid = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)
    stay = jax.random.uniform(k_stay, (batch, seq_len + 1)) < carry
    stay = stay.at[:, 0].set(False)
    pos = jnp.arange(seq_len + 1, dtype=jnp.int32)
    # a run of carried tokens continues from the last fresh draw before it
    start = jax.lax.cummax(jnp.where(stay, 0, pos[None, :]), axis=1)
    first = jnp.take_along_axis(iid, start, axis=1)
    return ((first + (pos[None, :] - start)) % vocab).astype(jnp.int32)
