"""GBMA — Gradient-Based Multiple Access (paper §III).

Three implementation tiers, all realizing Eq. (8)–(9):

  v_k = (1/N) sum_n h_{n,k} g_n(theta_k) + w_k,  w_k ~ N(0, sigma_w^2/(N^2 E_N) I_d)
  theta_{k+1} = theta_k - beta v_k

(i)   `ota_aggregate` / `GBMASimulator` — vectorized N-node simulation used by
      the paper-experiment benchmarks (linear regression, localization).
(ii)  `gbma_value_and_grad` + `perturb_gradients` — the *production* path: the
      fading superposition is obtained exactly by weighting each node's local
      loss with its stop-gradiented gain (∇ Σ h_n f_n /N = Σ h_n g_n /N) and
      letting pjit/GSPMD insert the all-reduce (the MAC superposition); edge
      noise is added to the reduced gradient tree afterwards. Composes with
      FSDP / tensor parallelism / remat / scan.
(iii) `shard_map_aggregate` — the explicit per-device protocol: scale the local
      gradient by the local node gain, `psum` over the node axes (= analog
      superposition over the MAC), normalize by N, add edge noise. Used for
      exposition and cross-validated against tier (ii) in tests.

Tier (i) and the tree helpers are thin veneers over the unified
channel-transport layer (`repro.core.transport`), which routes every slot
through the `mc/slots.py` algo registry — one definition of each MAC
algorithm shared by the Monte Carlo engine and real-model training. The
veneers keep this module's historical signatures and RNG streams
(split-for-split); values agree with the pre-transport implementations to
f32 ulp (<= 1e-6): the only arithmetic change is that channel constants
like the edge-noise std are now computed in traced f32 (the engine's
convention) instead of host-side f64, a one-ulp rounding difference.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.compat import tree_map
from repro.core import transport
from repro.core.channel import ChannelConfig, edge_noise_std, sample_gains

Array = jax.Array
PyTree = Any


# --------------------------------------------------------------------------
# tier (i): vectorized N-node simulation (paper experiments)
# --------------------------------------------------------------------------
def ota_aggregate(
    grads: Array,  # (N, d) per-node local gradients
    key: Array,
    cfg: ChannelConfig,
    use_kernel: bool = False,
) -> Array:
    """One MAC slot: returns v_k of shape (d,) per Eq. (8).

    A veneer over `transport.aggregate('gbma', ...)` — the slot key splits
    k -> (k_h, k_w) exactly as before (gains then edge noise), so fixed
    seeds reproduce; the received update is computed in f32 and cast back
    to `grads.dtype`. `use_kernel` routes the superposition through
    `repro.kernels.ota` (pallas on TPU, jnp oracle elsewhere)."""
    impl = ("pallas" if jax.default_backend() == "tpu" else "ref") \
        if use_kernel else "inline"
    tcfg = transport.TransportConfig(
        n_nodes=grads.shape[0], channel=cfg, ota_impl=impl)
    v, _, _ = transport.aggregate("gbma", grads, key, tcfg)
    return v.astype(grads.dtype)


@dataclasses.dataclass
class GBMASimulator:
    """Iterates theta_{k+1} = theta_k - beta * v_k on an N-node problem.

    `grad_fn(theta) -> (N, d)` returns every node's local gradient (the
    simulator plays both the nodes and the edge). Matches the paper's
    experimental setup; `run` returns the trajectory of estimates.
    """

    grad_fn: Callable[[Array], Array]
    channel: ChannelConfig
    stepsize: float

    def run(self, theta0: Array, steps: int, key: Array) -> Array:
        def body(theta, k):
            g = self.grad_fn(theta)  # (N, d)
            v = ota_aggregate(g, k, self.channel)
            return theta - self.stepsize * v, theta

        keys = jax.random.split(key, steps)
        theta_fin, traj = jax.lax.scan(body, theta0, keys)
        return jnp.concatenate([traj, theta_fin[None]], axis=0)  # (steps+1, d)


# --------------------------------------------------------------------------
# tier (ii): production path — h-weighted loss under pjit
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GBMAConfig:
    """GBMA integration config for the training substrate.

    n_nodes: total number of transmitting nodes N. Each node owns a contiguous
      group of examples in the global batch (global_batch % n_nodes == 0).
    channel: the fading-MAC model.
    enabled: if False the aggregator degrades to exact (centralized) mean — the
      paper's noiseless/equal-gain special case (Remark 1).
    """

    n_nodes: int = 16
    channel: ChannelConfig = dataclasses.field(default_factory=ChannelConfig)
    enabled: bool = True
    # §Perf: sample the edge noise directly in the gradient dtype (bf16) —
    # the f32 default is the faithful baseline; for bf16 gradients the noise
    # (std << 1) quantizes identically after the add
    noise_dtype: str = "float32"


def node_weights(key: Array, gcfg: GBMAConfig, global_batch: int) -> Array:
    """Per-example fading weights, shape (global_batch,).

    Example i belongs to node floor(i / (B/N)); all of a node's examples share
    its slot gain h_{n,k}. With `enabled=False` returns all-ones (equal gains,
    noiseless edge → centralized GD; Remark 1 of the paper).
    """
    if not gcfg.enabled:
        return jnp.ones((global_batch,), jnp.float32)
    n = gcfg.n_nodes
    if global_batch % n != 0:
        raise ValueError(f"global_batch {global_batch} not divisible by n_nodes {n}")
    h = sample_gains(key, gcfg.channel, (n,))  # (N,)
    return jnp.repeat(h, global_batch // n)


def gbma_value_and_grad(
    loss_fn: Callable[..., Array],
) -> Callable[..., Tuple[Array, PyTree]]:
    """Wrap a per-example loss into the h-weighted GBMA objective.

    `loss_fn(params, batch) -> (B,) per-example losses`. Returns a function
    `(params, batch, weights) -> (mean_loss, distorted_grad)` where
    `distorted_grad = (1/N) Σ_n h_n ∇f_n` exactly (f_n = mean loss of node n's
    example group, h_n folded into per-example weights that sum to B).
    """

    def weighted(params, batch, weights):
        losses = loss_fn(params, batch)  # (B,)
        w = jax.lax.stop_gradient(weights).astype(losses.dtype)
        return jnp.mean(w * losses), jnp.mean(losses)

    vg = jax.value_and_grad(weighted, has_aux=True)

    def fn(params, batch, weights):
        (_, clean_loss), grads = vg(params, batch, weights)
        return clean_loss, grads

    return fn


def perturb_gradients(
    grads: PyTree, key: Array, gcfg: GBMAConfig, dtype=None
) -> PyTree:
    """Add the edge noise w_k to the superposed gradient tree (Eq. 8).

    Per-leaf independent normals with std sigma_w/(N sqrt(E_N)); leaf keys
    come from `split(key, n_leaves)` so the tree structure, not leaf order
    in memory, defines the stream. SPMD-safe: same key on every device
    yields identical noise, consistent with any output sharding. The draw
    itself is `transport.add_tree_noise` (bit-identical to the historical
    inline loop); only the std constant stays host-side f64 here, so this
    fused path is byte-for-byte stable across the transport refactor.
    """
    if not gcfg.enabled:
        return grads
    if dtype is None:
        dtype = jnp.dtype(gcfg.noise_dtype)
    std = edge_noise_std(gcfg.channel, gcfg.n_nodes)
    return transport.add_tree_noise(grads, key, std, noise_dtype=dtype)


# --------------------------------------------------------------------------
# tier (iii): explicit shard_map protocol
# --------------------------------------------------------------------------
def shard_map_aggregate(
    local_grad: PyTree,
    local_gain: Array,  # scalar gain of this device's node
    key: Array,  # identical on all devices (edge noise)
    gcfg: GBMAConfig,
    axis_names: Sequence[str] = ("data",),
) -> PyTree:
    """Explicit OTA protocol body — call inside `repro.compat.shard_map`.

    Each device scales its local gradient by its own slot gain (the analog
    amplification sqrt(E_N) h g after phase correction and matched filtering),
    `psum`s over the node axes — the physical superposition on the MAC — then
    normalizes by N and adds the edge noise once (same key on all devices).
    """
    n = gcfg.n_nodes

    def superpose(g):
        s = g * local_gain.astype(g.dtype)
        for ax in axis_names:
            s = jax.lax.psum(s, ax)
        return s / n

    v = tree_map(superpose, local_grad)
    return perturb_gradients(v, key, gcfg)


def ota_aggregate_multiantenna(
    grads: Array,  # (N, d)
    key: Array,
    cfg: ChannelConfig,
    n_antennas: int,
) -> Array:
    """Multi-antenna edge receiver (related work [12], Amiri et al.): each of
    M antennas sees an independent fading realization of the same
    superposition; MRC-style averaging divides both the gradient-distortion
    variance (sigma_h^2 -> sigma_h^2/M) and the noise variance by M — the
    fading effect vanishes as M grows even without any phase correction at
    the transmitters.

    Veneer over `transport.aggregate('gbma', ..., n_antennas=M)`: the key
    splits `split(key, M)` into per-antenna slot chains exactly as the
    historical vmap did (M=1 included — its extra split is part of the
    stream)."""
    tcfg = transport.TransportConfig(
        n_nodes=grads.shape[0], channel=cfg, n_antennas=n_antennas)
    v, _, _ = transport.aggregate("gbma", grads, key, tcfg)
    return v.astype(grads.dtype)


def blind_ota_aggregate(
    grads: Array,  # (N, d) transmitted analog vectors (no precoding)
    key: Array,
    cfg: ChannelConfig,
    n_antennas: int,
) -> Array:
    """Blind-transmitter OTA slot (Amiri, Duman & Gündüz, arXiv:1907.03909).

    Nodes transmit sqrt(E_N) g_n with NO channel state information — no
    channel-inversion precoding, no phase correction — so antenna m of the
    edge receives the complex superposition
    ``y_m = Σ_n h~_{n,m} sqrt(E_N) g_n + z~_m`` with i.i.d. complex gains
    h~ = h e^{jφ}, φ ~ Unif[-π, π). The edge (which does know the channel —
    receiver CSI only) MRC-combines over its M antennas:

        v = 1/(N M E[h²]) Σ_m Re{ (Σ_n h~*_{n,m}) y_m } / sqrt(E_N)

    Channel hardening makes the per-node coefficient
    c_n = Σ_m(a_{n,m} A_m + b_{n,m} B_m)/(M E[h²]) concentrate on 1: the
    cross-node interference and the noise both vanish as 1/M, so v → the
    equal-gain (scale 1) GBMA update as M grows — no transmitter CSI
    needed. Effective noise variance ≈ σ_w²/(E_N N M E[h²]) per coordinate
    (vs σ_w²/(E_N N²) for precoded GBMA).

    Veneer over `transport.aggregate('blind', ...)` (the engine's
    `_blind_slot`): key chain slot -> `split(key, M)` -> per antenna
    (k_h complex gains, k_w stacked real/imag noise), split-for-split the
    historical stream.
    """
    tcfg = transport.TransportConfig(
        n_nodes=grads.shape[0], channel=cfg, n_antennas=n_antennas)
    v, _, _ = transport.aggregate("blind", grads, key, tcfg)
    return v.astype(grads.dtype)


# --------------------------------------------------------------------------
# energy accounting
# --------------------------------------------------------------------------
def slot_energy(grads: Array, cfg: ChannelConfig) -> Array:
    """Total transmitted energy of one slot: Σ_n E_N ||g_n||^2 (waveforms are
    orthonormal so the transmitted signal energy of node n is E_N ||g_n||²)."""
    return cfg.energy * jnp.sum(grads.astype(jnp.float32) ** 2)
