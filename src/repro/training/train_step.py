"""Train-step builder: model loss + gradient aggregation protocol + optimizer.

`TrainConfig.aggregator` resolves through the MAC algorithm registry
(`mc/slots.ALGO_REGISTRY`) via the channel-transport layer
(`repro.core.transport`) — every registered algorithm trains real models.
Two routes:

  * **fused** (`gbma` / `fdm` / `centralized`, the historical trio): the
    MAC is folded into the loss — GBMA's fading superposition is obtained
    exactly by h-weighting each node's local loss and letting pjit/GSPMD
    insert the all-reduce, then edge noise is added to the REDUCED
    gradient tree (`gbma.perturb_gradients`); fdm adds its per-node-
    averaged noise the same way. One gradient tree, no per-node
    materialization — this is the production path for large models, and it
    is byte-for-byte the pre-transport behaviour (pinned by the golden
    trajectory tests).
  * **transport** (everything else — `blind`, `blind_ec`, `momentum`,
    `nesterov`, `power_control` — or any aggregator when
    `route='transport'`): each node's local gradient is computed
    explicitly (vmap over the node axis of the batch; node n owns the
    n-th contiguous example group) and the per-node (N, ...) gradient tree
    goes through `transport.aggregate` — block-tiled OTA superposition
    through the same slot fns the Monte Carlo engine validates. Costs one
    (N, ...) gradient tree per step; the engine-parity tests pin the
    trajectory against `run_mc` on the same RNG stream.

Stateful aggregators (receiver momentum, blind_ec's per-node residual)
carry their transport state INSIDE the opt_state slot: `build_train_step`
attaches `train_step.init_state(params)` which returns `opt.init(params)`
for stateless runs (unchanged) and `(opt.init(params), transport_state)`
for stateful ones — `run_training` threads it opaquely either way.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import transport
from repro.core.channel import edge_noise_std
from repro.core.gbma import (GBMAConfig, gbma_value_and_grad, node_weights,
                             perturb_gradients)
from repro.models.model import Model
from repro.optim.gd import Optimizer, clip_by_global_norm, global_norm
from repro.sharding.specs import current_mesh, params_shardings

PyTree = Any

# aggregators whose MAC folds into the loss/reduced-tree (no per-node
# gradient materialization); everything else goes through the transport
_FUSED_AGGREGATORS = ("gbma", "fdm", "centralized")


def _constrain_like_params(grads: PyTree, fsdp: bool) -> PyTree:
    """Pin the gradient tree to the parameter shardings. Without this GSPMD
    materializes scan-accumulated cotangents replicated (64 GiB/device for the
    400B config) before the optimizer update re-shards them."""
    mesh = current_mesh()
    if mesh is None:
        return grads
    shardings = params_shardings(grads, fsdp, mesh)
    return jax.tree_util.tree_map(
        lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, shardings)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    aggregator: str = "gbma"  # any slots.ALGO_REGISTRY name
    gbma: GBMAConfig = dataclasses.field(default_factory=GBMAConfig)
    seed: int = 0
    clip_norm: Optional[float] = None
    # §Perf: 'rbg' generates the d-dimensional edge noise with one
    # RngBitGenerator op per leaf instead of threefry's elementwise chain —
    # at d = 671e9 the threefry pipeline materializes tens of GiB of u32
    # counter tensors per expert leaf. 'threefry2x32' is the baseline.
    rng_impl: str = "threefry2x32"
    # §Perf: gradient accumulation over microbatches. Faithful to the paper —
    # each node transmits ONE analog gradient per slot regardless of how it
    # computed it locally (f_n is the node's full local loss); only the
    # per-step activation working set shrinks by the microbatch factor.
    # Fused route only: the transport route materializes per-node gradients.
    microbatches: int = 1
    # 'auto': fused path for gbma/fdm/centralized, transport for the rest.
    # 'transport': force every aggregator through transport.aggregate —
    # the engine-parity testing mode (gbma-through-transport matches the
    # fused path to f32 ulp, not byte-for-byte).
    route: str = "auto"
    # transport knobs (antennas, power budget, receiver momentum, block
    # tiling, transmit dtype, OTA kernel impl, engine-parity key schedule).
    # None derives TransportConfig(n_nodes, channel) from `gbma`; an
    # explicit TransportConfig is used as-is (its n_nodes/channel win).
    transport: Optional[transport.TransportConfig] = None


def _fdm_noise(grads: PyTree, key, gcfg: GBMAConfig) -> PyTree:
    """FDM-GD: each node's dedicated channel adds independent noise at energy
    E_N; the edge averages N received gradients, so the per-coordinate noise
    std is sigma_w / (sqrt(E_N) * sqrt(N)) = sqrt(N) * GBMA's. The draw is
    `transport.add_tree_noise` (bit-identical to the historical inline
    loop; the std constant stays host-side f64)."""
    std = (gcfg.channel.noise_std
           / math.sqrt(gcfg.channel.energy * gcfg.n_nodes))
    return transport.add_tree_noise(grads, key, std)


def _accumulated_grads(vg, params, batch, weights, m: int, fsdp: bool):
    """Scan over m microbatches, accumulating the mean gradient in f32
    (sharded like the params). Cuts the per-step activation working set by m
    at the cost of an f32 gradient accumulator (2x param bytes)."""
    mb_batch = jax.tree_util.tree_map(
        lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]), batch)
    mb_w = weights.reshape(m, -1)
    acc0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    acc0 = _constrain_like_params(acc0, fsdp)

    def body(carry, mb):
        acc, loss_sum = carry
        b, w = mb
        loss, g = vg(params, b, w)
        g = _constrain_like_params(g, fsdp)
        acc = jax.tree_util.tree_map(
            lambda a, x: a + x.astype(jnp.float32) / m, acc, g)
        acc = _constrain_like_params(acc, fsdp)
        return (acc, loss_sum + loss / m), None

    (grads, loss), _ = jax.lax.scan(
        body, (acc0, jnp.zeros((), jnp.float32)), (mb_batch, mb_w))
    return loss, grads


def resolve_route(tcfg: TrainConfig) -> str:
    """'fused' or 'transport' for this config; validates the aggregator
    against the registry either way."""
    transport.resolve(tcfg.aggregator)  # raises on unknown names
    if tcfg.route not in ("auto", "transport"):
        raise ValueError(
            f"route must be 'auto' or 'transport', got {tcfg.route!r}")
    if tcfg.route == "transport":
        return "transport"
    return "fused" if tcfg.aggregator in _FUSED_AGGREGATORS else "transport"


def _transport_config(tcfg: TrainConfig) -> transport.TransportConfig:
    if tcfg.transport is not None:
        return tcfg.transport
    return transport.TransportConfig(n_nodes=tcfg.gbma.n_nodes,
                                     channel=tcfg.gbma.channel)


def _node_grads_fn(model: Model, n_nodes: int) -> Callable:
    """(params, batch) -> (mean clean loss, per-node gradient tree with
    (n_nodes, ...) leaves). Node n's local objective f_n is the mean loss
    over its contiguous example group (the `node_weights` partition), so
    the transport's (1/N) Σ_n superposition estimates ∇F exactly as the
    fused h-weighted path does."""

    def fn(params, batch):
        bsz = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if bsz % n_nodes != 0:
            raise ValueError(
                f"global batch {bsz} not divisible by n_nodes {n_nodes}")
        node_batch = jax.tree_util.tree_map(
            lambda x: x.reshape(n_nodes, bsz // n_nodes, *x.shape[1:]),
            batch)

        def one(b):
            def loss(p):
                per_ex, _ = model.train_loss_per_example(p, b)
                return jnp.mean(per_ex)

            return jax.value_and_grad(loss)(params)

        losses, node_g = jax.vmap(one)(node_batch)
        return jnp.mean(losses), node_g

    return fn


def build_train_step(model: Model, tcfg: TrainConfig, opt: Optimizer
                     ) -> Callable:
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics). Pure; jit/pjit at the call site.

    The returned callable carries `train_step.init_state(params)` — use it
    instead of `opt.init` so stateful aggregators get their transport
    state (receiver momentum / blind_ec residual) threaded through the
    opt_state slot; for stateless runs it returns `opt.init(params)`
    unchanged. Metrics: `loss` (clean), `grad_norm` (global norm BEFORE
    clipping), `clip_frac` (1.0 on steps where clipping engaged, 0.0
    otherwise), `noise_std`, and on the transport route `tx_energy` (the
    slot's transmitted energy E_N Σ_n ‖x_n‖²)."""
    gcfg = tcfg.gbma
    route = resolve_route(tcfg)
    base_key = jax.random.key(tcfg.seed, impl=tcfg.rng_impl)

    if route == "transport":
        return _build_transport_step(model, tcfg, opt, base_key)
    if tcfg.transport is not None:
        raise ValueError(
            "TrainConfig.transport is set but the fused route ignores it; "
            "pass route='transport' to use it")

    vg = gbma_value_and_grad(
        lambda p, b: model.train_loss_per_example(p, b)[0])

    def train_step(params, opt_state, batch, step):
        k_step = jax.random.fold_in(base_key, step)
        k_h, k_w = jax.random.split(k_step)
        bsz = batch["tokens"].shape[0]

        if tcfg.aggregator == "gbma" and gcfg.enabled:
            with jax.named_scope("repro.gbma.weights"):
                weights = node_weights(k_h, gcfg, bsz)
        else:
            weights = jnp.ones((bsz,), jnp.float32)

        with jax.named_scope("repro.train.loss_grad"):
            if tcfg.microbatches > 1:
                clean_loss, grads = _accumulated_grads(
                    vg, params, batch, weights, tcfg.microbatches,
                    model.cfg.fsdp)
            else:
                clean_loss, grads = vg(params, batch, weights)
                grads = _constrain_like_params(grads, model.cfg.fsdp)

        with jax.named_scope("repro.gbma.noise"):
            if tcfg.aggregator == "gbma" and gcfg.enabled:
                grads = perturb_gradients(grads, k_w, gcfg)
            elif tcfg.aggregator == "fdm":
                grads = _fdm_noise(grads, k_w, gcfg)

        with jax.named_scope("repro.train.optimizer"):
            grads, metrics = _clip_and_metrics(grads, tcfg)
            params, opt_state = opt.update(grads, opt_state, params)
        metrics["loss"] = clean_loss
        metrics["noise_std"] = (edge_noise_std(gcfg.channel, gcfg.n_nodes)
                                if tcfg.aggregator == "gbma" else 0.0)
        return params, opt_state, metrics

    train_step.init_state = opt.init
    return train_step


def _clip_and_metrics(grads: PyTree, tcfg: TrainConfig):
    """Shared clip + metric computation: `grad_norm` is the PRE-clip global
    norm (a clipped run's reported norm is the raw gradient scale, not the
    post-clip constant `clip_norm`); `clip_frac` marks the steps where the
    clip engaged. The clip itself reuses the already-computed norm."""
    gnorm = global_norm(grads)
    if tcfg.clip_norm is not None:
        grads = clip_by_global_norm(grads, tcfg.clip_norm, norm=gnorm)
        clip_frac = (gnorm > tcfg.clip_norm).astype(jnp.float32)
    else:
        clip_frac = jnp.zeros((), jnp.float32)
    return grads, {"grad_norm": gnorm, "clip_frac": clip_frac}


def _build_transport_step(model: Model, tcfg: TrainConfig, opt: Optimizer,
                          base_key) -> Callable:
    """The transport route: explicit per-node gradients through
    `transport.aggregate`. Slot key schedule: `transport.step_key` —
    `fold_in(base, step)` normally, the engine's `split(key(seed), steps)`
    replay when `transport.mc_steps` is set (parity testing)."""
    algo = tcfg.aggregator
    tp = _transport_config(tcfg)
    spec = transport.resolve(algo)
    if tcfg.microbatches > 1:
        raise ValueError(
            "the transport route materializes per-node gradients and does "
            "not compose with microbatch accumulation; use microbatches=1")
    stateful = transport.has_state(algo)
    grads_fn = _node_grads_fn(model, tp.n_nodes)

    def train_step(params, opt_state, batch, step):
        if stateful:
            opt_state, agg_state = opt_state
        else:
            agg_state = None
        slot_key = transport.step_key(base_key, step, tp.mc_steps)

        eval_params = transport.lookahead_params(algo, params, agg_state, tp) \
            if spec.nesterov else params
        with jax.named_scope("repro.train.loss_grad"):
            clean_loss, node_g = grads_fn(eval_params, batch)
            node_g = jax.vmap(
                lambda g: _constrain_like_params(g, model.cfg.fsdp))(node_g)

        with jax.named_scope("repro.transport.aggregate"):
            update, agg_state, aux = transport.aggregate(
                algo, node_g, slot_key, tp, agg_state)
            update = _constrain_like_params(update, model.cfg.fsdp)

        with jax.named_scope("repro.train.optimizer"):
            update, metrics = _clip_and_metrics(update, tcfg)
            params, opt_state = opt.update(update, opt_state, params)
        if stateful:
            opt_state = (opt_state, agg_state)
        metrics["loss"] = clean_loss
        metrics["noise_std"] = (edge_noise_std(tp.channel, tp.n_nodes)
                                if spec.ota else 0.0)
        metrics["tx_energy"] = aux["tx_energy"]
        return params, opt_state, metrics

    def init_state(params):
        if stateful:
            return (opt.init(params), transport.init_state(algo, params, tp))
        return opt.init(params)

    train_step.init_state = init_state
    return train_step
