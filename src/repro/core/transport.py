"""Unified channel-transport layer: any registered MAC algorithm on a
gradient PYTREE.

The paper's core object — the analog superposition of local gradients over
a noisy fading MAC (Eq. 8) — was implemented twice: as tree-level helpers
in `core/gbma.py` (the production training path: gbma/fdm/centralized
only) and as the per-slot algo registry in `core/mc/slots.py` (all eight
algorithms, validated by the Monte Carlo engine). This module is the
single seam between them: it applies ANY `slots.ALGO_REGISTRY` entry to a
gradient pytree of per-node gradients, so blind / blind_ec / momentum /
nesterov / power_control train real models over exactly the simulated MAC
the engine validates.

How a slot evaluates (flash-attention-style IO-aware tiling):

  * the tree's leaves are viewed as (N, size) column panels of one logical
    (N, D) transmission (D = total parameter count) — the concatenated
    matrix is NEVER materialized;
  * each slot's random draws are materialized ONCE for the full D via the
    algorithm's registered `hoist_draws` twin (the same replay machinery
    the engine's hoisted RNG plan uses), then column-sliced per block
    (`slots.slice_draws`) — so every block consumes ITS coordinates of THE
    slot's streams. The draws are therefore bit-identical across tilings —
    all slot computations are per-coordinate given their draws — and the
    only tiling artifact left is XLA reassociating the f32 node-
    superposition reduction differently per block shape: tiled and untiled
    agree to a few ulp (the tests pin <= 1e-6);
  * blocks stream through the slot fn (and, with `ota_impl != 'inline'`,
    through the pallas OTA kernel) one (N, block_d) tile at a time,
    accumulating in f32;
  * `transmit_dtype='bfloat16'` casts the transmitted blocks to bf16 (half
    the superposition memory traffic) while gains, noise and accumulation
    stay f32 — the received update is always f32. `centralized` is exempt
    (it models no channel, so there is nothing to quantize — and its plain
    node sum would otherwise accumulate in bf16).

Slot state (what the engine carries in its scan) lives in an explicit
state dict from `init_state`: `'m'` — the receiver-side momentum carry of
the momentum/nesterov algorithms (γ m + v, applied as the update);
`'e'` — blind_ec's per-node residual tree with the power-budget truncation
α = min(1, √(B/‖g+e‖²)) computed over the FULL per-node vector (a global
reduction across all blocks, handled here — the one slot quantity that is
not per-coordinate). Training integration: `training/train_step.py`
resolves `TrainConfig.aggregator` through this layer.

RNG contract: one slot consumes one key exactly as the engine's slot fns
split it. `step_key(base, step, mc_steps=steps)` replays the engine's
`split(key(seed), steps)[step]` schedule (threefry split streams depend on
the total count, so the engine's steps must be known) — the transport↔
engine parity tests drive both stacks from the same stream.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat import tree_flatten, tree_map, tree_unflatten
from repro.core.channel import ChannelConfig
from repro.core.mc.slots import (ALGO_REGISTRY, AlgoSpec, SlotCtx,
                                 resolve_ota_impl, slot_update_block)

Array = jax.Array
PyTree = Any

# block_d sentinel: one slot call on the concatenated (N, D) matrix — the
# untiled reference the bench compares the tiled path against
FULL_CONCAT = -1


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """The MAC transport of one training run.

    n_nodes: transmitting nodes N; every gradient leaf carries a leading
      node axis of this length.
    channel: the fading-MAC model (shared with the engine's ChannelBatch).
    n_antennas: edge antenna count M — required for the blind family,
      optional MRC path for the precoded family (None = single antenna,
      RNG-identical to `GBMASimulator`).
    gamma: receiver momentum coefficient of the uses_gamma algorithms
      (`run_mc(momentum=)`'s default 0.9).
    stepsize: the optimizer stepsize β, consumed ONLY by the nesterov
      lookahead θ − βγm (the engine's θ_eval); keep it equal to the
      optimizer's.
    power_budget: blind_ec's per-slot per-node budget B (squared norm of
      the transmitted vector; inf = unbounded).
    invert_channel / h_min: fdm gain equalization and the power-control
      silence threshold — engine defaults.
    block_d: column tile width. None (default) = one block per leaf (no
      copies, no splitting); an int tiles leaves into <= block_d columns;
      FULL_CONCAT materializes the whole (N, D) matrix in one slot call
      (the untiled reference).
    transmit_dtype: None (f32 faithful baseline) or 'bfloat16' — cast the
      transmitted blocks, keep gains/noise/accumulation f32.
    ota_impl: 'inline' | 'auto' | 'pallas' | 'interpret' | 'ref' for the
      single-antenna OTA superposition (`slots.resolve_ota_impl`: 'auto' =
      pallas on TPU, inline otherwise).
    mc_steps: when set, `step_key` replays the engine's
      `split(key(seed), mc_steps)` slot-key schedule for trajectory parity
      with `run_mc`; None uses the training stack's `fold_in` schedule.
    """

    n_nodes: int = 16
    channel: ChannelConfig = dataclasses.field(default_factory=ChannelConfig)
    n_antennas: Optional[int] = None
    gamma: float = 0.9
    stepsize: float = 0.0
    power_budget: float = math.inf
    invert_channel: bool = False
    h_min: float = 0.3
    block_d: Optional[int] = None
    transmit_dtype: Optional[str] = None
    ota_impl: str = "inline"
    mc_steps: Optional[int] = None


def resolve(algo: str) -> AlgoSpec:
    """Registry lookup with the engine's error message."""
    if algo not in ALGO_REGISTRY:
        raise ValueError(
            f"unknown algo {algo!r}; expected one of {tuple(ALGO_REGISTRY)}")
    return ALGO_REGISTRY[algo]


def has_state(algo: str) -> bool:
    """Whether `aggregate` for this algorithm carries transport state
    (momentum carry and/or error-feedback residual) between steps."""
    spec = resolve(algo)
    return spec.uses_gamma or spec.error_feedback


def init_state(algo: str, params: PyTree, cfg: TransportConfig) -> dict:
    """Zero transport state for `aggregate`: 'm' — the (params-shaped f32)
    receiver momentum of uses_gamma algorithms; 'e' — blind_ec's
    (n_nodes, *leaf.shape) f32 per-node residual tree."""
    spec = resolve(algo)
    st = {}
    if spec.uses_gamma:
        st["m"] = tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if spec.error_feedback:
        st["e"] = tree_map(
            lambda p: jnp.zeros((cfg.n_nodes,) + p.shape, jnp.float32),
            params)
    return st


def step_key(base_key: Array, step, mc_steps: Optional[int] = None) -> Array:
    """This step's slot key. Default: `fold_in(base_key, step)` (the
    training stack's schedule — any horizon, O(1) per step). With
    `mc_steps`, replay the engine's `split(jax.random.key(seed), steps)`
    schedule instead: threefry's split-element streams depend on the TOTAL
    split count, so engine-parity keys require the engine's full horizon
    (and O(steps) key material per step — a parity-testing mode, not a
    production schedule)."""
    if mc_steps is None:
        return jax.random.fold_in(base_key, step)
    return jax.random.split(base_key, mc_steps)[step]


def lookahead_params(algo: str, params: PyTree, state: Optional[dict],
                     cfg: TransportConfig) -> PyTree:
    """Nesterov lookahead θ_eval = θ − βγm (the engine's gradient
    evaluation point); identity for every other algorithm."""
    spec = resolve(algo)
    if not spec.nesterov or not state or "m" not in state:
        return params
    la = cfg.stepsize * cfg.gamma
    return tree_map(
        lambda p, m: (p.astype(jnp.float32) - la * m).astype(p.dtype),
        params, state["m"])


def add_tree_noise(grads: PyTree, key: Array, std, noise_dtype=jnp.float32
                   ) -> PyTree:
    """Per-leaf i.i.d. normal noise with scalar std: leaf keys come from
    `split(key, n_leaves)` so the tree structure defines the stream
    (SPMD-safe: same key on every device draws identical noise). The
    single definition behind `gbma.perturb_gradients` and the fdm
    training baseline — bit-compatible with both."""
    leaves, treedef = tree_flatten(grads)
    keys = jax.random.split(key, len(leaves))
    noisy = [
        (g + std * jax.random.normal(k, g.shape, dtype=noise_dtype)
         .astype(g.dtype))
        for g, k in zip(leaves, keys)
    ]
    return tree_unflatten(treedef, noisy)


# --------------------------------------------------------------------------
# internals
# --------------------------------------------------------------------------
def _params_dict(cfg: TransportConfig) -> dict:
    """The traced scalar params a slot fn reads — the single-row analogue
    of the engine's ChannelBatch params."""
    ch = cfg.channel
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return {
        "scale": f32(ch.scale),
        "noise_std": f32(ch.noise_std),
        "energy": f32(ch.energy),
        "phase_error_max": f32(ch.phase_error_max),
        "rician_k": f32(ch.rician_k),
        "n_nodes": f32(cfg.n_nodes),
    }


def make_ctx(cfg: TransportConfig, spec: AlgoSpec) -> SlotCtx:
    """The SlotCtx of one transport slot (full node count, no padding)."""
    if spec.blind and cfg.n_antennas is None:
        raise ValueError(
            f"{spec.name!r} needs TransportConfig.n_antennas (the edge "
            "antenna count M)")
    n = cfg.n_nodes
    return SlotCtx(
        fading=cfg.channel.fading, p=_params_dict(cfg),
        mask=jnp.ones((n,), jnp.float32), n_sizes=(n,),
        n_antennas=cfg.n_antennas, m_sizes=(),
        invert_channel=cfg.invert_channel, h_min=cfg.h_min,
        ota_impl=resolve_ota_impl(cfg.ota_impl),
        phase_zero=(cfg.channel.phase_error_max == 0.0))


def _flat_leaves(grads: PyTree, n: int) -> Tuple[list, list, Any]:
    leaves, treedef = tree_flatten(grads)
    if not leaves:
        raise ValueError("aggregate() needs a non-empty gradient tree")
    for g in leaves:
        if g.ndim < 1 or g.shape[0] != n:
            raise ValueError(
                f"every gradient leaf needs a leading node axis of length "
                f"n_nodes={n}; got leaf shape {g.shape}")
    flat = [g.reshape(n, -1) for g in leaves]
    sizes = [f.shape[1] for f in flat]
    return flat, sizes, treedef


def _block_ranges(sizes: list, block_d: Optional[int]) -> list:
    """(leaf_idx, lo, hi, flat_lo) column tiles; flat_lo is the leaf's
    offset in the concatenated D axis (the draw-stream coordinate)."""
    out, off = [], 0
    for li, sz in enumerate(sizes):
        width = sz if block_d is None else max(1, int(block_d))
        for lo in range(0, sz, width):
            out.append((li, lo, min(lo + width, sz), off))
        off += sz
    return out


def aggregate(
    algo: str,
    node_grads: PyTree,  # leaves (n_nodes, *shape): per-node local grads
    key: Array,  # this slot's key (one per step; see `step_key`)
    cfg: TransportConfig,
    state: Optional[dict] = None,
) -> Tuple[PyTree, Optional[dict], dict]:
    """One MAC slot over a gradient pytree: returns
    `(update, new_state, aux)`.

    `update` is the received update v (or the momentum carry m for
    uses_gamma algorithms) as an f32 tree shaped like one node's
    gradients — feed it to the optimizer (`gd(β)` reproduces the engine's
    θ ← θ − βm step rule). `state` must come from `init_state` for
    stateful algorithms (`has_state`) and is returned updated; stateless
    algorithms accept and return None. `aux['tx_energy']` is the slot's
    transmitted energy E_N Σ_n ‖x_n‖² of the actually-transmitted vectors
    (after blind_ec's truncation, before any transmit-dtype cast —
    matching the engine's accounting).

    Tiling: per-coordinate slot semantics + one full-D draw
    materialization make every `block_d` choice value-identical up to f32
    reduction-order reassociation in the node superposition — a few ulp,
    pinned <= 1e-6 by the tests (see module docstring).
    Algorithms registered WITHOUT a `hoist_draws` twin cannot be
    column-tiled (their in-slot draws would repeat per block), so any
    random twin-less algorithm runs as one FULL_CONCAT slot;
    `centralized` (draw-free) tiles normally.
    """
    spec = resolve(algo)
    n = cfg.n_nodes
    ctx = make_ctx(cfg, spec)
    flat, sizes, treedef = _flat_leaves(node_grads, n)
    total_d = sum(sizes)

    if spec.uses_gamma or spec.error_feedback:
        if state is None or (spec.uses_gamma and "m" not in state) \
                or (spec.error_feedback and "e" not in state):
            raise ValueError(
                f"{algo!r} carries transport state — pass "
                "transport.init_state(algo, params, cfg) and thread the "
                "returned state")
    new_state = dict(state) if state else None

    # ---- error feedback: residual add + power-budget truncation --------
    # α is a per-node GLOBAL norm over the full D vector — the one slot
    # quantity that is not per-coordinate, so it is computed here across
    # all leaves before any block is transmitted (engine scan-body
    # semantics: u = g + e; α = min(1, √(B/max(‖u‖², 1e-30)));
    # x = α u; e ← u − x).
    if spec.error_feedback:
        e_leaves = tree_flatten(state["e"])[0]
        u = [f.astype(jnp.float32) + e.reshape(n, -1)
             for f, e in zip(flat, e_leaves)]
        sq = sum(jnp.sum(x * x, axis=1) for x in u)  # (n,)
        alpha = jnp.minimum(1.0, jnp.sqrt(
            jnp.float32(cfg.power_budget) / jnp.maximum(sq, 1e-30)))
        tx = [alpha[:, None] * x for x in u]
        new_state["e"] = tree_unflatten(treedef, [
            (x - t).reshape(e.shape)
            for x, t, e in zip(u, tx, e_leaves)])
    else:
        tx = flat

    aux = {"tx_energy": cfg.channel.energy * sum(
        jnp.sum(x.astype(jnp.float32) ** 2) for x in tx)}

    if cfg.transmit_dtype is not None and algo != "centralized":
        tx = [x.astype(cfg.transmit_dtype) for x in tx]

    # ---- one full-D draw materialization (the tiling enabler) ----------
    if spec.hoist_draws is not None:
        draws = spec.hoist_draws(key[None], ctx, n, total_d)
        draws = tree_map(lambda a: a[0], draws)
        ctx = dataclasses.replace(ctx, draws=draws)

    # ---- block-tiled slot evaluation -----------------------------------
    block_d = cfg.block_d
    if spec.hoist_draws is None and algo != "centralized":
        block_d = FULL_CONCAT  # random twin-less algo: single slot call
    if block_d == FULL_CONCAT:
        g_full = tx[0] if len(tx) == 1 else jnp.concatenate(tx, axis=1)
        v = slot_update_block(algo, g_full, key, ctx, 0,
                              total_d).astype(jnp.float32)
        parts, off = [], 0
        for sz in sizes:
            parts.append(v[off:off + sz])
            off += sz
    else:
        parts = [[] for _ in sizes]
        for li, lo, hi, flat_lo in _block_ranges(sizes, block_d):
            v_blk = slot_update_block(algo, tx[li][:, lo:hi], key, ctx,
                                      flat_lo + lo, flat_lo + hi)
            parts[li].append(v_blk.astype(jnp.float32))
        parts = [ps[0] if len(ps) == 1 else jnp.concatenate(ps)
                 for ps in parts]

    v_leaves = [p.reshape(g.shape[1:]) for p, g in
                zip(parts, tree_flatten(node_grads)[0])]
    v_tree = tree_unflatten(treedef, v_leaves)

    # ---- receiver momentum carry (engine: m ← γm + v, update = m) ------
    if spec.uses_gamma:
        m_new = tree_map(lambda m, v_: cfg.gamma * m + v_,
                         state["m"], v_tree)
        new_state["m"] = m_new
        return m_new, new_state, aux
    return v_tree, new_state, aux
