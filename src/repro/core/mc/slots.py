"""Per-slot algorithm updates behind an open registry.

One MAC slot maps the transmitted per-node vectors (n_max, d) to the
received update (d,). Each algorithm registers a `slot_fn(g, key, ctx)`
via `register_algo(...)` together with the flags the engine needs
(momentum/Nesterov/error-feedback carries, antenna requirements, gain
hoisting, Theorem-1 applicability); `ALGOS` is derived from the registry,
and the old `_slot_update` if-chain is now a table lookup. Adding an
algorithm is a registration — the engine (`mc/engine.py`) builds its
dispatch switch and scan carries from the flags.

The `SlotCtx` bundles everything a slot sees besides the transmitted
vectors and the slot key: the static compile choices (fading family, node
and antenna size grids, `invert_channel`, `h_min`, the OTA kernel impl)
plus the row's traced params `p` and mask. RNG notes live on each slot fn
— the split orders mirror the reference simulators exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.mc.sampling import (
    _antenna_keys,
    _magnitude_m2,
    _row_complex_gains,
    _row_gains,
    _row_normal,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SlotCtx:
    """Slot-call context: static engine choices + this row's traced params.

    p:        traced per-row params (channel scalars, n_nodes, flags).
    mask:     (n_max,) validity mask of the padded node axis.
    n_sizes:  distinct true node counts in the batch (static).
    n_antennas: static broadcast antenna count (None = single antenna,
              RNG-identical to `GBMASimulator`).
    m_sizes:  distinct per-row antenna counts (static; empty = broadcast).
    ota_impl: a resolved `resolve_ota_impl` value: 'inline' (engine
              einsum), or 'pallas' / 'interpret' / 'ref' to route the OTA
              superposition through `repro.kernels.ota`.
    phase_zero: static promise that every row's phase_error_max is 0 —
              lets the hoisted draw twins skip the precoded-phase stream
              (value-identical; see `sampling._sample_gains`).
    draws:    this slot's pre-materialized draw dict under the execution
              layer's hoisted RNG plan (`mc/exec.py`) — the per-step slice
              of whatever this algorithm's `hoist_draws` returned. None =
              draw from the slot key inside the slot fn (inscan plan, or
              an algorithm registered without a hoist twin).
    """

    fading: str
    p: dict
    mask: Array
    n_sizes: tuple
    n_antennas: Optional[int]
    m_sizes: tuple
    invert_channel: bool
    h_min: float
    ota_impl: str = "inline"
    phase_zero: bool = False
    draws: Optional[dict] = None


OTA_IMPLS = ("auto", "inline", "pallas", "interpret", "ref")


def resolve_ota_impl(ota_impl: str, n_sizes: tuple = (1,)) -> str:
    """The one routing rule for the single-antenna OTA superposition,
    shared by the engine and the transport layer.

    'auto' → 'pallas' on TPU when the kernel applies, 'inline' otherwise.
    'pallas' compiles the Mosaic TPU kernel and is refused off-TPU;
    'interpret' runs the same kernel in the Pallas interpreter (tests on
    CPU ask for it explicitly); 'ref' is the kernel's jnp oracle. The
    kernel normalizes by a STATIC node count, so it only applies when
    every row transmits at the same (full, unpadded) N — an explicit
    kernel impl on a padded node-count sweep is an error rather than a
    silent wrong normalization.
    """
    if ota_impl not in OTA_IMPLS:
        raise ValueError(
            f"ota_impl must be one of {OTA_IMPLS}, got {ota_impl!r}")
    eligible = len(n_sizes) == 1
    on_tpu = jax.default_backend() == "tpu"
    if ota_impl == "auto":
        return "pallas" if (eligible and on_tpu) else "inline"
    if ota_impl != "inline" and not eligible:
        raise ValueError(
            f"ota_impl={ota_impl!r} needs a single node count per call "
            f"(got n_sizes={n_sizes}): the OTA kernel normalizes by the "
            "static N, which a padded node-count sweep does not have")
    if ota_impl == "pallas" and not on_tpu:
        raise ValueError(
            "ota_impl='pallas' compiles the TPU kernel, but the backend is "
            f"{jax.default_backend()!r}; use 'interpret' to run the kernel "
            "in the Pallas interpreter")
    return ota_impl


@dataclasses.dataclass(frozen=True)
class AlgoSpec:
    """One registered algorithm.

    slot_fn(g, key, ctx) -> (d,) received update for transmitted g.
    ota:            receives the OTA superposition of Eq. (8) (the MAC
                    slot is shared) — the old `_OTA_ALGOS` membership.
    blind:          no-CSI transmitter family (M-antenna MRC edge) — the
                    old `_BLIND_ALGOS`; requires `n_antennas`.
    uses_gamma:     row takes the `run_mc(momentum=)` coefficient (the
                    momentum carry is universal; γ=0 rows reduce to
                    vanilla GD bit-exactly).
    nesterov:       gradient evaluated at the lookahead θ − βγm.
    error_feedback: row carries the per-node residual + power-budget
                    truncation in the scan (`blind_ec` semantics).
    hoist_draws(step_keys, ctx, n_max, d) -> dict: the algorithm's draw
                    twin for the execution layer's hoisted RNG plan
                    (`mc/exec.py`): materializes every random stream the
                    slot fn consumes for ALL steps at once — key-split
                    order identical to the in-scan draws — returning a
                    dict of (steps, ...) arrays whose per-step slices
                    arrive back as `ctx.draws`. None = the algorithm only
                    runs its in-scan draw path (the hoisted plan passes
                    `draws=None` for it).
    theorem1:       the Theorem-1 bound applies (single-antenna precoded
                    GBMA — the setting the theorem covers).
    """

    name: str
    slot_fn: Callable[[Array, Array, SlotCtx], Array]
    ota: bool = False
    blind: bool = False
    uses_gamma: bool = False
    nesterov: bool = False
    error_feedback: bool = False
    hoist_draws: Optional[Callable] = None
    theorem1: bool = False


ALGO_REGISTRY: dict = {}  # name -> AlgoSpec, insertion-ordered


def register_algo(name: str,
                  slot_fn: Callable[[Array, Array, SlotCtx], Array],
                  *, ota: bool = False, blind: bool = False,
                  uses_gamma: bool = False, nesterov: bool = False,
                  error_feedback: bool = False,
                  hoist_draws: Optional[Callable] = None,
                  theorem1: bool = False,
                  overwrite: bool = False) -> AlgoSpec:
    """Register a per-slot algorithm under `name` (the `run_mc(algo=)`
    value). Returns the spec; `ALGOS` updates automatically. Algorithms
    registered without a `hoist_draws` twin still run under the hoisted
    RNG plan — they just keep drawing inside the scan."""
    if name in ALGO_REGISTRY and not overwrite:
        raise ValueError(f"algo {name!r} is already registered "
                         "(pass overwrite=True to replace it)")
    spec = AlgoSpec(name=name, slot_fn=slot_fn, ota=ota, blind=blind,
                    uses_gamma=uses_gamma, nesterov=nesterov,
                    error_feedback=error_feedback,
                    hoist_draws=hoist_draws,
                    theorem1=theorem1)
    ALGO_REGISTRY[name] = spec
    return spec


def __getattr__(name: str):
    # live views derived from the registry, so late registrations show up
    if name == "ALGOS":
        return tuple(ALGO_REGISTRY)
    if name == "_OTA_ALGOS":
        return tuple(n for n, s in ALGO_REGISTRY.items() if s.ota)
    if name == "_BLIND_ALGOS":
        return tuple(n for n, s in ALGO_REGISTRY.items() if s.blind)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# --------------------------------------------------------------------------
# slot implementations (mirror the reference simulators' RNG usage)
#
# Each slot fn consumes `ctx.draws` when the hoisted RNG plan supplied it
# and falls back to drawing from the slot key otherwise; each family's
# `*_hoist_draws` twin vmaps the SAME draw code over the step keys, so the
# two plans are value-identical by construction.
# --------------------------------------------------------------------------
def _ctx_with_draws(ctx: SlotCtx, draws) -> SlotCtx:
    return dataclasses.replace(ctx, draws=draws)


def _step_antenna_keys(key: Array, ctx: SlotCtx) -> Array:
    """One slot's antenna keys: the per-row counts-as-data replay when the
    antenna count is row data, the plain split for a static M."""
    if ctx.m_sizes:
        return _antenna_keys(key, ctx.m_sizes)
    return jax.random.split(key, ctx.n_antennas)


def _gains_deterministic(ctx: SlotCtx) -> bool:
    """Whether this batch's precoded gains consume NO randomness (equal
    fading with the phase stream statically zero): the hoist twins then
    leave 'h' out of the draw dict — materializing a (steps, n_max)
    broadcast buffer is pure memory traffic — and the slot fns recompute
    the broadcast inline, which is value-identical by definition."""
    return ctx.fading == "equal" and ctx.phase_zero


def _deterministic_gains(key: Array, ctx: SlotCtx, n_max: int) -> Array:
    """The inline (n_max,) gain vector for `_gains_deterministic` batches,
    multiplied by the validity mask to make it an OPAQUE operand: without
    that, XLA sees a scalar broadcast and lowers the slot superposition as
    an unvectorized full reduce (measured ~2.4x slower than the matvec at
    N=4096) instead of the matvec every random h takes. The multiply is
    bit-exact: valid lanes hold exactly 1.0 (h·1 == h), and padded lanes
    are exactly 0 on both sides (the padded samplers zero-pad).
    (`lax.optimization_barrier` would be the canonical tool, but it has no
    vmap batching rule on the supported JAX range.)"""
    h = _row_gains(key, ctx.fading, ctx.p, ctx.n_sizes, n_max,
                   ctx.phase_zero)
    return h * ctx.mask


def _ota_draw(key: Array, ctx: SlotCtx, n_max: int, d: int) -> dict:
    """One OTA slot's draws — the k → (k_h, k_w) chain of `_ota_slot`:
    the (n_max,) channel gains and the (d,) edge noise."""
    k_h, k_w = jax.random.split(key)
    out = {"w": jax.random.normal(k_w, (d,), jnp.float32)}
    if not _gains_deterministic(ctx):
        out["h"] = _row_gains(k_h, ctx.fading, ctx.p, ctx.n_sizes, n_max,
                              ctx.phase_zero)
    return out


def _ota_slot(g: Array, key: Array, ctx: SlotCtx) -> Array:
    """Single-antenna OTA superposition (Eq. 8): v = (1/N) Σ h_n g_n + w.

    slot key → (k_h, k_w); k_h draws the (n_max,) gains unless the hoisted
    plan supplied them (`ctx.draws`), k_w the (d,) edge noise —
    split-for-split identical to `gbma.ota_aggregate`. With
    `ctx.ota_impl != 'inline'` the superposition + noise-add routes through
    the tiled `repro.kernels.ota.ota_edge_aggregate` kernel: compiled for
    the TPU ('pallas'), run by the Pallas interpreter ('interpret'), or the
    jnp oracle ('ref'); the traced noise std folds into the noise operand
    so the kernel's static `noise_scale` stays 1.
    """
    p = ctx.p
    if ctx.draws is not None:
        w = ctx.draws["w"]
        h = ctx.draws.get("h")
        if h is None:  # deterministic gains were (rightly) not hoisted
            h = _deterministic_gains(key, ctx, g.shape[0])
    else:
        k_h, k_w = jax.random.split(key)
        h = _row_gains(k_h, ctx.fading, p, ctx.n_sizes, g.shape[0])
        w = jax.random.normal(k_w, (g.shape[1],), dtype=g.dtype)
    std = p["noise_std"] / (p["n_nodes"] * jnp.sqrt(p["energy"]))
    if ctx.ota_impl != "inline":
        from repro.kernels.ota.ops import ota_edge_aggregate

        # valid only when every row transmits at the full static node count
        # (run_mc enforces this): the kernel normalizes by the static N.
        # out_dtype matches the inline einsum's promotion (f32 gains x g),
        # so bf16-transmit blocks still emit an f32 received update.
        return ota_edge_aggregate(g, h, std * w, noise_scale=1.0,
                                  impl="ref" if ctx.ota_impl == "ref"
                                  else "pallas",
                                  interpret=ctx.ota_impl == "interpret",
                                  out_dtype=jnp.promote_types(
                                      g.dtype, jnp.float32))
    v = jnp.einsum("n,nd->d", h, g) / p["n_nodes"]
    return v + std * w


def _gbma_hoist_draws(step_keys: Array, ctx: SlotCtx, n_max: int,
                      d: int) -> dict:
    """All-steps draw twin of `_gbma_slot`: single-antenna slots hoist to
    {'h': (steps, n_max), 'w': (steps, d)}; antenna paths (static M or
    per-row counts) insert an antenna axis after steps."""
    if ctx.n_antennas is None and not ctx.m_sizes:
        return jax.vmap(lambda k: _ota_draw(k, ctx, n_max, d))(step_keys)
    return jax.vmap(lambda k: jax.vmap(
        lambda ak: _ota_draw(ak, ctx, n_max, d))(
            _step_antenna_keys(k, ctx)))(step_keys)


def _gbma_slot(g: Array, key: Array, ctx: SlotCtx) -> Array:
    """Precoded OTA aggregation, shared by gbma/momentum/nesterov.

    n_antennas=None: single-antenna edge, RNG-identical to `GBMASimulator`.
    An integer (1 included) takes the MRC path of
    `ota_aggregate_multiantenna`, whose extra key split changes the stream
    even for M=1 — mirrored so fixed seeds reproduce exactly. Per-row
    counts (m_sizes) take the masked-MRC path: each row consumes exactly
    the first m of its replayed split(key, m).
    """
    p = ctx.p
    if ctx.m_sizes or ctx.n_antennas is not None:
        if ctx.draws is not None:
            v = jax.vmap(lambda dr: _ota_slot(
                g, key, _ctx_with_draws(ctx, dr)))(ctx.draws)
        else:
            v = jax.vmap(lambda k: _ota_slot(g, k, ctx))(
                _step_antenna_keys(key, ctx))
        if ctx.m_sizes:
            amask = (jnp.arange(v.shape[0])
                     < p["n_antennas"]).astype(v.dtype)
            return jnp.einsum("m,md->d", amask, v) / p["n_antennas"]
        return jnp.mean(v, axis=0)
    return _ota_slot(g, key, ctx)


def _centralized_slot(g: Array, key: Array, ctx: SlotCtx) -> Array:
    """Noiseless benchmark GD: the slot key is unused (and there is no
    hoist twin — nothing random to hoist)."""
    return jnp.sum(g, axis=0) / ctx.p["n_nodes"]


def _blind_antenna_draw(key: Array, ctx: SlotCtx, n_max: int,
                        d: int) -> dict:
    """One antenna's draw chain in `_blind_slot` — k → (k_h, k_w): the
    complex gain parts (a, b) and the stacked real/imag edge noise."""
    k_h, k_w = jax.random.split(key)
    a, b = _row_complex_gains(k_h, ctx.fading, ctx.p, ctx.n_sizes, n_max)
    return {"a": a, "b": b,
            "z": jax.random.normal(k_w, (2, d), jnp.float32)}


def _blind_hoist_draws(step_keys: Array, ctx: SlotCtx, n_max: int,
                       d: int) -> dict:
    """All-steps draw twin of `_blind_slot`: (steps, m, ...) complex-gain
    and edge-noise streams (m = static M or the padded per-row axis)."""
    return jax.vmap(lambda k: jax.vmap(
        lambda ak: _blind_antenna_draw(ak, ctx, n_max, d))(
            _step_antenna_keys(k, ctx)))(step_keys)


def _blind_slot(g: Array, key: Array, ctx: SlotCtx) -> Array:
    """Blind transmitters (1907.03909): nodes send g uncoded; antenna m
    receives y_m = Σ_n h~_{n,m} g_n + z~_m (complex); the edge MRC-
    combines with receiver CSI, normalized by M·E[h²] — mirrors
    `gbma.blind_ota_aggregate` split-for-split."""
    p = ctx.p
    n_max = g.shape[0]
    m2 = _magnitude_m2(ctx.fading, p)
    std = p["noise_std"] / jnp.sqrt(p["energy"])

    def combine(a, b, z):
        y_r = jnp.einsum("n,nd->d", a, g) + std * z[0]
        y_i = jnp.einsum("n,nd->d", b, g) + std * z[1]
        return jnp.sum(a) * y_r + jnp.sum(b) * y_i

    def antenna(k):
        dr = _blind_antenna_draw(k, ctx, n_max, g.shape[1])
        return combine(dr["a"], dr["b"], dr["z"])

    if ctx.draws is not None:
        s = jax.vmap(lambda dr: combine(dr["a"], dr["b"], dr["z"]))(
            ctx.draws)
    else:
        s = jax.vmap(antenna)(_step_antenna_keys(key, ctx))
    m_true = p["n_antennas"] if ctx.m_sizes else jnp.float32(ctx.n_antennas)
    amask = (jnp.arange(s.shape[0]) < m_true).astype(g.dtype)
    return jnp.einsum("m,md->d", amask, s) / (m_true * p["n_nodes"] * m2)


def _fdm_draw(key: Array, ctx: SlotCtx, n_max: int, d: int) -> dict:
    """`_fdm_slot`'s per-slot draws: the (n_max, d) per-node noise and —
    unless the channel is inverted (gain equalized; k_h split off but
    unconsumed, matching `baselines.FDMGD`) — the (n_max,) gains."""
    p = ctx.p
    k_h, k_w = jax.random.split(key)
    out = {"noise_raw": _row_normal(k_w, p, ctx.n_sizes, n_max, d,
                                    jnp.float32)}
    if not ctx.invert_channel and not _gains_deterministic(ctx):
        out["h"] = _row_gains(k_h, ctx.fading, p, ctx.n_sizes, n_max,
                              ctx.phase_zero)
    return out


def _fdm_hoist_draws(step_keys: Array, ctx: SlotCtx, n_max: int,
                     d: int) -> dict:
    return jax.vmap(lambda k: _fdm_draw(k, ctx, n_max, d))(step_keys)


def _fdm_slot(g: Array, key: Array, ctx: SlotCtx) -> Array:
    """Orthogonal-channel GD: independent per-node (d,) noise; with
    `invert_channel` the gain is equalized (k_h split off but unconsumed,
    matching `baselines.FDMGD`)."""
    p = ctx.p
    n_max = g.shape[0]
    if ctx.draws is not None:
        raw = ctx.draws["noise_raw"]
        h = ctx.draws.get("h")
        if h is None and not ctx.invert_channel:
            h = _deterministic_gains(key, ctx, n_max)
    else:
        k_h, k_w = jax.random.split(key)
        raw = _row_normal(k_w, p, ctx.n_sizes, n_max, g.shape[1], g.dtype)
        h = None
        if not ctx.invert_channel:
            h = _row_gains(k_h, ctx.fading, p, ctx.n_sizes, n_max)
    noise = p["noise_std"] / jnp.sqrt(p["energy"]) * raw
    if ctx.invert_channel:
        rx = g + noise
    else:
        rx = h[:, None] * g + noise
    return jnp.sum(rx * ctx.mask[:, None], axis=0) / p["n_nodes"]


def _pc_draw(key: Array, ctx: SlotCtx, n_max: int, d: int) -> dict:
    """`_power_control_slot`'s per-slot draws: gains + (d,) edge noise."""
    k_h, k_w = jax.random.split(key)
    out = {"w": jax.random.normal(k_w, (d,), jnp.float32)}
    if not _gains_deterministic(ctx):
        out["h"] = _row_gains(k_h, ctx.fading, ctx.p, ctx.n_sizes, n_max,
                              ctx.phase_zero)
    return out


def _pc_hoist_draws(step_keys: Array, ctx: SlotCtx, n_max: int,
                    d: int) -> dict:
    return jax.vmap(lambda k: _pc_draw(k, ctx, n_max, d))(step_keys)


def _power_control_slot(g: Array, key: Array, ctx: SlotCtx) -> Array:
    """CA-DSGD-style truncated channel inversion [11]: nodes below `h_min`
    stay silent; the active set inverts its gains."""
    p = ctx.p
    n_max = g.shape[0]
    if ctx.draws is not None:
        w_raw = ctx.draws["w"]
        h = ctx.draws.get("h")
        if h is None:  # deterministic gains were (rightly) not hoisted
            h = _deterministic_gains(key, ctx, n_max)
    else:
        k_h, k_w = jax.random.split(key)
        h = _row_gains(k_h, ctx.fading, p, ctx.n_sizes, n_max)
        w_raw = jax.random.normal(k_w, (g.shape[1],), dtype=g.dtype)
    active = (h >= ctx.h_min).astype(g.dtype) * ctx.mask
    n_active = jnp.maximum(jnp.sum(active), 1.0)
    sup = jnp.einsum("n,nd->d", active, g)
    w = p["noise_std"] / (n_active * jnp.sqrt(p["energy"])) * w_raw
    return sup / n_active + w


# --------------------------------------------------------------------------
# built-in registrations (order defines the historical ALGOS tuple)
# --------------------------------------------------------------------------
register_algo("gbma", _gbma_slot, ota=True,
              hoist_draws=_gbma_hoist_draws, theorem1=True)
register_algo("centralized", _centralized_slot)
register_algo("fdm", _fdm_slot, hoist_draws=_fdm_hoist_draws)
register_algo("power_control", _power_control_slot,
              hoist_draws=_pc_hoist_draws)
register_algo("momentum", _gbma_slot, ota=True, uses_gamma=True,
              hoist_draws=_gbma_hoist_draws)
register_algo("nesterov", _gbma_slot, ota=True, uses_gamma=True,
              nesterov=True, hoist_draws=_gbma_hoist_draws)
register_algo("blind", _blind_slot, blind=True,
              hoist_draws=_blind_hoist_draws)
register_algo("blind_ec", _blind_slot, blind=True, error_feedback=True,
              hoist_draws=_blind_hoist_draws)


def hoist_draw_elems(name: str, *, steps: int, n_max: int, dim: int,
                     m_live: int, invert_channel: bool) -> int:
    """f32-element count of one trajectory's hoisted draw streams for the
    named algorithm — the registry's side of the execution layer's
    analytic memory model (`exec.estimate_peak_bytes`). Lives next to
    `register_algo` so a new algorithm's `hoist_draws` twin and its
    memory footprint stay in one file. Unregistered algorithms and those
    without a hoist twin draw in-scan: 0 hoisted elements."""
    spec = ALGO_REGISTRY.get(name)
    if spec is None or spec.hoist_draws is None:
        return 0
    if spec.blind:
        # complex gain pair (m, n_max) + edge noise (m, 2, dim)
        return steps * m_live * 2 * (n_max + dim)
    if name == "fdm":
        # per-node noise (n_max, dim) + gains unless inverted
        # (the inverted channel is equalized — no gain stream)
        return steps * n_max * (dim + (0 if invert_channel else 1))
    # gbma family / power_control: gains + edge noise
    return steps * m_live * (n_max + dim)


# --------------------------------------------------------------------------
# block-shaped entry point (the channel-transport layer's tiling surface)
# --------------------------------------------------------------------------
# d-axis layout of the hoisted draw dicts: these keys carry a trailing
# axis of length d and slice per column block; every other key ('h', 'a',
# 'b') is per-node/per-antenna only and is shared by all blocks of a slot.
_DRAW_D_KEYS = ("w", "z", "noise_raw")


def slice_draws(draws: Optional[dict], lo: int, hi: int) -> Optional[dict]:
    """Column-block [lo, hi) view of one slot's draw dict.

    Slicing the d-carrying streams ('w' (d,), 'z' (..., 2, d),
    'noise_raw' (n_max, d)) on their LAST axis and passing the per-node
    streams through whole keeps block-tiled slot evaluation value-
    identical to the untiled call: every slot computation is
    per-coordinate given its draws, so coordinate c of the update depends
    only on column c of g and of the d-carrying draws. (The draws match
    bitwise; the one residual tiling artifact is XLA reassociating the
    f32 node-superposition reduction per block shape — a few ulp.)"""
    if draws is None:
        return None
    return {k: (v[..., lo:hi] if k in _DRAW_D_KEYS else v)
            for k, v in draws.items()}


def slot_update_block(algo: str, g: Array, key: Array, ctx: SlotCtx,
                      lo: int, hi: int) -> Array:
    """One column block of a slot update: `g` is the (n_max, hi-lo) block
    of the transmitted vectors, `ctx.draws` the FULL-d draw dict (sliced
    here). Requires hoisted draws for any algorithm that consumes
    randomness — re-running a slot fn's in-scan draw path per block would
    repeat the same key (correlated noise across blocks) and break the
    tiled==untiled guarantee. `repro.core.transport` enforces that."""
    spec = ALGO_REGISTRY[algo]
    if ctx.draws is None and spec.hoist_draws is not None:
        raise ValueError(
            f"slot_update_block({algo!r}) needs pre-materialized draws "
            "(ctx.draws): per-block in-slot draws would reuse the slot key "
            "across blocks")
    ctx_blk = dataclasses.replace(ctx, draws=slice_draws(ctx.draws, lo, hi))
    return spec.slot_fn(g, key, ctx_blk)


def _slot_update(g: Array, key: Array, *, algo: str, fading: str, p: dict,
                 mask: Array, n_sizes: tuple, n_antennas: Optional[int],
                 m_sizes: tuple, invert_channel: bool, h_min: float,
                 ota_impl: str = "inline") -> Array:
    """Back-compat wrapper over the registry dispatch: one MAC slot,
    transmitted per-node vectors (n_max, d) -> received update (d,).

    `g` is whatever the nodes put on the channel this slot — the masked
    local gradients for most algorithms; for `blind_ec` rows the scan body
    has already folded in the local residual and the power-budget
    truncation before calling here.

    Padded node rows carry exactly-zero vectors (the problem grad fns
    mask them) and zero-padded channel gains, so every per-node reduction
    normalizes by the row's true node count p['n_nodes'], and shaped noise
    draws (fdm) are masked before the node average.
    """
    if algo not in ALGO_REGISTRY:
        raise ValueError(
            f"unknown algo {algo!r}; expected one of {tuple(ALGO_REGISTRY)}")
    ctx = SlotCtx(fading=fading, p=p, mask=mask, n_sizes=n_sizes,
                  n_antennas=n_antennas, m_sizes=m_sizes,
                  invert_channel=invert_channel, h_min=h_min,
                  ota_impl=ota_impl)
    return ALGO_REGISTRY[algo].slot_fn(g, key, ctx)
