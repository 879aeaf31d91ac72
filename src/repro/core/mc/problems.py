"""Problem registry for the Monte Carlo engine.

An engine problem is (a) a per-node gradient map `theta -> (N, d)` and (b) a
scalar risk metric `theta -> float`, both traceable. The engine batches
problems with different node counts into one compile by padding per-node
arrays to N_max (see `MCProblemBatch`), which needs three things per problem
*kind*: row-based grad/risk functions with stable identities (the jit cache
of `_mc_core` keys on them), the per-node data fields and their pad values,
and — for stochastic problems — a minibatch gradient that draws sample
indices inside the scan.

All of that lives in the open `PROBLEMS` registry: `register_problem(...)`
replaces the hard-coded `_ROW_FNS` / `_PER_NODE_FIELDS` dicts of the old
monolith, so a new workload is a registration plus a constructor — no
engine edits. Built-ins: `quadratic` (Eq. 27), `localization` (§VI-B), and
the stochastic `logistic` (federated logistic regression on a non-iid
partition, beyond-paper Fig. 8).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ProblemSpec:
    """One registered problem kind.

    grad_row / risk_row take `(row, theta)` where `row` is the problem's
    data dict for one batch row (per-node leaves padded to N_max, plus the
    validity `row['mask']`); grad_row must return exactly-zero gradients
    for padded node rows (multiply by the mask). Identities must be stable
    (module-level functions), or every `run_mc` call recompiles the engine.

    pad_values maps each per-node data field to its pad constant — chosen
    so the padded rows stay FINITE before masking (0 * inf = nan would
    poison the row; e.g. localization pads sensor positions far away, not
    at the source).

    stochastic_grad_row, when given, makes the kind stochastic-capable:
    `(row, theta, key, b_count, b_max)` draws a size-`b_max` minibatch of
    per-node sample indices from `key` inside the scan, uses the first
    `b_count` (traced, per-row — an int32 lane count) lanes, and returns
    the minibatch gradient. `sample_axis_field` names the data field whose
    axis 1 is the per-node sample axis (sets the full-batch size the
    `batch_frac` knob scales).

    sample_indices_row / stochastic_grad_from_idx optionally split the
    stochastic gradient into its index draw (`(row, key, b_max) ->
    (n_max, b_max)` int indices) and the gradient over given indices
    (`(row, theta, idx, b_count)`), with stochastic_grad_row ≡ their
    composition. The split lets the execution layer's hoisted RNG plan
    (`mc/exec.py`) materialize the minibatch-index stream outside the
    scan like every channel stream; kinds registered without it simply
    keep drawing indices in-scan.
    """

    kind: str
    grad_row: Callable[[dict, Array], Array]
    risk_row: Callable[[dict, Array], Array]
    pad_values: dict
    stochastic_grad_row: Optional[Callable] = None
    sample_axis_field: Optional[str] = None
    sample_indices_row: Optional[Callable] = None
    stochastic_grad_from_idx: Optional[Callable] = None


PROBLEMS: dict = {}  # kind -> ProblemSpec, insertion-ordered


def register_problem(
    kind: str,
    grad_row: Callable[[dict, Array], Array],
    risk_row: Callable[[dict, Array], Array],
    pad_values: dict,
    *,
    stochastic_grad_row: Optional[Callable] = None,
    sample_axis_field: Optional[str] = None,
    sample_indices_row: Optional[Callable] = None,
    stochastic_grad_from_idx: Optional[Callable] = None,
    overwrite: bool = False,
) -> ProblemSpec:
    """Register a problem kind so library-built `MCProblem`s of that kind
    stack into padded node-count sweeps (and, with `stochastic_grad_row`,
    run minibatch SGD inside the scan — plus hoisted index draws when the
    `sample_indices_row`/`stochastic_grad_from_idx` split is supplied).
    Returns the spec."""
    if kind in PROBLEMS and not overwrite:
        raise ValueError(f"problem kind {kind!r} is already registered "
                         "(pass overwrite=True to replace it)")
    if (stochastic_grad_row is None) != (sample_axis_field is None):
        raise ValueError("stochastic_grad_row and sample_axis_field must "
                         "be given together")
    if (sample_indices_row is None) != (stochastic_grad_from_idx is None):
        raise ValueError("sample_indices_row and stochastic_grad_from_idx "
                         "must be given together")
    if sample_indices_row is not None and stochastic_grad_row is None:
        raise ValueError("the index/gradient split refines a "
                         "stochastic_grad_row; register that too")
    spec = ProblemSpec(kind=kind, grad_row=grad_row, risk_row=risk_row,
                       pad_values=dict(pad_values),
                       stochastic_grad_row=stochastic_grad_row,
                       sample_axis_field=sample_axis_field,
                       sample_indices_row=sample_indices_row,
                       stochastic_grad_from_idx=stochastic_grad_from_idx)
    PROBLEMS[kind] = spec
    return spec


# --------------------------------------------------------------------------
# problem containers
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MCProblem:
    """On-device problem: per-node gradients plus a scalar risk metric.

    grad_fn: theta (d,) -> (N, d) all nodes' local gradients.
    risk_fn: theta (d,) -> scalar excess risk / error, fully traceable.

    `kind`/`data` are filled by the library constructors
    (`quadratic_mc_problem`, `localization_mc_problem`,
    `logistic_mc_problem`) and let `MCProblemBatch.stack` pad several
    problems with different node counts into one batch. Hand-built problems
    may leave them unset; they then run on the closure path (single node
    count per call). `stochastic=True` (set when the registered kind has a
    `stochastic_grad_row`) lets `run_mc(batch_frac=...)` draw per-slot
    minibatches inside the scan.
    """

    grad_fn: Callable[[Array], Array]
    risk_fn: Callable[[Array], Array]
    dim: int
    n_nodes: int
    kind: str = ""
    data: Optional[dict] = None
    stochastic: bool = False


@dataclasses.dataclass(frozen=True)
class MCProblemBatch:
    """C problems stacked along a batch axis, node dims padded to N_max.

    data leaves carry a leading (C,) axis; per-node leaves are zero-padded
    to `n_max` and `data['mask']` (C, n_max) marks the valid rows. grad/risk
    take (row, theta) and are the registered `PROBLEMS[kind]` row fns.
    """

    kind: str
    grad_fn: Callable[[dict, Array], Array]
    risk_fn: Callable[[dict, Array], Array]
    data: dict
    n_nodes: tuple  # true node count per row (host ints)
    dim: int
    n_max: int
    stochastic: bool = False

    @classmethod
    def stack(cls, problems: Sequence[MCProblem]) -> "MCProblemBatch":
        kinds = {p.kind for p in problems}
        if len(kinds) != 1 or "" in kinds or problems[0].data is None:
            raise ValueError(
                "MCProblemBatch.stack needs library-built problems of one "
                f"kind (got kinds={sorted(kinds)}); hand-built MCProblems "
                "run on the closure path, one node count per call")
        kind = problems[0].kind
        if kind not in PROBLEMS:
            raise ValueError(
                f"problem kind {kind!r} is not registered; call "
                "register_problem(kind, grad_row, risk_row, pad_values)")
        if any(p.data is None for p in problems):
            raise ValueError(
                "every stacked problem needs a data dict (hand-built "
                "MCProblems without data run on the closure path)")
        dims = {p.dim for p in problems}
        if len(dims) != 1:
            raise ValueError(f"problems must share dim, got {sorted(dims)}")
        spec = PROBLEMS[kind]
        n_nodes = tuple(p.n_nodes for p in problems)
        n_max = max(n_nodes)
        pads = spec.pad_values
        leaves = {}
        for name in problems[0].data:
            rows = []
            for p in problems:
                leaf = p.data[name]
                if name in pads:
                    pad = [(0, n_max - p.n_nodes)] + [(0, 0)] * (leaf.ndim - 1)
                    leaf = jnp.pad(leaf, pad, constant_values=pads[name])
                rows.append(leaf)
            try:
                leaves[name] = jnp.stack(rows)
            except (ValueError, TypeError) as e:
                raise ValueError(
                    f"data field {name!r} does not stack across the batch "
                    f"(shapes {[np.shape(r) for r in rows]}); non-node "
                    "dims must match row-for-row") from e
        mask = np.zeros((len(problems), n_max), np.float32)
        for i, n in enumerate(n_nodes):
            mask[i, :n] = 1.0
        leaves["mask"] = jnp.asarray(mask)
        return cls(kind=kind, grad_fn=spec.grad_row, risk_fn=spec.risk_row,
                   data=leaves, n_nodes=n_nodes, dim=problems[0].dim,
                   n_max=n_max,
                   stochastic=any(p.stochastic for p in problems))

    def __len__(self) -> int:
        return len(self.n_nodes)

    @property
    def spec(self) -> ProblemSpec:
        return PROBLEMS[self.kind]


# --------------------------------------------------------------------------
# quadratic (regularized least squares, Eq. 27)
# --------------------------------------------------------------------------
def _quadratic_grad_row(row: dict, theta: Array) -> Array:
    resid = row["X"] @ theta - row["y"]
    g = resid[:, None] * row["X"] + row["lam"] * theta[None, :]
    return g * row["mask"][:, None]


def _quadratic_risk_row(row: dict, theta: Array) -> Array:
    diff = theta - row["theta_star"]
    return 0.5 * diff @ (row["H"] @ diff)


def quadratic_mc_problem(
    X: np.ndarray, y: np.ndarray, lam: float, theta_star: np.ndarray
) -> MCProblem:
    """Regularized least squares (Eq. 27), one sample per node.

    The excess risk uses the exact quadratic form around the minimizer:
    F(θ) - F* = 0.5 (θ-θ*)ᵀ (A + λI) (θ-θ*) with A = XᵀX/N — closed form,
    no F* cancellation, safe in f32.
    """
    n, d = X.shape
    H64 = X.T.astype(np.float64) @ X.astype(np.float64) / n + lam * np.eye(d)
    Xj, yj = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    Hj = jnp.asarray(H64, jnp.float32)
    ts = jnp.asarray(theta_star, jnp.float32)

    def grad_fn(theta):
        return (Xj @ theta - yj)[:, None] * Xj + lam * theta[None, :]

    def risk_fn(theta):
        diff = theta - ts
        return 0.5 * diff @ (Hj @ diff)

    data = {"X": Xj, "y": yj, "H": Hj, "theta_star": ts,
            "lam": jnp.float32(lam)}
    return MCProblem(grad_fn=grad_fn, risk_fn=risk_fn, dim=d, n_nodes=n,
                     kind="quadratic", data=data)


# --------------------------------------------------------------------------
# localization (paper §VI-B)
# --------------------------------------------------------------------------
def _localization_grad_row(row: dict, theta: Array) -> Array:
    diff = theta[None, :] - row["r"]
    d2 = jnp.sum(diff**2, axis=1)
    resid = row["x"] - row["signal_a"] / d2
    g = (4.0 * row["signal_a"] * resid / d2**2)[:, None] * diff
    return g * row["mask"][:, None]


def _localization_risk_row(row: dict, theta: Array) -> Array:
    return jnp.sum((theta - row["src"]) ** 2)


def localization_mc_problem(
    r: np.ndarray, x: np.ndarray, src: np.ndarray, signal_a: float
) -> MCProblem:
    """Source localization of paper §VI-B; risk = squared position error."""
    rj, xj = jnp.asarray(r, jnp.float32), jnp.asarray(x, jnp.float32)
    srcj = jnp.asarray(src, jnp.float32)

    def grad_fn(theta):
        diff = theta[None, :] - rj  # (N, 2)
        d2 = jnp.sum(diff**2, axis=1)
        resid = xj - signal_a / d2
        return (4.0 * signal_a * resid / d2**2)[:, None] * diff

    def risk_fn(theta):
        return jnp.sum((theta - srcj) ** 2)

    data = {"r": rj, "x": xj, "src": srcj, "signal_a": jnp.float32(signal_a)}
    return MCProblem(grad_fn=grad_fn, risk_fn=risk_fn, dim=2,
                     n_nodes=r.shape[0], kind="localization", data=data)


# --------------------------------------------------------------------------
# logistic (federated logistic regression, stochastic-capable — Fig. 8)
# --------------------------------------------------------------------------
def _logistic_margin(row: dict, theta: Array) -> Array:
    """y_i <x_i, θ> per (node, local sample)."""
    return row["yn"] * jnp.einsum("nkf,f->nk", row["Xn"], theta)


def _logistic_grad_row(row: dict, theta: Array) -> Array:
    """Full-batch per-node gradient of the regularized logistic loss:
    g_n = (1/k) Σ_i −σ(−m_i) y_i x_i + λ θ, masked to zero on padded
    rows."""
    k = row["Xn"].shape[1]
    coef = -jax.nn.sigmoid(-_logistic_margin(row, theta)) * row["yn"]
    g = jnp.einsum("nk,nkf->nf", coef, row["Xn"]) / jnp.float32(k)
    g = g + row["lam"] * theta[None, :]
    return g * row["mask"][:, None]


def _logistic_sample_idx_row(row: dict, key: Array, b_max: int) -> Array:
    """The logistic minibatch index draw: (n_max, b_max) with-replacement
    per-node sample indices for one slot key.

    Index entry (n, j) draws as a SCALAR from
    `fold_in(fold_in(key, j), n)` rather than one (n_max, b_max)-shaped
    draw: a shaped draw's row-major layout would make each node's
    minibatch depend on the sweep-wide b_max — per-(lane, node) scalar
    keys keep every entry identical across all sweep paddings, so
    one-compile fraction sweeps and node-count sweeps both reproduce their
    dedicated runs row-for-row (the same invariant `mc/sampling.py`
    maintains for the channel draws)."""
    n_max, k, _ = row["Xn"].shape
    nodes = jnp.arange(n_max, dtype=jnp.uint32)
    lane_keys = [jax.random.fold_in(key, j) for j in range(b_max)]
    return jnp.stack(
        [jax.vmap(lambda n, kj=kj: jax.random.randint(
            jax.random.fold_in(kj, n), (), 0, k))(nodes)
         for kj in lane_keys], axis=1)


def _logistic_sgrad_from_idx_row(row: dict, theta: Array, idx: Array,
                                 b_count: Array) -> Array:
    """Minibatch logistic gradient over ALREADY-DRAWN indices: uses the
    first `b_count` (traced int32 — the per-row `batch_frac` knob) of the
    idx lanes and averages. The lane count divides as float32 at this
    single use site; it is carried as int32 so large per-node sample
    counts survive exactly (a float32 lane count silently rounds above
    2^24)."""
    b_max = idx.shape[1]
    Xs = jnp.take_along_axis(row["Xn"], idx[:, :, None], axis=1)
    ys = jnp.take_along_axis(row["yn"], idx, axis=1)
    lane = (jnp.arange(b_max) < b_count).astype(jnp.float32)[None, :]
    m = ys * jnp.einsum("nbf,f->nb", Xs, theta)
    coef = -jax.nn.sigmoid(-m) * ys * lane
    g = jnp.einsum("nb,nbf->nf", coef, Xs) \
        / jnp.asarray(b_count, jnp.float32)
    g = g + row["lam"] * theta[None, :]
    return g * row["mask"][:, None]


def _logistic_sgrad_row(row: dict, theta: Array, key: Array,
                        b_count: Array, b_max: int) -> Array:
    """Minibatch twin of `_logistic_grad_row`: every node draws `b_max`
    with-replacement sample indices from ITS local shard (one key per
    slot), uses the first `b_count` (traced — the per-row `batch_frac`
    knob) lanes, and averages. At b_count == k this is an unbiased
    bootstrap estimate, not the full-batch gradient — the exact full-batch
    limit is the static `batch_frac == 1.0` path, which skips sampling
    entirely. Composition of the registered index/gradient split, so the
    in-scan and hoisted-index plans are value-identical."""
    idx = _logistic_sample_idx_row(row, key, b_max)
    return _logistic_sgrad_from_idx_row(row, theta, idx, b_count)


def _logistic_risk_row(row: dict, theta: Array) -> Array:
    """Excess risk F(θ) − F* of the GLOBAL objective: masked mean of
    log(1 + e^{−m}) over the row's true N·k samples plus the L2 term,
    minus the host-side F* (f64 Newton, stored in the data)."""
    loss = jnp.logaddexp(jnp.float32(0.0), -_logistic_margin(row, theta))
    w = row["mask"][:, None]
    n_samples = jnp.sum(row["mask"]) * row["Xn"].shape[1]
    f = jnp.sum(loss * w) / n_samples \
        + 0.5 * row["lam"] * jnp.sum(theta * theta)
    return f - row["f_star"]


def _logistic_solve(X: np.ndarray, y: np.ndarray, lam: float,
                    iters: int = 60) -> tuple:
    """Host-side f64 Newton solve of the regularized logistic objective;
    returns (theta_star, f_star)."""
    n, d = X.shape
    theta = np.zeros(d, np.float64)
    for _ in range(iters):
        m = y * (X @ theta)
        s = 1.0 / (1.0 + np.exp(m))  # σ(−m)
        grad = -(X.T @ (s * y)) / n + lam * theta
        w = s * (1.0 - s)
        H = (X.T * w) @ X / n + lam * np.eye(d)
        step = np.linalg.solve(H, grad)
        theta = theta - step
        if np.linalg.norm(step) < 1e-12:
            break
    f_star = float(np.mean(np.logaddexp(0.0, -y * (X @ theta)))
                   + 0.5 * lam * np.sum(theta**2))
    return theta, f_star


def logistic_mc_problem(
    X: np.ndarray, y: np.ndarray, n_nodes: int, lam: float = 0.1,
    *, noniid: bool = True,
) -> MCProblem:
    """Federated logistic regression on a label-sorted (non-iid) partition.

    The global batch is partitioned into `n_nodes` equal shards via
    `repro.data.federated` — label-sorted first when `noniid=True`, so each
    node's local distribution is skewed (the federated-SGD setting of
    Amiri & Gündüz, arXiv:1907.09769). Labels are ±1. The risk is the
    global excess objective F(θ) − F*, with F* from a host-side f64 Newton
    solve. The kind is stochastic-capable: `run_mc(batch_frac=...)` draws
    per-slot local minibatches inside the scan.
    """
    from repro.data.federated import partition_noniid, partition_rows

    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    if not np.all(np.abs(y) == 1.0):
        raise ValueError("logistic labels must be ±1")
    parts = (partition_noniid(X, y, n_nodes) if noniid
             else partition_rows(X, y, n_nodes))
    k = parts[0][0].shape[0]
    if any(px.shape[0] != k for px, _ in parts):
        raise ValueError(
            f"samples ({X.shape[0]}) must split evenly over {n_nodes} nodes")
    theta_star, f_star = _logistic_solve(X, y, lam)
    Xn = jnp.asarray(np.stack([px for px, _ in parts]), jnp.float32)
    yn = jnp.asarray(np.stack([py for _, py in parts]), jnp.float32)
    d = X.shape[1]
    data = {"Xn": Xn, "yn": yn, "lam": jnp.float32(lam),
            "f_star": jnp.float32(f_star),
            "theta_star": jnp.asarray(theta_star, jnp.float32)}
    full_mask = {"mask": jnp.ones((n_nodes, 1), jnp.float32)[:, 0]}

    def grad_fn(theta):
        return _logistic_grad_row({**data, **full_mask}, theta)

    def risk_fn(theta):
        return _logistic_risk_row({**data, **full_mask}, theta)

    return MCProblem(grad_fn=grad_fn, risk_fn=risk_fn, dim=d,
                     n_nodes=n_nodes, kind="logistic", data=data,
                     stochastic=True)


# --------------------------------------------------------------------------
# built-in registrations
# --------------------------------------------------------------------------
# Localization sensor positions pad far from the search region so the
# padded rows' 1/d² terms stay finite (they are masked to zero afterwards,
# but inf·0 would poison the row).
register_problem("quadratic", _quadratic_grad_row, _quadratic_risk_row,
                 {"X": 0.0, "y": 0.0})
register_problem("localization", _localization_grad_row,
                 _localization_risk_row, {"r": 1.0e6, "x": 0.0})
register_problem("logistic", _logistic_grad_row, _logistic_risk_row,
                 {"Xn": 0.0, "yn": 0.0},
                 stochastic_grad_row=_logistic_sgrad_row,
                 sample_axis_field="Xn",
                 sample_indices_row=_logistic_sample_idx_row,
                 stochastic_grad_from_idx=_logistic_sgrad_from_idx_row)


def _per_node_fields() -> dict:
    """Back-compat view of the old `_PER_NODE_FIELDS` dict (kind -> pad
    values), derived from the registry."""
    return {kind: dict(spec.pad_values) for kind, spec in PROBLEMS.items()}


def _row_fns() -> dict:
    """Back-compat view of the old `_ROW_FNS` dict (kind -> (grad, risk)),
    derived from the registry."""
    return {kind: (spec.grad_row, spec.risk_row)
            for kind, spec in PROBLEMS.items()}
