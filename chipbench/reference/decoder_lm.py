"""Plain reference of a dense decoder language model trained by GBMA
over a Rayleigh-fading multiple-access channel (arXiv:1908.07463 Eqs. 8
and 9, applied to a model's gradient), with momentum on the edge.

It imports nothing of the program and computes in straightforward
`jax.numpy`: pre-norm layers of RMS norm (with scale, eps 1e-6), causal
multi-head attention with rotary positions (half-split pairs), a gated
SiLU MLP, a final RMS norm and an output projection tied to the
embedding; the loss of an example is its mean next-token cross-entropy.
Parameters are taken in the layout the benchmark makes them in: a dict
with `embed` (V, d), `final_norm` (d,) and
`segments.seg0.sub0.{ln1, ln2, attn.{wq, wk, wv, wo}, mlp.{wi, wg, wo}}`
stacked over layers on a leading axis.

One GBMA step with N nodes, node n holding the n-th contiguous group of
the batch: the gradient of (1/N) sum_n h_n f_n, with f_n node n's mean
loss and h_n its Rayleigh gain, plus edge noise of std
noise_std / (N sqrt(E_N)) on every coordinate. The random stream is the
one the program documents: the step key is fold_in(key(seed), step),
split into (k_h, k_w); k_h splits into (k_mag, k_phase) and the gains
are scale * sqrt(-2 log u), u uniform on [1e-12, 1) from k_mag; k_w
splits into one key per parameter leaf, in the order of
`jax.tree_util.tree_leaves`, each drawing that leaf's standard normals.
Momentum: m <- mu m + g, theta <- theta - lr m.

`dtype="float32"` with `precision="highest"` is the reference; the
control computes the model in `bfloat16` (the optimizer stays float32).
The gradient is taken node by node, so that one node's activations are
live at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _layer(x, p, cfg, precision):
    b, s, _ = x.shape
    h, hd = cfg["n_heads"], cfg["head_dim"]
    hkv = cfg["n_kv_heads"]
    mm = functools.partial(jnp.einsum, precision=precision)
    a = _rms(x, p["ln1"])
    q = mm("bsd,dh->bsh", a, p["attn"]["wq"]).reshape(b, s, h, hd)
    k = mm("bsd,dh->bsh", a, p["attn"]["wk"]).reshape(b, s, hkv, hd)
    v = mm("bsd,dh->bsh", a, p["attn"]["wv"]).reshape(b, s, hkv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * hd)
    x = x + mm("bsh,hd->bsd", o, p["attn"]["wo"])
    m = _rms(x, p["ln2"])
    gate = jax.nn.silu(mm("bsd,df->bsf", m, p["mlp"]["wg"]))
    up = mm("bsd,df->bsf", m, p["mlp"]["wi"])
    return x + mm("bsf,fd->bsd", gate * up, p["mlp"]["wo"])


def example_losses(params, tokens, cfg, precision, dtype):
    """(B,) mean next-token cross-entropy of each sequence."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    x = jnp.take(params["embed"], inputs, axis=0)

    def body(x, p):
        return _layer(x, p, cfg, precision), None

    x, _ = jax.lax.scan(body, x, params["segments"]["seg0"]["sub0"])
    h = _rms(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", h, params["embed"],
                        precision=precision).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "dtype"))
def _node_value_grad(params, tokens, *, cfg_items, precision, dtype):
    cfg = dict(cfg_items)

    def loss(p):
        return jnp.mean(example_losses(p, tokens, cfg, precision, dtype))

    value, grad = jax.value_and_grad(loss)(params)
    return value, jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32), grad)


def gains(step_key, n_nodes: int, scale: float):
    k_h, _ = jax.random.split(step_key)
    k_mag, _ = jax.random.split(k_h)
    u = jax.random.uniform(k_mag, (n_nodes,), minval=1e-12, maxval=1.0)
    return scale * jnp.sqrt(-2.0 * jnp.log(u))


@jax.jit
def _add_noise(grads, k_w, std):
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    keys = jax.random.split(k_w, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        g + std * jax.random.normal(k, g.shape, jnp.float32)
        for g, k in zip(leaves, keys)])


@functools.partial(jax.jit, static_argnames=("lr", "mu"))
def _momentum(params, m, g, *, lr, mu):
    m = jax.tree_util.tree_map(lambda a, b: mu * a + b, m, g)
    return jax.tree_util.tree_map(lambda p, a: p - lr * a, params, m), m


@jax.jit
def _weighted_sum(acc, g, w):
    return jax.tree_util.tree_map(lambda a, b: a + w * b, acc, g)


def train(params, batches, *, model: dict, train_cfg: dict, seed: int,
          precision: str = "highest", dtype: str = "float32",
          on_step=None):
    """Run len(batches) GBMA steps from `params`; calls
    `on_step(step, loss, params, momentum)` after each and returns the
    final (params, momentum)."""
    n = train_cfg["nodes"]
    std = train_cfg["noise_std"] / (n * float(np.sqrt(train_cfg["energy"])))
    cfg_items = tuple(sorted((k, v) for k, v in model.items()
                             if isinstance(v, (int, float, str, bool))))
    base = jax.random.key(seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    for step, tokens in enumerate(batches):
        k_step = jax.random.fold_in(base, step)
        h = gains(k_step, n, train_cfg["scale"])
        _, k_w = jax.random.split(k_step)
        per = tokens.shape[0] // n
        acc = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = []
        for i in range(n):
            value, g = _node_value_grad(
                params, tokens[i * per:(i + 1) * per], cfg_items=cfg_items,
                precision=precision, dtype=jnp.dtype(dtype))
            acc = _weighted_sum(acc, g, h[i] / n)
            losses.append(float(value))
        g = _add_noise(acc, k_w, jnp.float32(std))
        params, m = _momentum(params, m, g, lr=train_cfg["lr"],
                              mu=train_cfg["momentum"])
        if on_step is not None:
            on_step(step, float(np.mean(losses)), params, m)
    return params, m
