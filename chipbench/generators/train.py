"""Training cells: every call in the window is one step of the program's
jitted train step (`repro.training.train_step.build_train_step`, donated
state, as `repro.training.loop.run_training` runs it), ending in
`block_until_ready` on the updated parameters.

Configuration (`configs/<config>.json`): `model` holds the fields of the
program's `ModelConfig`; `training` the aggregator, node count, channel
and optimizer; `init` how the benchmark draws the weights.

Traffic parameters (`traffic/<config>.<traffic>.json`):

  global_batch, seq_len  sequences per step and tokens per sequence
  zipf_a, carry          the synthetic token statistics (`gen.token_batch`)
  check_steps            the first steps, which the reference replays
  limits                 the limit of each compared number

Set-up makes the weights on the device in one jitted call from `--seed`,
builds the one compiled step and drives it through the first
`check_steps` steps (the first compiles); the window continues the same
object from there, each step on a batch of its own drawn from the seed.
The channel's draws come from the configuration's `training.seed`, which
the step compiles in, so every run finds its programs in the cache.
"""
from __future__ import annotations

import numpy as np

from chipbench import counting, gen

SEED_SPACE = 2**31


def _leaf_norm_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def leaf_norms(tree):
        """L2 norm of every weight matrix: a stacked leaf (layers on its
        leading axis) gives one norm per layer."""
        out = []
        for x in jax.tree_util.tree_leaves(tree):
            x = x.astype(jnp.float32)
            if x.ndim >= 3:
                out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                    range(1, x.ndim)))))
            else:
                out.append(jnp.sqrt(jnp.sum(x * x))[None])
        return jnp.concatenate(out)

    return leaf_norms


def init_params(shapes, key, init: dict):
    """Weights drawn from `key` in the layout `shapes` gives: the embedding
    at `embed_std` times a normal truncated at two, norm scales at one,
    every other matrix at 1/sqrt(fan_in) times that normal."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    treedef = jax.tree_util.tree_structure(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if "embed" in name:
            std = init["embed_std"]
        elif "ln" in name or "norm" in name:
            out.append(jnp.ones(s.shape, s.dtype))
            continue
        else:
            std = 1.0 / np.sqrt(s.shape[-2])
        out.append((std * jax.random.truncated_normal(
            k, -2.0, 2.0, s.shape, jnp.float32)).astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


class Workload:
    span_names = ("train.batch", "train.step")

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 catalog):
        self.cfg, self.tr = config, traffic
        self.devices = devices
        self.ref = catalog.module("reference", config["reference"])
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Draw this run's weights and token stream from `seed`. The
        channel's key is the configuration's (`training.seed`): it is
        compiled into the step, as the launcher compiles its own."""
        ss = np.random.SeedSequence(seed)
        w, d = (int(x) % SEED_SPACE for x in ss.generate_state(2))
        self.weight_seed, self.data_seed = w, d

    # ------------------------------------------------------------ set-up
    def inputs(self, steps: int = 2) -> dict:
        """The traffic of this run: the token batches of its first steps
        and the seed of its weights."""
        import functools

        import jax

        tr = self.tr
        self._make_batch = jax.jit(functools.partial(
            gen.token_batch, batch=tr["global_batch"], seq_len=tr["seq_len"],
            vocab=self.cfg["model"]["vocab_size"], zipf_a=tr["zipf_a"],
            carry=tr["carry"]))
        return {"weight_seed": self.weight_seed,
                **{f"tokens.{i}": np.asarray(self._batch(i))
                   for i in range(steps)}}

    def _batch(self, step: int):
        import jax

        return self._make_batch(jax.random.fold_in(
            jax.random.key(self.data_seed), step))

    def _params0(self):
        import jax

        return self._init(jax.random.key(self.weight_seed))

    def setup(self) -> None:
        import functools

        import jax

        from repro.configs.base import ModelConfig
        from repro.core.channel import ChannelConfig
        from repro.core.gbma import GBMAConfig
        from repro.models.model import build_model
        from repro.optim.gd import get_optimizer
        from repro.training.train_step import TrainConfig, build_train_step

        self._annotate = jax.profiler.TraceAnnotation
        cfg, tr, t = self.cfg, self.tr, self.cfg["training"]
        model = build_model(ModelConfig(**cfg["model"]))
        shapes = model.params_shape()
        self._init = jax.jit(functools.partial(
            init_params, shapes, init=cfg["init"]))
        self.inputs(steps=0)
        tcfg = TrainConfig(
            aggregator=t["aggregator"], seed=t["seed"],
            gbma=GBMAConfig(n_nodes=t["nodes"], channel=ChannelConfig(
                fading=t["fading"], scale=t["scale"],
                noise_std=t["noise_std"], energy=t["energy"])))
        step = build_train_step(
            model, tcfg, get_optimizer(t["optimizer"], t["lr"]))
        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._init_state = step.init_state
        self._norms = _leaf_norm_fn()
        self.start()

    def start(self) -> None:
        """Fresh weights from the seed, and the first steps through the
        window's own step and feed (the first call compiles)."""
        import jax

        with jax.default_device(self.devices[0]):
            self.params = self.opt_state = None
            self.params = self._params0()
            self.opt_state = self._init_state(self.params)
            self.losses = []
            for i in range(self.tr["check_steps"]):
                self._train_step(i)
                self.losses.append(float(self._metrics["loss"]))
                if i == 0:  # the momentum after one step is the gradient
                    self.first_grad = np.asarray(self._norms(self.opt_state))
            p0 = self._params0()
            self.change = np.asarray(self._norms(jax.tree_util.tree_map(
                lambda a, b: a - b, self.params, p0)))
            del p0

    def _train_step(self, step: int) -> None:
        import jax

        with self._annotate("train.batch"):
            batch = {"tokens": self._batch(step)}
        with self._annotate("train.step"):
            self.params, self.opt_state, self._metrics = self._step(
                self.params, self.opt_state, batch, step)
            jax.block_until_ready(self.params)

    # ------------------------------------------------------------ window
    def call(self, i: int) -> None:
        import jax

        with jax.default_device(self.devices[0]):
            self._train_step(self.tr["check_steps"] + i)

    def tokens_per_step(self) -> int:
        return self.tr["global_batch"] * self.tr["seq_len"]

    def end_to_end(self, calls: int, elapsed: float) -> dict:
        return {"train_tokens_per_s": calls * self.tokens_per_step()
                / elapsed}

    def counts(self, calls: int, elapsed: float) -> dict:
        return {"tokens_per_s": calls * self.tokens_per_step() / elapsed,
                "model_flops_per_token":
                    counting.decoder_train_flops_per_token(
                        self.cfg["model"], self.tr["seq_len"])}

    # ------------------------------------------------------------ check
    def release(self) -> None:
        import gc

        self.params = self.opt_state = self._metrics = None
        self._step = None
        gc.collect()

    def reference(self, precision: str = "highest",
                  dtype: str = "float32") -> dict:
        """The reference's losses of the first steps, its momentum's leaf
        norms after one step and its parameters' change after all."""
        import jax

        t = self.cfg["training"]
        out = {"losses": []}
        p0 = self._params0()
        batches = [self._batch(i) for i in range(self.tr["check_steps"])]

        def on_step(step, loss, params, m):
            out["losses"].append(loss)
            if step == 0:
                out["first_grad"] = np.asarray(self._norms(m))

        with jax.default_device(self.devices[0]):
            params, _ = self.ref.train(
                p0, batches, model=self.cfg["model"], train_cfg=t,
                seed=t["seed"], precision=precision, dtype=dtype,
                on_step=on_step)
            p0 = self._params0()
            out["change"] = np.asarray(self._norms(jax.tree_util.tree_map(
                lambda a, b: a - b, params, p0)))
        return out

    def gaps(self, answer: dict, ref: dict) -> dict:
        """Losses by the widest relative gap; the first gradient and the
        change by the worst leaf: the gap between the two norms over the
        reference's norm of that leaf or of the median leaf, whichever is
        larger. Leaves whose reference gradient is under a thousandth of
        the median leaf's move by round-off alone and are left out of the
        change."""
        la, lr = np.asarray(answer["losses"]), np.asarray(ref["losses"])
        out = {"loss_gap": float(np.max(np.abs(la - lr) / np.abs(lr)))}
        g_ref = ref["first_grad"]
        keep = g_ref >= 1e-3 * np.median(g_ref)
        for key, sel in (("first_grad", np.ones_like(keep)),
                         ("change", keep)):
            a, b = answer[key][sel], ref[key][sel]
            den = np.maximum(b, np.median(b))
            out[key + "_gap"] = float(np.max(np.abs(a - b) / den))
        return out

    def program_answer(self) -> dict:
        return {"losses": self.losses, "first_grad": self.first_grad,
                "change": self.change}

    def check(self) -> tuple:
        gaps = self.gaps(self.program_answer(), self.reference())
        limits = self.tr["limits"]
        checks = [{"name": k, "value": gaps[k], "limit": limits[k]}
                  for k in limits]
        return checks, int(any(c["value"] > c["limit"] for c in checks))

    # ------------------------------------------------------------ calibration
    def calibrate(self) -> dict:
        """Readings of the program's first steps, of the control (the
        reference computed in bfloat16 in the program's place) and of the
        faults planted in the reference put in the program's place: half of
        each node's sequences left out, the mean over the rest; one token of
        the batch altered where it is drawn. A step that leaves the state
        unchanged reads 1 on `change_gap` by construction."""
        ref = self.reference()
        out = {"program": self.gaps(self.program_answer(), ref),
               "control": self.gaps(self.reference(dtype="bfloat16"), ref),
               "faults": {}}
        real = self._batch
        n = self.cfg["training"]["nodes"]
        try:
            def half(step):
                b = real(step)
                per = b.shape[0] // n
                keep = np.concatenate([np.arange(i * per, i * per + per // 2)
                                       for i in range(n)])
                return b[np.repeat(keep, 2)]
            self._batch = half
            out["faults"]["half"] = self.gaps(self.reference(), ref)

            vocab = self.cfg["model"]["vocab_size"]

            def altered(step):
                b = real(step)
                return b.at[0, 1].set((b[0, 1] + 1) % vocab)
            self._batch = altered
            out["faults"]["altered"] = self.gaps(self.reference(), ref)
        finally:
            self._batch = real
        return out
