"""Training launcher: `python -m repro.launch.train --arch repro-100m
--steps 200 --aggregator gbma`. Runs on the local device(s); the production
mesh path is exercised by dryrun.py. `main(argv)` takes its arguments as a
list, so a caller in the same process (`chip_smoke.py`) drives it as a
function and reads the returned loss history.

`--aggregator` accepts EVERY algorithm in the MAC registry
(`mc/slots.ALGO_REGISTRY`): gbma/fdm/centralized run the fused production
path; blind/blind_ec/momentum/nesterov/power_control route through the
channel-transport layer (per-node gradients over the simulated MAC — see
docs/training.md). The blind family needs `--antennas`; `--power-budget`
bounds blind_ec's per-node slot energy; `--block-d` / `--transmit-dtype`
expose the transport's tiling and bf16-transmit knobs.
"""
from __future__ import annotations

import argparse
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs.registry import get_config
from repro.core import transport
from repro.core.channel import ChannelConfig
from repro.core.gbma import GBMAConfig
from repro.core.mc.slots import ALGO_REGISTRY
from repro.data.synthetic import SyntheticTokens, TokenDatasetConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.optim.gd import get_optimizer
from repro.training.loop import run_training
from repro.training.train_step import (TrainConfig, build_train_step,
                                       resolve_route)


def main(argv: Optional[Sequence[str]] = None) -> list:
    """Parse `argv` (None: the command line), train, and return the
    per-step metric history of `run_training`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--aggregator", default="gbma",
                    choices=tuple(ALGO_REGISTRY))
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--noise-std", type=float, default=0.01)
    ap.add_argument("--energy-eps", type=float, default=None,
                    help="E_N = nodes^(eps-2); default E_N = 1")
    ap.add_argument("--fading", default="rayleigh")
    ap.add_argument("--optimizer", default="momentum")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--antennas", type=int, default=None,
                    help="edge antenna count M (required for blind/"
                         "blind_ec; MRC path for precoded aggregators)")
    ap.add_argument("--power-budget", type=float, default=None,
                    help="blind_ec per-node per-slot squared-norm budget")
    ap.add_argument("--gamma", type=float, default=0.9,
                    help="receiver momentum of momentum/nesterov "
                         "aggregators")
    ap.add_argument("--block-d", type=int, default=None,
                    help="transport column-tile width (default: one block "
                         "per parameter leaf)")
    ap.add_argument("--transmit-dtype", default=None,
                    choices=(None, "bfloat16"),
                    help="cast transmitted gradient blocks (transport "
                         "route); accumulation stays f32")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduced config")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.arch_id} params={n_params/1e6:.1f}M "
          f"aggregator={args.aggregator} nodes={args.nodes}")

    energy = (args.nodes ** (args.energy_eps - 2.0)
              if args.energy_eps is not None else 1.0)
    channel = ChannelConfig(fading=args.fading, noise_std=args.noise_std,
                            energy=energy)
    route = resolve_route(TrainConfig(aggregator=args.aggregator))
    tcfg = TrainConfig(
        aggregator=args.aggregator,
        gbma=GBMAConfig(n_nodes=args.nodes, channel=channel),
        transport=transport.TransportConfig(
            n_nodes=args.nodes, channel=channel, n_antennas=args.antennas,
            power_budget=(args.power_budget if args.power_budget is not None
                          else math.inf),
            gamma=args.gamma, stepsize=args.lr, block_d=args.block_d,
            transmit_dtype=args.transmit_dtype, ota_impl="auto")
        if route == "transport" else None)
    opt = get_optimizer(args.optimizer, args.lr)
    step = build_train_step(model, tcfg, opt)

    ds = SyntheticTokens(TokenDatasetConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))

    def batches():
        for tokens in ds:
            b = {"tokens": tokens}
            if cfg.n_patches:
                b["patch_embed"] = jnp.zeros(
                    (args.batch, cfg.n_patches, cfg.d_model))
                b["tokens"] = tokens[:, : args.seq - cfg.n_patches + 1]
            if model.kind == "encdec":
                b["frames"] = jnp.zeros((args.batch, cfg.enc_seq,
                                         cfg.d_model))
            yield b

    params, opt_state, hist = run_training(
        step, params, step.init_state(params), batches(), args.steps,
        log_every=max(args.steps // 20, 1))
    if args.checkpoint:
        ckpt.save(args.checkpoint, params)
        print(f"saved checkpoint to {args.checkpoint}")
    print(f"final loss {hist[-1]['loss']:.4f} "
          f"(from {hist[0]['loss']:.4f})")
    return hist


if __name__ == "__main__":
    enable_compile_cache()
    main()
