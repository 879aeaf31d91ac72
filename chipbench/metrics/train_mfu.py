"""Model FLOP/s utilization of the whole training step: the forward and
backward operations per token the model needs (`counting`), times the
tokens per second of the window, over the chips' bf16 peak."""


def read(ctx):
    run = ctx["run"]
    chips = len(run["devices"])
    return 100.0 * run["model_flops_per_token"] * run["tokens_per_s"] / (
        chips * run["peak"]["bf16_flops_per_s"])
