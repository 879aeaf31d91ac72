"""Share of the traced window in which no operation ran on the device,
mean over the chips used: 100 * (1 - busy / window)."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
