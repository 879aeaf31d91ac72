"""Share of the node-slots the program dispatched that are padding:
100 * (1 - node_slots / padded_node_slots), the counts `run_mc` keeps of
every batch it dispatches (the sum over rows of N_i, and rows x N_max).
The counters cover the process (set-up's warm call too), whose calls all
share the window's shapes. Nothing to read where the program keeps no
such counters."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters()
    padded = c.get("repro.run_mc.padded_node_slots", 0)
    if not padded:
        return None
    return 100.0 * (1.0 - c["repro.run_mc.node_slots"] / padded)
