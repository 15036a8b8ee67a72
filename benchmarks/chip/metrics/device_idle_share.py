"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, in %."""

import trace_reduce as tr


def read(ctx):
    shares = [1.0 - tr.busy_ns(ops) / tr.window_ns(ops) for ops in ctx["ops"].values() if ops]
    return 100.0 * sum(shares) / len(shares) if shares else None
