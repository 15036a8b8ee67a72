"""Device milliseconds per meta step under the ``cd_passes`` scope, averaged over
the cell's chips."""

import trace_reduce as tr

PHASE = "cd_passes"


def read(ctx):
    per_dev = [tr.scope_ns(ops).get(PHASE) for ops in ctx["ops"].values()]
    if not per_dev or any(v is None for v in per_dev):
        return None
    return sum(per_dev) / len(per_dev) / ctx["steps"] / 1e6
