"""The fused Adam adaptation kernel's share of its roofline, in %: the
least time the chip could take for its bytes and operations (``flops.py``;
the bytes bound it) over its device time per meta step. The kernel's ops
are the Mosaic calls whose source locations name ``adam_adapt.py``
(``trace_reduce.kernels_from_hlo``); the step's other Pallas kernels, such
as the weighted cross-entropy of the meta loss, do not count."""

import trace_reduce as tr


def is_kernel(op):
    return "adam_adapt" in op.kernel


def read(ctx):
    cost, peaks = ctx["adam_adapt"], ctx["peaks"]
    bound_s = max(cost["bytes"] / peaks["hbm_bytes_per_s"], cost["flops"] / peaks["flops_per_s"])
    per_dev = [tr.match_ns(ops, is_kernel) for ops in ctx["ops"].values()]
    if not per_dev or not all(per_dev):
        return None
    kernel_s = sum(per_dev) / len(per_dev) / ctx["steps"] / 1e9
    return 100.0 * bound_s / kernel_s
