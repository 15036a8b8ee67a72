"""Model FLOP utilisation of the whole meta step, in %: ``flops.py``'s
model FLOPs of the traced steps over the traced window, over the chips'
published peak (recomputation under remat not counted)."""

import trace_reduce as tr


def read(ctx):
    window_ns = max((tr.window_ns(ops) for ops in ctx["ops"].values()), default=0.0)
    if window_ns <= 0:
        return None
    achieved = ctx["step_flops"] * ctx["steps"] / (window_ns / 1e9)
    return 100.0 * achieved / (ctx["chips"] * ctx["peaks"]["flops_per_s"])
