"""Device time per model block, from the scope paths ``trace_reduce`` gives
each operation.

The program names each model block with a ``jax.named_scope``
(``repro.obs.trace.BLOCKS``), and the name reaches every compiled op's
``op_name``, forward and backward. Under ``jax.grad`` JAX may wrap a
component of the path in the transforms it went through: a block outside a
scanned layer stack reads ``transpose(jvp(loss))`` in the backward pass.
``components`` strips such wrappers. An operation belongs to the innermost
block on its path, so no operation counts twice; one on no block's path
(residual adds, optimizer updates, the scan's own slicing) belongs to none.
``cross_attention`` is a block of its own and never counts as
``attention``.

A program that names no block (one from before the scopes) gives no
operation with a block, and each reader then returns None.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

#: the program's block scopes (``repro.obs.trace.BLOCKS``)
BLOCKS = ("embed", "norm", "attention", "cross_attention", "mlp", "unembed", "loss")


def components(path: str) -> List[str]:
    """The scope names on ``path``, outermost first, with transform
    wrappers stripped (``transpose(jvp(mlp))`` reads as ``mlp``). Of a
    fused op's ``;``-joined paths the first is read."""
    out = []
    for seg in path.split(";", 1)[0].split("/"):
        while seg.endswith(")") and "(" in seg:
            seg = seg[seg.index("(") + 1:-1]
        out.append(seg)
    return out


def block_of(path: str) -> Optional[str]:
    """The innermost block on ``path``, or None."""
    found = None
    for name in components(path):
        if name in BLOCKS:
            found = name
    return found


def block_ns(ops: Sequence) -> Dict[Optional[str], float]:
    """Device time of one device's non-container operations per block; the
    operations on no block's path under the key None."""
    out: Dict[Optional[str], float] = {}
    for o in ops:
        if o.container:
            continue
        b = block_of(o.path)
        out[b] = out.get(b, 0.0) + o.dur_ns
    return out


def block_ms(ctx, block: str) -> Optional[float]:
    """Device milliseconds per meta step in ``block``, averaged over the
    cell's chips and counted over every phase; None when no operation of
    the trace carries the block."""
    per_dev = [block_ns(ops) for ops in ctx["ops"].values()]
    if not any(block in d for d in per_dev):
        return None
    return sum(d.get(block, 0.0) for d in per_dev) / len(per_dev) / ctx["steps"] / 1e6
