"""Ising solver throughput (``core/ising.solve_many`` -> ``kernels/sa_sweep.py``
and ``sqa_sweep.py``): spin updates of the traced window's Ising kernel
calls over those kernels' device time.

The updates are counted from the calls' own operand shapes in the trace:
each kernel draws one uniform per attempted spin update, so its
highest-rank operand — the uniforms, (problems, sweeps, chains, n) for SA
and SQ, with a Trotter axis for SQA — holds as many elements as the call
makes updates.  Fewer sweeps, reads or problems show up here as fewer
updates."""

import math
import re

# The Ising kernels in a v5e trace: `sa_sweep_many.<n>` (SA and SQ) and
# `sqa_sweep_many.<n>`.
KERNELS = ("sweep_many",)
SHAPE = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")


def operands(text: str) -> list:
    """The operand shapes of a custom call's HLO text; none where the text
    holds no whole operand list (a cut text must not count short)."""
    head, sep, rest = text.partition("custom-call(")
    depth = 1
    for end, ch in enumerate(rest if sep else ""):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return [tuple(int(d) for d in m.split(",") if d)
                    for m in SHAPE.findall(rest[:end])]
    return []


def updates_per_call(text: str) -> int:
    shapes = operands(text)
    if not shapes:
        return 0
    return math.prod(max(shapes, key=len))


def read(ctx):
    red = ctx["trace"]
    kernel_s = red.kernel_seconds(KERNELS)
    updates = sum(n * updates_per_call(text)
                  for text, n in red.calls(KERNELS).items())
    if kernel_s <= 0 or updates <= 0:
        return None
    return updates / kernel_s / 1e6
