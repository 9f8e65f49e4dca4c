"""Share of the roofline reached by the fused bit-linear kernels
(``kernels/bitlinear.py``: ``bitlinear_decode``, ``bitlinear_grid`` and
their ``_grouped`` forms) in the traced window, in %.

For each call the least time the chip could take is the larger of the
bytes it must move over the HBM bandwidth and the operations it must do
over the bf16 peak (``bench/peaks.json``); the share is the sum of those
least times over the calls' device time.  Both are counted from the
call's own operand and result shapes in the trace: x (…, T, d_in),
m_packed (…, r, c, tn, kb) uint8 and C (…, r, c, K, td) read once, y
(…, T, d_out) written once; operations 2·T·d_in·c·K for x·M and
2·T·r·K·d_out for (x·M)·C, per group (expert) where there is a leading
group axis.  In decode the bytes bound every call."""

import math
import re

KERNELS = ("bitlinear",)
TYPED = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
         "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}


def _shapes(text: str) -> list:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in TYPED.findall(text)]


def call_cost(text: str):
    """(bytes, operations) of one bit-linear call from its HLO text, or
    None where the text does not hold the whole call."""
    head, sep, rest = text.partition(" custom-call(")
    if not sep:
        return None
    depth, end = 1, None
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            end = i
            break
    if end is None:
        return None
    result = _shapes(head.split(" = ", 1)[-1])
    ops = _shapes(rest[:end])
    if len(result) != 1 or len(ops) != 3:
        return None
    (xt, x), (mt, mp), (ct, C) = ops
    if mt != "u8" or any(t not in BYTES for t in (xt, ct, result[0][0])):
        return None
    nbytes = sum(BYTES[t] * math.prod(s) for t, s in ops + result)
    *groups, T, d_in = x
    *_, r, c, _, _ = mp
    K, td = C[-2:]
    g = math.prod(groups)
    flops = g * (2 * T * d_in * c * K + 2 * T * r * K * c * td)
    return nbytes, flops


def read(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    kernel_s = red.kernel_seconds(KERNELS)
    least = 0.0
    for text, n in red.calls(KERNELS).items():
        cost = call_cost(text)
        if cost is None:
            return None
        nbytes, flops = cost
        least += n * max(nbytes / peaks["hbm_bytes_per_s"],
                         flops / peaks["bf16_flops_per_s"])
    if kernel_s <= 0 or least <= 0:
        return None
    return 100.0 * least / kernel_s
