"""The int8 wire kernels in a trace: which device ops they are, and the
bytes each call moved.

The kernels are known by their interface, read off each custom call's
signature: the quantize takes one dense payload and returns the int8
values and one f32 scale per row; the dequantize takes exactly those
two and returns the dense payload.  The bytes of each call follow from
its shapes (`counts.wire_quant_bytes`), so no count of calls per step
is assumed.
"""
from __future__ import annotations

import math
import re

from bench.lib import counts

_SHAPE = r"\[([0-9,]*)\]\{[^}]*\}"
QUANT = re.compile(rf"= \(s8{_SHAPE}, f32{_SHAPE}\) custom-call\((\w+)\[")
DEQUANT = re.compile(rf"= (\w+){_SHAPE} custom-call\(s8{_SHAPE} %\S+, "
                     rf"f32{_SHAPE} %\S+\)$")
ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2}


def calls(ctx, kernel: str) -> list:
    """[(device ns inside the traced window, bytes)] of every call of
    `kernel` ("wire_quant" | "wire_dequant") on the traced chips."""
    out = []
    for ops in ctx["planes"]:
        for name, s, e in ops:
            s, e = max(s, ctx["lo"]), min(e, ctx["hi"])
            if e <= s:
                continue
            if kernel == "wire_quant":
                m = QUANT.search(name)
                dims, dtype = (m.group(1), m.group(3)) if m else (None, None)
            else:
                m = DEQUANT.search(name)
                dims, dtype = (m.group(2), m.group(1)) if m else (None, None)
            if m is None:
                continue
            shape = tuple(int(d) for d in dims.split(",") if d)
            out.append((e - s, counts.wire_quant_bytes(shape,
                                                       ITEMSIZE[dtype])))
    return out


def roofline(ctx, kernel: str) -> float | None:
    """Percent of the roofline: the calls' least time at the HBM peak
    (they move bytes; their FLOPs are negligible) over their summed
    device time."""
    found = calls(ctx, kernel)
    if not found:
        return None
    least = sum(counts.least_time_s(0.0, b, ctx["peak"]) for _, b in found)
    return 100.0 * least / (math.fsum(ns for ns, _ in found) * 1e-9)
