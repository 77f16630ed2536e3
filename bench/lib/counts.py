"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick's own counts: a kernel's roofline share and a
step's MFU divide them by measured time, so they never come from the
program under test or from XLA's cost model.
"""
from __future__ import annotations

import json
import math
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for `device_kind`; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in the peak "
                       f"table {PEAKS_FILE} (known: {sorted(table)})")
    return table[device_kind]


def vgg_fwd_flops(plan, *, hw: int, in_ch: int, fc_width: int,
                  n_classes: int) -> int:
    """Forward FLOPs of one sample through a VGG layer plan (3x3 SAME
    convs, 2x2 max pools, global mean, two dense layers); a
    multiply-add counts 2."""
    flops, ch, size = 0, in_ch, hw
    for item in plan:
        if item == "M":
            size //= 2
        else:
            flops += 2 * 9 * ch * item * size * size
            ch = item
    return flops + 2 * ch * fc_width + 2 * fc_width * n_classes


def wire_quant_bytes(shape, itemsize: int) -> int:
    """HBM bytes of one per-row int8 quantize of a (..., K) payload:
    the dense input read once, the int8 values and one f32 scale per
    last-axis row written.  A 0-d payload is one row of one element."""
    n = math.prod(shape) if shape else 1
    rows = n // shape[-1] if shape else 1
    return n * itemsize + n + 4 * rows


def wire_dequant_bytes(shape, itemsize: int) -> int:
    """The receiving side: int8 values and row scales read, the dense
    payload written.  Moves the same bytes as the quantize."""
    return wire_quant_bytes(shape, itemsize)


def least_time_s(flops: float, nbytes: float, peak: dict,
                 flops_key: str = "bf16_flops_per_s") -> float:
    """Roofline bound of one piece of work: the larger of its compute
    time and its memory time at the chip's peaks."""
    return max(flops / peak[flops_key], nbytes / peak["hbm_bytes_per_s"])
