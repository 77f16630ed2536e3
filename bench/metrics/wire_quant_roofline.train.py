"""Roofline share of the int8 wire quantize kernel: the bytes its calls
need (dense payload read, int8 values and f32 row scales written) at
the HBM peak, over its summed device time."""
from bench.lib import wire_kernels


def read(ctx):
    return wire_kernels.roofline(ctx, "wire_quant")
