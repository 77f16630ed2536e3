"""Roofline share of the int8 wire dequantize kernel: int8 values and
row scales read, the dense payload written, at the HBM peak, over its
summed device time."""
from bench.lib import wire_kernels


def read(ctx):
    return wire_kernels.roofline(ctx, "wire_dequant")
