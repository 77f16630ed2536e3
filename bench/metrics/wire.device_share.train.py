"""Device time of the int8 wire's quantize and dequantize kernels over
the device's busy time in the traced window."""
from bench.lib import wire_kernels


def read(ctx):
    ns = sum(t for k in ("wire_quant", "wire_dequant")
             for t, _ in wire_kernels.calls(ctx, k))
    if not ns or not ctx.get("busy_s"):
        return None
    return 100.0 * ns * 1e-9 / (ctx["busy_s"] * ctx["chips"])
