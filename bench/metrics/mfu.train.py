"""The whole training step's share of the chips' bf16 peak: model FLOPs
per sample (forward + backward = 3 x forward, nothing recomputed
counted) x samples per second of the traced window / (chips x peak).
f32 matmuls at default precision run as bf16 passes, so the bf16 peak
is the ceiling."""


def read(ctx):
    flops = 3 * ctx["model"].fwd_flops_per_sample(ctx["cfg"], ctx["traffic"])
    return 100.0 * flops * ctx["samples_per_s"] / (
        ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
