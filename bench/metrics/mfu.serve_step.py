"""The whole decode step's share of the chip's peak: over the window's
steps, the sum of each step's least time (the larger of its FLOPs at
the bf16 peak and its bytes at the HBM peak) over the sum of the steps'
host times.  Bytes are what the algorithm needs: each half's weights
read once per step and the valid cached keys and values of the live
tenants; FLOPs are 2 x weights per live token plus attention over the
valid positions.  Decode is bound by bytes."""
from bench.lib import counts


def read(ctx):
    rec, cfg, peak = ctx["record"], ctx["cfg"], ctx["peak"]
    if not rec.step_s:
        return None
    D, F, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    per_layer = 2 * D * H * hd + 2 * D * K * hd + 3 * D * F
    weights = L * per_layer + cfg["vocab"] * D          # tied head once
    itemsize = 2 if cfg["dtype"] == "bfloat16" else 4
    kv_per_pos = L * 2 * K * hd * itemsize
    least = 0.0
    for live, pos in zip(rec.step_live, rec.step_pos):
        flops = 2 * weights * live + 4 * L * H * hd * pos
        nbytes = weights * itemsize + kv_per_pos * pos
        least += counts.least_time_s(flops, nbytes, peak)
    return 100.0 * least / sum(rec.step_s)
