"""The whole decode step's share of the chip's peak for an MLA + MoE
server, by `mfu.serve_step`'s rule: over the window's steps, the sum of
each step's least time (the larger of its FLOPs at the bf16 peak and its
bytes at the HBM peak) over the sum of the steps' host times.  The
FLOPs and bytes of a step are the cell model's `decode_step_cost`: the
client's weights once, the server's weights without the routed
experts, each held expert weighted by its chance of being hit under
uniform routing (an estimate), and the valid latent rows of the live
tenants, not the ring; FLOPs of the active parameters and of the
absorbed attention over the valid positions.  Decode is bound by
bytes."""
from bench.lib import counts


def read(ctx):
    rec, cfg, peak = ctx["record"], ctx["cfg"], ctx["peak"]
    cost = getattr(ctx.get("model"), "decode_step_cost", None)
    if not rec.step_s or cost is None:
        return None
    least = sum(counts.least_time_s(*cost(cfg, live, pos), peak)
                for live, pos in zip(rec.step_live, rec.step_pos))
    return 100.0 * least / sum(rec.step_s)
