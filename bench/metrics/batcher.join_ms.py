"""Median host time of one `Batcher.join` in the window (the
benchmark's span around the call: prefill through both halves, the
scatter into the slot, the first token on the host)."""
import statistics


def read(ctx):
    joins = ctx["record"].join_s
    return 1e3 * statistics.median(joins) if joins else None
