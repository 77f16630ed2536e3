"""Median host time of one `Batcher.step` in the window (the
benchmark's span around the call: every live tenant's client step, the
batched server step, the logits down the wire, the host argmax)."""
import statistics


def read(ctx):
    steps = ctx["record"].step_s
    return 1e3 * statistics.median(steps) if steps else None
