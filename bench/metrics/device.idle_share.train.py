"""Share of the traced training window in which no op ran on the
device: 1 - union of op intervals / window, averaged over the chips."""


def read(ctx):
    if ctx.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
