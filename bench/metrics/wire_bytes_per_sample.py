"""Bytes that crossed the cut (activations up, gradients down) plus the
p2p weight handoff, from the engine's meter over the window, per
training sample of the window.  The meter prices each payload from its
actual packed dtypes."""


def read(ctx):
    if not ctx.get("samples"):
        return None
    return ctx["wire_bytes"] / ctx["samples"]
