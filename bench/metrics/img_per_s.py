"""Images whose logits reached the host in the window, per second of
the window, for the whole system (all its chips)."""


def read(ctx):
    return ctx.window.images / ctx.window.seconds
