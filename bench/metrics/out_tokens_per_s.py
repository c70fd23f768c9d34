"""Output tokens that reached the host inside the window, per second of
the window."""
from bench import window as Wn


def read(ctx):
    return Wn.tokens_in_window(ctx) / ctx.seconds
