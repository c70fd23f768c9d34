"""Seconds from the start of the process to the first due arrival: weights
made on the device, the engine built, programs compiled or loaded from the
cache, warm-up and the set-up the traffic needs (documents prefilled)."""


def read(ctx):
    return ctx.setup_s
