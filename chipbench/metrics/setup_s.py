"""setup_s: process start to the window's first instant (host clock):
imports, device, weights, engine, compile-cache loads and warm-up."""


def read(ctx):
    return ctx.setup_s
