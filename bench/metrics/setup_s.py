"""Set-up seconds: process start to the window's start (data made on the
device, compile or compile-cache load, warm-up)."""


def read(ctx):
    return ctx.setup_s
