"""Process start to the window's first statement: imports, device and
server bring-up, generation, bulk load, warm-up and compilation."""


def read(ctx):
    return ctx.setup_s
