"""Peak bytes in use on the fullest chip after the window, from the
device allocator, in GiB."""


def read(ctx):
    return ctx["peak_bytes"] / 2 ** 30
