"""Tokens trained by all active slots in the window, over the window
(host clock)."""


def read(ctx):
    return ctx["work_per_s"]
