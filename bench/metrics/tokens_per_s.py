"""Tokens trained by all active slots in the window, over the window
(host clock)."""


def read(ctx):
    if ctx["cell"].kind != "lm":
        return None
    return ctx["work_per_s"]
