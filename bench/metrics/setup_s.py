"""Process start to window start: loading, building the search, compiles
or cache reads, the first steps the reference follows (host clock)."""


def read(ctx):
    return ctx["setup_s"]
