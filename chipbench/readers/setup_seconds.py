"""Process start to the first timed instant."""


def read(ctx):
    return ctx["setup_s"]
