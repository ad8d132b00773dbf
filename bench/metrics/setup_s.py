"""Process start -> the window's start: network, plane, warm-up (compiles
included) and the standing set."""


def read(ctx):
    return ctx["setup_s"]
