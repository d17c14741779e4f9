"""Process start to the first measured call: imports, the chip, data and
parameters from the seed, compile or cache load, the first (warm-up) call."""


def read(ctx):
    return ctx["setup_s"]
