"""Process start to the start of the window: imports, weights, model,
warm-up, and compilation where the cache misses."""


def read(run):
    return run.setup_s
