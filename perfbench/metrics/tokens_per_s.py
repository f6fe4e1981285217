"""Output tokens of every request served, over the whole window (the
benchmark's clock around ``ContinuousScheduler.run``)."""


def read(run):
    return run.tokens_out / run.window_s
