"""Scheduler: host time per decode round with no device result awaited
(``ServeStats.host_s``, the loop's ``tick`` spans less their sync and idle
spans, over the decode steps).  A program without spans reads nothing."""


def read(run):
    host, steps = getattr(run.stats, "host_s", None), run.stats.decode_steps
    return 1e3 * host / steps if host is not None and steps else None
