"""Scheduler: mean share of the slot pool that is live per decode step."""


def read(run):
    return 100.0 * run.stats.slot_utilization if run.stats.decode_steps else None
