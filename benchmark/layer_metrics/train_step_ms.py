"""Median host time between consecutive steps' loss fetches over the window."""


def read(run):
    return run.outcome.host.get("step_ms_median")
