"""On device 0: time a collective op runs and no other op does, over the
traced stretch."""


def read(run):
    if run.trace is None or not run.trace.collective_s:
        return None
    return 100.0 * run.trace.collective_exposed_s / run.trace.window_s
