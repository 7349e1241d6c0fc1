"""Requests completed in the window per second of the window (host clock)."""


def read(run):
    return len(run.done) / (run.t_close - run.t_open)
