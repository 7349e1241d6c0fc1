"""Seconds from process start to the window's opening: imports, device
start, binding, payloads, warm-up and any compilation (host clock)."""


def read(run):
    return run.t_open - run.t_start
