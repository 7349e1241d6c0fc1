"""Share of the HBM roofline reached by the served route's device programs,
in percent: the least time the requests completed in the traced window
need (``work.request_min_s``: their inputs read and output written once, at
the published HBM bandwidth, over the chips used) divided by the device's
busy time in that window (profiler trace). Serving is the only device work
in the window, so the busy time covers whatever implements the route."""


def read(run):
    least = run.min_device_s()
    if run.trace is None or run.trace["busy_s"] <= 0 or not least:
        return None
    return 100.0 * least / run.trace["busy_s"]
