"""Median latency of the requests completed in the window: from when each
was due (open loop) or offered (closed loop) to when its result was a host
array (host clock)."""
from chipbench.stats import percentile


def read(run):
    return percentile(run.latencies_ms(), 50)
