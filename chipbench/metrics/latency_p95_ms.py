"""95th percentile of the same latencies as ``latency_p50_ms``."""
from chipbench.stats import percentile


def read(run):
    return percentile(run.latencies_ms(), 95)
