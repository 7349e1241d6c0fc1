"""Mean rows per batch dispatched in the window, as a percentage of the
engine's ``max_batch`` (counted by the benchmark's span around execute)."""


def read(run):
    spans = run.stages_in_window()
    if not spans:
        return None
    return 100.0 * sum(s.rows for s in spans) / (len(spans) * run.max_batch)
