"""Mean host milliseconds of ``BoundOp.execute`` per batch dispatched in the
window: stacking, host-to-device transfer and launch; on the RNS route the
whole limb split, kernel and CRT (the benchmark's span around the call)."""


def read(run):
    spans = run.stages_in_window()
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)
