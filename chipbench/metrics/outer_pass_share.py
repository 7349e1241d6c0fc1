"""Share of the device's busy time in the traced window spent outside the
inner VMEM-resident row kernel, in percent: on the HBM-resident four-step
route, the outer column passes (``hbm_outer_fwd``, ``hbm_outer_inv``), the
paired product and the copies between them. The inner kernel is the one
whose device op is named ``fft_planes`` (``kernels/fft.py``); a trace
without it (another route, or a program without the tier) reads nothing."""

INNER = "fft_planes"


def _kernel(op: str) -> str:
    """'%fft_planes.2' -> 'fft_planes': the op's name without the HLO sigil
    and instance number."""
    return op.lstrip("%").split(".", 1)[0]


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    inner = [s for op, s in run.trace["device_ops"] if _kernel(op) == INNER]
    if not inner:
        return None
    busy = run.trace["busy_s"]
    return 100.0 * max(0.0, busy - sum(inner)) / busy
