"""Share of the HBM roofline reached by the column passes of the 3-D FFT
(``axis_dft``: the y and z axes of each NPB FT transform), in percent: the
least time their work needs at the published HBM bandwidth over their
device time in the traced window (profiler trace, ops found by name).

Least bytes (``least_bytes``): a pass reads the grid's two float32 planes
and writes two, 16n bytes; a transform makes two passes, and a job makes
1 + niter transforms, so 672n bytes a job at Class B. The DFT matrices are
left out: they could be computed in place. The requests' own least time
(``min_device_s``, 8n bytes a pass over the grid and 2 + niter passes a
job, in ``ops/nas-ft.py``) prices the bytes, so this reads the same peak
table. The class, and so niter, is found from the grid's n."""

KERNELS = ("axis_dft",)
# n -> steps of the NPB FT class (ops/nas-ft.py)
NITER = {1 << 18: 6, 1 << 23: 6, 1 << 25: 20, 1 << 27: 20}
REQUEST_BYTES_PER_POINT = 8         # one complex64 pass over the grid


def least_bytes(n: int, niter: int) -> int:
    """Bytes the column passes of one job must move."""
    return 16 * n * 2 * (1 + niter)


def read(run):
    if run.trace is None or not run.min_device_s() or not run.done:
        return None
    spent = sum(s for op, s in run.trace["device_ops"]
                if op.lstrip("%").split(".", 1)[0] in KERNELS)
    niter = NITER.get(len(run.done[0].payload))
    if spent <= 0 or niter is None:
        return None
    # the requests' least seconds price (2 + niter) passes of
    # REQUEST_BYTES_PER_POINT bytes a point
    scale = least_bytes(1, niter) / (REQUEST_BYTES_PER_POINT * (2 + niter))
    return 100.0 * run.min_device_s() * scale / spent
