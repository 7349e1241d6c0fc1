"""Share of the HBM roofline reached by the two outer Pallas kernels of the
HBM-resident four-step (``hbm_outer_fwd``, ``hbm_outer_inv``), in percent:
the least time their work needs at the published HBM bandwidth over their
device time in the traced window (profiler trace, ops found by name).

Least bytes (``least_bytes``): the forward pass reads a product's two rows
and writes its two column-transformed planes, 16n bytes; the inverse pass
reads a pair's two planes and writes the pair's two rows, 16n bytes a pair.
Twiddle and DFT tables are left out: they could be computed in place. The
requests' own least time (``min_device_s``, 12n bytes each for
polymul-real) prices the bytes, so this reads the same peak table."""

KERNELS = ("hbm_outer_fwd", "hbm_outer_inv")
REQUEST_BYTES_PER_POINT = 12        # polymul-real: read a and b, write c


def least_bytes(n: int, products: int) -> int:
    """Bytes the two outer kernels must move for ``products`` products of
    length n (an odd batch pairs its last product with a zeros one)."""
    pairs = -(-products // 2)
    return 16 * n * products + 16 * n * pairs


def read(run):
    if run.trace is None or not run.min_device_s():
        return None
    spent = sum(s for op, s in run.trace["device_ops"]
                if op.lstrip("%").split(".", 1)[0] in KERNELS)
    if spent <= 0:
        return None
    # the requests' least seconds price REQUEST_BYTES_PER_POINT bytes a
    # point; the outer kernels' least bytes are least_bytes(1, 2) / 2 a
    # point of each product
    scale = least_bytes(1, 2) / 2 / REQUEST_BYTES_PER_POINT
    return 100.0 * run.min_device_s() * scale / spent
