"""The least time the chip could spend on a served request.

The work of a request is what it must read and write, at the payload's
width: one read of every input and one write of the output. It is the
request's and not the current implementation's, so a later algorithm is
judged on the same yardstick. Each op states its bytes per point, and its
flops where a published peak prices them, in ``ops/<op>.py``
(``BYTES_PER_POINT``, ``flops``); the peaks come from ``peaks.json``.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(LookupError):
    """The device kind has no row in the peak table."""


def load_peaks(kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise UnknownDevice(f"device kind {kind!r} is not in {path.name} "
                            f"(known: {', '.join(table)})")
    return table[kind]


def request_bytes(op_module, n: int, limbs: int = 1) -> int:
    """Bytes one request of length ``n`` must move through HBM."""
    return op_module.BYTES_PER_POINT * n * limbs


def request_min_s(op_module, n: int, limbs: int, peaks: dict
                  ) -> tuple[float, str]:
    """(least seconds on one chip, the term that bounds it). Ops without a
    published peak for their arithmetic (32-bit integers) have the HBM
    term alone."""
    t_hbm = request_bytes(op_module, n, limbs) / peaks["hbm_bytes_per_s"]
    flops = op_module.flops(n)
    if flops is None:
        return t_hbm, "hbm"
    t_flop = flops / peaks["bf16_flops_per_s"]
    return (t_hbm, "hbm") if t_hbm >= t_flop else (t_flop, "flops")
