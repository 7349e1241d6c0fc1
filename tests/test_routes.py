"""The route table: which route and plan each (op, n, modulus_bits,
model_shards) binds to, or the ``OpConfigError`` naming the constraint
that refuses it (docs/planner.md is the same table).

Each row calls ``OpSpec.route``, the op's one route decision, which
builds no mesh and no jit, so the distributed rows need no devices.
"""
import re

import pytest

from repro.launch.ops import OpConfigError, OpContext, get_op


def _ok(route, tier, *, real=False, exact=False, inner_n=0, seq_shards=1,
        grid=()):
    return (route, tier, real, exact, inner_n, seq_shards, grid)


CEILING = "VMEM-resident ceiling _MAX_LOCAL_N="
TILING = "D^2 | n"

ROWS = [
    # (op, n, modulus_bits, model_shards) -> route and plan, or error text
    ("fft", 1024, None, 1, _ok("fft", "local")),
    ("fft", 16384, None, 1, _ok("fft", "local")),
    ("fft", 65536, None, 1, _ok("fft", "local")),
    ("fft", 131072, None, 1, CEILING),
    ("fft", 1024, None, 4, "--model-shards applies to"),
    ("fft", 1024, 60, 1, "--modulus-bits applies to"),
    ("fft", 1000, None, 1, "must be a power of two"),
    ("rfft", 1024, None, 1, _ok("rfft-real", "local", real=True)),
    ("rfft", 65536, None, 1, _ok("rfft-real", "local", real=True)),
    ("rfft", 131072, None, 1, "serves polymul-real only"),
    ("rfft", 1024, None, 4, "--model-shards applies to"),
    ("polymul", 1024, None, 1, _ok("polymul", "local")),
    ("polymul", 65536, None, 1, _ok("polymul", "local")),
    ("polymul", 131072, None, 1, CEILING),
    ("polymul-real", 16384, None, 1,
     _ok("polymul-real-packed", "local", real=True)),
    ("polymul-real", 65536, None, 1,
     _ok("polymul-real-packed", "local", real=True)),
    ("polymul-real", 131072, None, 1,
     _ok("polymul-real-hbm", "hbm", real=True, inner_n=16384)),
    ("polymul-real", 524288, None, 1,
     _ok("polymul-real-hbm", "hbm", real=True, inner_n=16384)),
    ("polymul-real", 1024, None, 8,
     _ok("polymul-real-distributed", "distributed", real=True,
         seq_shards=8)),
    ("polymul-real", 262144, None, 4,
     _ok("polymul-real-distributed", "distributed", real=True,
         seq_shards=4)),
    ("polymul-real", 1024, None, 3, TILING),
    ("polymul-real", 64, None, 16, TILING),
    ("polymul-mod", 1024, None, 1,
     _ok("polymul-mod-single", "local", exact=True)),
    ("polymul-mod", 65536, None, 1,
     _ok("polymul-mod-single", "local", exact=True)),
    ("polymul-mod", 131072, None, 1, "VMEM-resident ceiling "
                                     "_MAX_LOCAL_N_EXACT="),
    ("polymul-mod", 8192, 210, 1,
     _ok("polymul-mod-rns", "local", exact=True)),
    ("polymul-mod", 1024, 60, 1, _ok("polymul-mod-rns", "local", exact=True)),
    ("polymul-mod", 1024, None, 8,
     _ok("polymul-mod-distributed", "distributed", exact=True,
         seq_shards=8)),
    ("polymul-mod", 1024, 60, 8, "shards limbs"),
    ("polymul-mod", 1024, None, 3, TILING),
    ("nas-ft", 1 << 18, None, 1,
     _ok("nas-ft-grid", "grid", grid=(64, 64, 64))),
    ("nas-ft", 1 << 25, None, 1,
     _ok("nas-ft-grid", "grid", grid=(256, 256, 512))),
    ("nas-ft", 1 << 24, None, 1, "NPB FT classes"),
    ("nas-ft", 1 << 25, None, 4, "--model-shards applies to"),
    ("nas-ft", 1 << 25, 60, 1, "--modulus-bits applies to"),
    ("dct", 1024, None, 1, "unknown op"),
]


@pytest.mark.parametrize(
    "op,n,bits,shards,want", ROWS,
    ids=[f"{r[0]}-n{r[1]}-bits{r[2]}-shards{r[3]}" for r in ROWS])
def test_route_table(op, n, bits, shards, want):
    ctx = OpContext(modulus_bits=bits, model_shards=shards)
    if isinstance(want, str):
        with pytest.raises(OpConfigError, match=re.escape(want)):
            get_op(op).route(n, ctx)
        return
    plan, route = get_op(op).route(n, ctx)
    assert (route, plan.tier, plan.real, plan.exact, plan.inner_n,
            plan.seq_shards, plan.grid) == want
