"""Distributed four-step FFT: multi-device correctness (subprocess meshes)."""
import pytest

from conftest import run_in_subprocess_devices

pytestmark = pytest.mark.dist


def test_four_step_fft_and_polymul_8dev():
    out = run_in_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.fft import distributed as dfft

mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
B, n = 4, 256
x = rng.standard_normal((B, n)) + 1j * rng.standard_normal((B, n))
sh = NamedSharding(mesh, P("data", "model"))
xj = jax.device_put(jnp.asarray(x, jnp.complex64), sh)

y = jax.jit(dfft.make_sharded_fft(mesh))(xj)
err = np.max(np.abs(np.asarray(y) - np.fft.fft(x)))
assert err < 1e-3, f"fwd err {err}"

z = jax.jit(dfft.make_sharded_fft(mesh, inverse=True))(y)
err = np.max(np.abs(np.asarray(z) - x))
assert err < 1e-4, f"roundtrip err {err}"

a = rng.standard_normal((B, n)); b = rng.standard_normal((B, n))
aj = jax.device_put(jnp.asarray(a, jnp.complex64), sh)
bj = jax.device_put(jnp.asarray(b, jnp.complex64), sh)
c = jax.jit(dfft.make_sharded_polymul(mesh))(aj, bj)
want = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b))
err = np.max(np.abs(np.asarray(c) - want))
assert err < 1e-3, f"polymul err {err}"
print("OK")
""", n_devices=8)
    assert "OK" in out


def test_four_step_zorder_saves_collectives():
    """The unordered (Z-order) path must contain fewer all-to-alls."""
    out = run_in_subprocess_devices("""
import re, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.fft import distributed as dfft

mesh = jax.make_mesh((1, 8), ("data", "model"))
sh = NamedSharding(mesh, P("data", "model"))
spec = jax.ShapeDtypeStruct((2, 512), jnp.complex64)

def count_a2a(fn, nargs):
    lowered = jax.jit(fn).lower(*([spec] * nargs))
    txt = lowered.compile().as_text()
    return len(re.findall(r'all-to-all', txt))

ordered = count_a2a(dfft.make_sharded_fft(mesh), 1)
import functools
pm = dfft.make_sharded_polymul(mesh)
pm_n = count_a2a(pm, 2)
print(f"ordered={ordered} polymul={pm_n}")
# ordered fwd uses 3 transposes; polymul (2 fwd + 1 inv, all Z-order) uses 6
assert pm_n < 3 * ordered, (ordered, pm_n)
""", n_devices=8)
    assert "ordered=" in out


def test_fft_distributed_fp32_accuracy_large_n_8dev():
    """n = 2^20 over 8 shards stays within fp32 tolerance of the f64
    numpy oracle — the end-to-end half of the fp32-twiddle regression pin
    (the table-level half, which fails on the pre-fix float32 twiddle
    arithmetic, is tests/test_dist_real.py::
    test_fp32_twiddle_regression_exact_integer_exponents)."""
    out = run_in_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.fft import distributed as dfft

mesh = jax.make_mesh((1, 8), ("data", "model"))
sh = NamedSharding(mesh, P("data", "model"))
rng = np.random.default_rng(0)
n = 1 << 20
x = rng.standard_normal((1, n)) + 1j * rng.standard_normal((1, n))
xj = jax.device_put(jnp.asarray(x, jnp.complex64), sh)
y = np.asarray(jax.jit(dfft.make_sharded_fft(mesh))(xj))
want = np.fft.fft(x)
err = np.max(np.abs(y - want)) / np.max(np.abs(want))
assert err < 2e-6, f"fwd rel err {err}"
back = np.asarray(jax.jit(dfft.make_sharded_fft(mesh, inverse=True))(
    jax.device_put(jnp.asarray(y), sh)))
err = np.max(np.abs(back - x)) / np.max(np.abs(x))
assert err < 2e-6, f"roundtrip rel err {err}"
print("OK")
""", n_devices=8)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Collective byte closed forms == the live ledger of each sharded builder
# ---------------------------------------------------------------------------

def _traced_collective_bytes(build, *args) -> int:
    """All-to-all + ppermute bytes the ``dist.collectives`` ledger records
    while lowering ``build`` (an AbstractMesh trace needs no devices)."""
    import jax
    from repro.dist import collectives
    with collectives.ledger() as led:
        jax.jit(build).lower(*args)
    return led.bytes_by_kind["all-to-all"] + led.bytes_by_kind["ppermute"]


@pytest.mark.parametrize("builder", ["fft", "rfft", "polymul",
                                     "polymul_real", "ntt_polymul"])
def test_xla_collective_bytes_match_live_ledger(builder):
    """``four_step_collective_stats`` == the ledger of the sharded builder,
    traced at the real shard count on an AbstractMesh."""
    import jax
    import jax.numpy as jnp
    from repro.core.fft import distributed as dfft
    from repro.core.ntt import NTTParams
    from repro.core.ntt import distributed as dntt
    from repro.dist import compat
    n, batch, D = 1024, 4, 4
    mesh = compat.abstract_mesh((D,), ("model",))

    def spec(dtype):
        return jax.ShapeDtypeStruct((batch, n), dtype)
    if builder == "ntt_polymul":
        build = dntt.make_sharded_ntt_polymul(
            mesh, NTTParams.make(n), axis_name="model", batch_axes=())
        got = _traced_collective_bytes(build, spec(jnp.uint32),
                                       spec(jnp.uint32))
        want = dntt.four_step_collective_stats(n, batch, D,
                                               op="polymul")["bytes"]
    else:
        make = {"fft": dfft.make_sharded_fft,
                "rfft": dfft.make_sharded_rfft,
                "polymul": dfft.make_sharded_polymul,
                "polymul_real": dfft.make_sharded_polymul_real}[builder]
        dtype = jnp.float32 if builder in ("rfft", "polymul_real") \
            else jnp.complex64
        arity = 2 if builder.startswith("polymul") else 1
        got = _traced_collective_bytes(make(mesh, batch_axes=()),
                                       *[spec(dtype)] * arity)
        want = dfft.four_step_collective_stats(n, batch, D,
                                               op=builder)["total_bytes"]
    assert got == want


def test_xla_collective_bytes_pad_odd_real_batches():
    """The distributed real tier pads an odd batch to the next even size:
    a batch of 3 moves the closed form's bytes at batch 4."""
    import jax
    import jax.numpy as jnp
    from repro.core.fft import distributed as dfft
    from repro.dist import compat
    n, D = 1024, 4
    mesh = compat.abstract_mesh((D,), ("model",))
    got = _traced_collective_bytes(
        dfft.make_sharded_rfft(mesh, batch_axes=()),
        jax.ShapeDtypeStruct((3, n), jnp.float32))
    assert got == dfft.four_step_collective_stats(n, 4, D,
                                                  op="rfft")["total_bytes"]
