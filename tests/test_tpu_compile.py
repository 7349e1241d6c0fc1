"""Compile rehearsal: the serving Pallas kernels compile for a TPU v5e.

Nothing runs here: each kernel is lowered and compiled ahead of time for a
described (not attached) v5e chip, at the largest sweep length the engine
serves (n = 16384) and at the engine's batch, and at the planner's local
ceilings. This catches what interpret mode cannot — unaligned or strided
slices, primitives Mosaic has no lowering for, blocks that break the (8, 128)
tiling rule, kernels that outgrow VMEM — at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, and a test
file that loaded it at collection would break parallel test workers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.fft import planner
from repro.core.ntt import NTTParams, RNSParams
from repro.kernels import fft as kfft
from repro.kernels import ntt as kntt
from repro.kernels import polymul as kpoly

N = 16384
ENGINE_BATCH = 64     # chip_smoke.py's --batch: the engine's block cap


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args, **kw):
    text = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in text


def _float_cases(n, b):
    """name -> (jitted kernel entry point, operand shapes, static kwargs)."""
    return {
        "fft_planes": (kfft.fft_planes, [(b, n)] * 2, {}),
        "ifft_planes": (kfft.fft_planes, [(b, n)] * 2, {"inverse": True}),
        "rfft_planes": (kfft.rfft_planes, [(b, n)], {}),
        "irfft_planes": (kfft.irfft_planes, [(b, n // 2)] * 2, {}),
        "polymul_complex_planes": (kpoly.polymul_complex_planes,
                                   [(b, n)] * 4, {}),
        "polymul_real_planes": (kpoly.polymul_real_planes, [(b, n)] * 2, {}),
    }


@pytest.mark.parametrize("name", sorted(_float_cases(N, 1)))
def test_float_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes, kw = _float_cases(N, ENGINE_BATCH)[name]
    args = [_spec(s, jnp.float32, one_chip) for s in shapes]
    _assert_kernel(fn, *args, interpret=False, **kw)


@pytest.mark.parametrize("inverse", [False, True])
def test_ntt_batched_compiles_for_v5e(one_chip, inverse):
    x = _spec((ENGINE_BATCH, N), jnp.uint32, one_chip)
    _assert_kernel(kntt.ntt_batched, x, NTTParams.make(N), inverse=inverse,
                   interpret=False)


def test_ntt_polymul_compiles_for_v5e(one_chip):
    a = _spec((ENGINE_BATCH, N), jnp.uint32, one_chip)
    _assert_kernel(kntt.ntt_polymul, a, a, NTTParams.make(N),
                   interpret=False)


@pytest.mark.parametrize("n,bits", [(N, 60), (8192, 218)])
def test_rns_ntt_polymul_compiles_for_v5e(one_chip, n, bits):
    """At n=16384 and at the HomomorphicEncryption.org 128-bit point
    (N=8192, log Q=218) the chip smoke serves: scalar-prefetch layout."""
    rns = RNSParams.make(n, modulus_bits=bits)
    a = _spec((rns.k, ENGINE_BATCH, n), jnp.uint32, one_chip)
    _assert_kernel(kntt.rns_ntt_polymul, a, a, rns, interpret=False)


def test_rns_own_basis_compiles_for_v5e(one_chip):
    """The kernel the he128 cell serves: N=8192, 210-bit Q, in Q's own
    basis (its 7 primes), at the cell's batch of 16."""
    rns = RNSParams.make(8192, modulus_bits=210).own_basis
    assert rns.k == 7
    a = _spec((rns.k, 16, rns.n), jnp.uint32, one_chip)
    _assert_kernel(kntt.rns_ntt_polymul, a, a, rns, interpret=False)


def test_fft_compiles_at_planner_ceiling(one_chip):
    """_MAX_LOCAL_N is a compile fact: the float kernels compile at the
    local ceiling at their planned block."""
    n = planner._MAX_LOCAL_N
    for name in ("fft_planes", "polymul_complex_planes",
                 "polymul_real_planes"):
        fn, shapes, kw = _float_cases(n, 8)[name]
        _assert_kernel(fn, *[_spec(s, jnp.float32, one_chip) for s in shapes],
                       interpret=False, **kw)


def test_ntt_compiles_at_planner_ceiling(one_chip):
    n = planner._MAX_LOCAL_N_EXACT
    a = _spec((8, n), jnp.uint32, one_chip)
    _assert_kernel(kntt.ntt_polymul, a, a, NTTParams.make(n),
                   interpret=False)


def test_hbm_polymul_real_route_compiles_at_its_deployment(one_chip):
    """The gw-n524288-closed deployment: n = 2^19 (256 s at 2048 Hz) real
    products at the engine's batch of 16 through the hbm tier, Pallas
    kernels compiled; each of its launches fits the VMEM budget."""
    from repro.core.fft import hbm
    n, batch = 1 << 19, 16
    p = planner.plan(n, batch, real=True)
    assert p.tier == "hbm"
    x = _spec((batch, n), jnp.float32, one_chip)
    text = hbm.polymul_real_hbm.lower(
        x, x, inner_n=p.inner_n, interpret=False).compile().as_text()
    for name in ("tpu_custom_call", "hbm_outer_fwd", "hbm_outer_inv",
                 "fft_planes"):
        assert name in text, name
    n1 = n // p.inner_n
    assert hbm.outer_vmem_bytes(n, n1) <= kfft.VMEM_BUDGET_BYTES
    inner_block = kfft._fit_block(p.block_b, batch * n1)
    assert kfft.block_vmem_bytes(p.inner_n, inner_block) \
        <= kfft.VMEM_BUDGET_BYTES


def test_planned_blocks_fit_the_vmem_budget():
    """Every planned block at the ceiling and at the engine's n fits the
    budget the kernels are planned against."""
    for n in (N, planner._MAX_LOCAL_N):
        for real in (False, True):
            blk = kfft.plan_batch_block(n, real=real)
            assert kfft.block_vmem_bytes(n, blk, real=real) \
                <= kfft.VMEM_BUDGET_BYTES
    assert np.log2(planner._MAX_LOCAL_N) % 1 == 0


@pytest.mark.parametrize("view", [(256, 256, 512), (1, 256, 256 * 512)],
                         ids=["y", "z"])
@pytest.mark.parametrize("inverse", [False, True])
def test_axis_dft_compiles_at_npb_class_b(one_chip, view, inverse):
    """The column pass over NPB FT Class B's (NZ, NY, NX) = (256, 256,
    512) grid: its y view and its z view, each launch within the VMEM
    budget."""
    from repro.core.fft import hbm
    x = _spec(view, jnp.float32, one_chip)
    text = hbm.axis_dft.lower(x, x, inverse=inverse,
                              interpret=False).compile().as_text()
    assert "tpu_custom_call" in text and "axis_dft" in text
    assert hbm.axis_vmem_bytes(view[1], view[2]) <= kfft.VMEM_BUDGET_BYTES


def test_nas_ft_route_compiles_at_npb_class_b(one_chip):
    """The ftB-512x256x256-closed deployment: one Class B job (2^25
    points, 20 steps) through the nas-ft route's solve, x rows, both
    column passes and the step loop in one program."""
    from repro.core.fft import ft3d
    from repro.launch import ops
    _, shape, niter = ops.NAS_FT_CLASSES[1 << 25]
    assert planner.plan_grid(shape).tier == "grid"
    u0 = _spec((1, 1 << 25), jnp.complex64, one_chip)
    text = ft3d.nas_ft.lower(u0, shape=shape, niter=niter,
                             alpha=ops.NAS_FT_ALPHA,
                             interpret=False).compile().as_text()
    for name in ("tpu_custom_call", "axis_dft", "fft_planes"):
        assert name in text, name
