"""Serve-engine tier: op-dispatch registry + continuous batching.

Unit half:
  * the registry (``launch/ops.py``) is the ONLY dispatch surface — the
    serve module carries no per-op ladder, and its CLI choices/help derive
    from the registry;
  * registry parity: every op served through the engine bit-matches the
    direct BoundOp call on the same payloads (the old single-op path);
  * complex results come back as float32 words and reach the host as the
    public kernels' complex64 result, bit for bit;
  * mixed-op / mixed-n bucketing correctness against the numpy oracles;
  * tail batches execute at their ACTUAL size (never padded to the block);
  * latency percentiles are monotone (p50 <= p90 <= p99 <= max);
  * bounded-queue admission raises Backpressure when full;
  * registry validation errors exit the CLI cleanly (argparse error).

Dist half (subprocess, 8 virtual devices):
  * odd-batch distributed real tier pinned vs numpy (the ROADMAP
    leftover: internal pad + slice instead of the even-batch guard);
  * a mixed stream including both distributed routes served from one
    engine process.
"""
import os
import pathlib

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import REPO, run_in_subprocess_devices
from repro.kernels import ops as kops
from repro.launch import ops as op_registry
from repro.launch import serve
from repro.launch.engine import Backpressure, ServeEngine


# ---------------------------------------------------------------------------
# Registry is the one source of op truth
# ---------------------------------------------------------------------------

def test_registry_covers_all_ops_and_serve_has_no_ladder():
    names = op_registry.op_names()
    assert set(names) == {"fft", "rfft", "polymul", "polymul-real",
                          "polymul-mod", "nas-ft"}
    # PR 10 promoted the old string grep ("elif op ==" in serve's source,
    # dodgeable by renaming the variable) to the AST dispatch-ladder lint
    # rule: the whole launch/ package must carry ZERO op-name string
    # ladders outside the ops.py registry (docs/static_analysis.md).
    from repro import analysis
    launch_dir = os.path.dirname(op_registry.__file__)
    res = analysis.analyze_paths([launch_dir])
    ladders = [f for f in res.findings if f.rule == "dispatch-ladder"]
    assert ladders == [], \
        "serve must dispatch through the registry, not a per-op ladder:\n" \
        + "\n".join(f.format() for f in ladders)
    # CLI surface derives from the registry
    help_text = op_registry.cli_help()
    for name in names:
        assert name in help_text
    assert set(op_registry.ops_using("modulus_bits")) == {"polymul-mod"}
    assert set(op_registry.ops_using("model_shards")) == {"polymul-real",
                                                          "polymul-mod"}
    for spec in op_registry.registry():
        assert spec.summary and spec.arity in (1, 2)
    # narrow() strips exactly the knobs an op ignores, so the mixed engine
    # can feed one process-level context to every op (the rejections of
    # foreign knobs are rows of tests/test_routes.py)
    ctx = op_registry.OpContext(modulus_bits=100, model_shards=8)
    assert op_registry.get_op("fft").narrow(ctx) == op_registry.OpContext()
    assert op_registry.get_op("polymul-real").narrow(ctx) == \
        op_registry.OpContext(model_shards=8)
    assert op_registry.get_op("polymul-mod").narrow(ctx) == ctx


# ---------------------------------------------------------------------------
# Registry parity: engine == direct BoundOp call, per op
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op,kw", [
    ("fft", {}),
    ("rfft", {}),
    ("polymul", {}),
    ("polymul-real", {}),
    ("polymul-mod", {}),
    ("polymul-mod", {"modulus_bits": 100}),
])
def test_engine_parity_with_direct_dispatch(op, kw, rng):
    """Each op served through the continuous-batching engine bit-matches
    the direct BoundOp batch call on the same payloads (batches [4, 2]:
    the tail exercises actual-size dispatch)."""
    n, cap, total = 64, 4, 6
    svc = serve.FFTService(n, cap, op, **kw)
    payloads = [svc.bound.random_payload(rng) for _ in range(total)]
    for rid, p in enumerate(payloads):
        svc.submit(rid, p)
    stats = svc.run(total)
    assert stats["served"] == total
    sizes = stats["buckets"][f"{op}/n={n}"]["batch_sizes"]
    assert sizes == [4, 2], sizes
    # direct dispatch at the SAME batch boundaries the scheduler used
    direct = [svc.bound.to_numpy(svc.bound.execute(payloads[:4])),
              svc.bound.to_numpy(svc.bound.execute(payloads[4:]))]
    flat = [row for arr in direct for row in arr]
    for rid in range(total):
        got, want = svc.results[rid], flat[rid]
        if got.dtype == object or np.issubdtype(got.dtype, np.integer):
            assert (got == want).all(), f"rid={rid}"
        else:
            np.testing.assert_array_equal(got, want)
        svc.bound.verify(payloads[rid], got)


# ---------------------------------------------------------------------------
# Complex results: float32 words on the wire, complex64 on the host
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("n", [64, 256])
@pytest.mark.parametrize("op,public,width", [
    ("fft", kops.fft, lambda n: n),
    ("rfft", kops.rfft, lambda n: n // 2 + 1),
    ("polymul", functools.partial(kops.polymul, mode="circular"),
     lambda n: n),
], ids=["fft", "rfft", "polymul"])
def test_complex_results_come_back_as_words(op, public, width, n, backend,
                                            monkeypatch, rng):
    """A complex route's device result is float32 words, (re, im)
    interleaved; ``to_numpy`` turns them into the complex64 batch the
    public kernel function gives, bit for bit, at the same shape, and the
    engine delivers those rows."""
    monkeypatch.setenv("REPRO_FFT_BACKEND", backend)
    batch = 5
    engine = ServeEngine(max_batch=batch, max_pending=16)
    bound = engine.register(op, n)
    assert bound.result_dtype == np.complex64
    payloads = [bound.random_payload(rng) for _ in range(batch)]
    out = bound.execute(payloads)
    assert out.dtype == jnp.float32
    assert out.shape == (batch, 2 * width(n))
    got = bound.to_numpy(out)
    assert got.dtype == np.complex64 and got.shape == (batch, width(n))
    operands = tuple(jnp.asarray(c)
                     for c in op_registry._stack_operands(bound, payloads))
    want = np.asarray(jax.jit(functools.partial(public, backend=backend))(
        *(o.astype(jnp.complex64) if op == "polymul" else o
          for o in operands)))
    assert want.dtype == np.complex64 and want.shape == got.shape
    assert np.array_equal(got, want)
    for rid, p in enumerate(payloads):
        engine.submit(op, n, p, rid=rid)
    engine.run(batch)
    for rid in range(batch):
        assert engine.results[rid].dtype == np.complex64
        assert np.array_equal(engine.results[rid], want[rid]), rid


# ---------------------------------------------------------------------------
# Mixed-op / mixed-n bucketing
# ---------------------------------------------------------------------------

def test_mixed_stream_bucketing_correctness(rng):
    """One engine, 3 ops x 2 lengths interleaved: every request lands in
    its shape bucket and every served result passes its op's oracle."""
    ops = ("fft", "rfft", "polymul-real")
    lens = (64, 128)
    engine = ServeEngine(max_batch=4, max_pending=64)
    combos = [(op, n) for op in ops for n in lens]
    for op, n in combos:
        engine.register(op, n)
    engine.warmup()
    kept = {}
    total = 18
    for rid in range(total):
        op, n = combos[rid % len(combos)]
        p = engine.bound(op, n).random_payload(rng)
        kept[rid] = (op, n, p)
        engine.submit(op, n, p, rid=rid)
    stats = engine.run(total)
    assert stats["served"] == total
    assert len(stats["buckets"]) == len(combos)
    assert sum(b["served"] for b in stats["buckets"].values()) == total
    for b in stats["buckets"].values():
        assert all(1 <= s <= 4 for s in b["batch_sizes"]), b
        assert 0 < b["utilization"] <= 1.0
    for rid, (op, n, p) in kept.items():
        engine.bound(op, n).verify(p, engine.results[rid])
        # results keep their bucket's shape: no cross-bucket mixups
        width = {"fft": n, "rfft": n // 2 + 1, "polymul-real": n}[op]
        assert engine.results[rid].shape == (width,)


def test_tail_batch_runs_at_actual_size(rng):
    """11 requests through a cap-8 bucket must dispatch as [8, 3] — the
    tail batch executes at 3 rows, never padded to the block."""
    engine = ServeEngine(max_batch=8, max_pending=64)
    engine.register("rfft", 64)
    engine.warmup()
    for rid in range(11):
        engine.submit("rfft", 64,
                      rng.standard_normal(64).astype(np.float32), rid=rid)
    stats = engine.run(11)
    sizes = stats["buckets"]["rfft/n=64"]["batch_sizes"]
    assert sizes == [8, 3], sizes
    assert sum(sizes) == 11 and max(sizes) <= 8
    util = stats["buckets"]["rfft/n=64"]["utilization"]
    assert abs(util - (11 / 16)) < 1e-9


def test_latency_percentiles_monotone(rng):
    engine = ServeEngine(max_batch=4, max_pending=64)
    engine.register("fft", 64)
    engine.warmup()
    for rid in range(10):
        engine.submit(
            "fft", 64,
            (rng.standard_normal(64)
             + 1j * rng.standard_normal(64)).astype(np.complex64), rid=rid)
    stats = engine.run(10)
    lat = stats["latency_ms"]
    assert 0 < lat["p50"] <= lat["p90"] <= lat["p99"] <= lat["max"]
    assert lat["p50"] <= lat["mean"] * 10   # sanity: same order of magnitude
    assert stats["throughput_per_s"] > 0
    assert stats["compute_throughput_per_s"] >= stats["throughput_per_s"]


def test_backpressure_bounded_queue(rng):
    engine = ServeEngine(max_batch=4, max_pending=3)
    engine.register("fft", 64)
    x = (rng.standard_normal(64) + 0j).astype(np.complex64)
    for rid in range(3):
        engine.submit("fft", 64, x, rid=rid)
    with pytest.raises(Backpressure):
        engine.submit("fft", 64, x, rid=99, block=False)
    with pytest.raises(Backpressure):
        engine.submit("fft", 64, x, rid=99, timeout=0.05)
    stats = engine.run(3)         # draining frees the queue again
    assert stats["served"] == 3
    engine.submit("fft", 64, x, rid=3, block=False)
    assert engine.run(4)["served"] == 4


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_cli_engine_service_mixed_stream():
    stats = serve.main(["--service", "engine",
                        "--ops", "fft,rfft,polymul-real",
                        "--ns", "64,128", "--requests", "12",
                        "--batch", "4"])
    assert stats["served"] == 12
    assert len(stats["buckets"]) == 6
    lat = stats["latency_ms"]
    assert lat["p50"] <= lat["p99"]


def test_cli_exits_with_registry_validation_error(capsys):
    for argv in (["--op", "polymul-mod", "--modulus-bits", "100",
                  "--model-shards", "8"],
                 ["--op", "fft", "--modulus-bits", "40"],
                 ["--service", "engine", "--ops", "fft,nope", "--ns", "64"]):
        with pytest.raises(SystemExit) as exc:
            serve.main(argv)
        assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "single-limb" in err          # the registry's own message


def test_fft_service_legacy_surface(rng):
    """The single-op wrapper keeps the pre-engine surface (plan / route /
    _fn / ntt_params / rns) that callers and older tests assert against."""
    svc = serve.FFTService(64, 2, "polymul-mod")
    assert svc.route == "polymul-mod-single"
    assert svc.plan.exact and svc.ntt_params is not None and svc.rns is None
    rns_svc = serve.FFTService(64, 2, "polymul-mod", modulus_bits=100)
    assert rns_svc.route == "polymul-mod-rns"
    assert rns_svc.rns is not None and rns_svc.rns.k > 1
    a = rng.integers(0, svc.ntt_params.q, (2, 64)).astype(np.uint32)
    b = rng.integers(0, svc.ntt_params.q, (2, 64)).astype(np.uint32)
    out = np.asarray(svc._fn(jnp.asarray(a), jnp.asarray(b)))
    from repro.core.ntt import negacyclic_polymul
    assert (out == negacyclic_polymul(a, b, svc.ntt_params)).all()


# ---------------------------------------------------------------------------
# Dist half: odd-batch distributed real tier + mixed distributed stream
# ---------------------------------------------------------------------------

@pytest.mark.dist
def test_odd_batch_distributed_real_vs_numpy():
    """The distributed real tier serves ODD batches (internal zeros-row
    pad + slice, replacing the even-batch guard) and stays pinned to the
    f64 numpy oracle; rfft/irfft roundtrip at odd B too."""
    out = run_in_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.fft import distributed as dfft
from repro.launch import serve

mesh = jax.make_mesh((8,), ("model",))
rng = np.random.default_rng(0)
for B in (1, 3, 5):
    a = rng.standard_normal((B, 1024)).astype(np.float32)
    b = rng.standard_normal((B, 1024)).astype(np.float32)
    got = np.asarray(jax.jit(
        dfft.make_sharded_polymul_real(mesh, batch_axes=()))(a, b))
    want = np.fft.ifft(np.fft.fft(a.astype(np.float64))
                       * np.fft.fft(b.astype(np.float64))).real
    err = np.max(np.abs(got - want))
    assert got.shape == (B, 1024) and err < 1e-3, (B, err)
    x = rng.standard_normal((B, 1024)).astype(np.float32)
    pk = jax.jit(dfft.make_sharded_rfft(mesh, batch_axes=()))(x)
    back = np.asarray(jax.jit(
        dfft.make_sharded_irfft(mesh, batch_axes=()))(pk))
    assert back.shape == (B, 1024)
    np.testing.assert_allclose(back, x, rtol=1e-4, atol=1e-4)

# the serve route accepts odd batches end-to-end now
svc = serve.FFTService(1024, 3, "polymul-real", model_shards=8)
assert svc.route == "polymul-real-distributed"
stats = serve.main(["--service", "fft", "--n", "1024", "--batch", "3",
                    "--requests", "7", "--op", "polymul-real",
                    "--model-shards", "8"])
assert stats["served"] == 7, stats
print("OK")
""", n_devices=8)
    assert "OK" in out


@pytest.mark.dist
def test_engine_mixed_stream_with_distributed_routes():
    """One engine process: local fft/rfft buckets next to the distributed
    polymul-real and polymul-mod tiers, all drained with continuous
    batching and verified against their oracles."""
    out = run_in_subprocess_devices("""
from repro.launch import serve

stats = serve.main(["--service", "engine",
                    "--ops", "fft,rfft,polymul-real,polymul-mod",
                    "--ns", "512", "--model-shards", "8",
                    "--requests", "16", "--batch", "4"])
assert stats["served"] == 16, stats
routes = {b["route"] for b in stats["buckets"].values()}
assert "polymul-real-distributed" in routes, routes
assert "polymul-mod-distributed" in routes, routes
assert "fft" in routes and "rfft-real" in routes, routes
lat = stats["latency_ms"]
assert 0 < lat["p50"] <= lat["p99"], lat
print("OK")
""", n_devices=8)
    assert "OK" in out


_CACHE_SNIPPET = """
import jax, jax.numpy as jnp
from repro.launch.cache import setup_compile_cache
path = setup_compile_cache()
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, monkeypatch, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory
    and receives the entries; unset, the cache is <checkout>/.jax_cache."""
    from repro.launch.cache import DEFAULT_DIR, ENV_VAR
    if from_env:
        monkeypatch.setenv(ENV_VAR, str(tmp_path))
    else:
        monkeypatch.delenv(ENV_VAR, raising=False)
    out = run_in_subprocess_devices(_CACHE_SNIPPET, n_devices=1,
                                    timeout=300).split()
    want = str(tmp_path) if from_env else str(DEFAULT_DIR)
    assert out[-2:] == [want, want]
    assert DEFAULT_DIR.parent == pathlib.Path(REPO)
    if from_env:
        assert any(tmp_path.iterdir())
