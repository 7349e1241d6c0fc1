"""Rehearse the four-chip cell without the chips.

    python chipbench/rehearse_gw.py virtual   # 4 virtual CPU devices
    python chipbench/rehearse_gw.py compile   # compile for a described v5e:2x2

``virtual`` drives a whole short run of ``gw-n262144-4chip-closed`` (the
engine's distributed polymul-real route at n = 2^18, model_shards 4, its
check against the plain reference) on four CPU devices, past the look for
a chip. ``compile`` compiles the same route's program, with the Pallas
kernels compiled rather than interpreted, for a described four-chip v5e at
the engine's batch, and prints its memory and collectives.
"""
import json
import os
import pathlib
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CELL = "gw-n262144-4chip-closed"


def _bench_file() -> pathlib.Path:
    """BENCHMARK.json with the cell's row, whether or not it is listed."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    if not any(w["name"] == CELL for w in bench["workloads"]):
        bench["workloads"].append({"name": CELL, "config": wl["config"],
                                   "traffic": wl["traffic"], "chips": 4,
                                   "why": wl["why"]})
    path = HERE / "out" / "rehearse-BENCHMARK.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(bench))
    return path


def virtual() -> None:
    from chipbench import harness
    cell = harness.Cell.load(CELL, bench_file=_bench_file())
    res = harness.run_cell(cell, seed=2**31 + 1, seconds=3.0, trace=False,
                           t_start=T_START, require_tpu=False)
    print(json.dumps(res))
    if not res["correct"]:
        raise SystemExit("the four-chip route is not correct on 4 virtual "
                         "devices")


def compile_for_v5e() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.fft import distributed as dfft
    from repro.kernels import fft as kfft, polymul as kpoly
    jax.config.update("jax_enable_compilation_cache", False)

    def compiled(interpret):        # Pallas kernels compiled, not interpreted
        return False if interpret is None else interpret
    kfft.resolve_interpret = kpoly.resolve_interpret = compiled
    cfg = json.loads((HERE / "configs" / "gw-matched-filter-n262144.json")
                     .read_text())
    n, batch = cfg["n"], cfg["engine"]["max_batch"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(topo.devices, ("model",))
    fn = jax.jit(dfft.make_sharded_polymul_real(mesh, batch_axes=(),
                                                backend="pallas"))
    spec = jax.ShapeDtypeStruct((batch, n), jnp.float32,
                                sharding=NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    exe = fn.lower(spec, spec).compile()
    text = exe.as_text()
    print(json.dumps({
        "compile_s": time.perf_counter() - t0,
        "tpu_custom_call": "tpu_custom_call" in text,
        "all_to_all": text.count("all-to-all("),
        "collective_permute": text.count("collective-permute("),
        "memory": str(exe.memory_analysis()),
    }))


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    if mode not in ("virtual", "compile"):
        raise SystemExit(__doc__)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if mode == "virtual":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    virtual() if mode == "virtual" else compile_for_v5e()
    return 0


if __name__ == "__main__":
    sys.exit(main())
