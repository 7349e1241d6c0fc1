import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective statistics.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k [--multi-pod] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

The XLA_FLAGS line above MUST precede any jax import (device count locks on
first init). Nothing here allocates device memory: inputs are
ShapeDtypeStructs and compilation is AOT.
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import (ASSIGNED, SHAPES, cell_supported,
                                    get_config)
from repro.dist import compat
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim import adamw
from repro.train import step as step_lib

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", )
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|u32|s8|u8|pred|c64)\[([\d,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
          "s8": 1, "u8": 1, "pred": 1, "c64": 8}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in (post-SPMD) HLO."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(type_str):
            dtype, dims = sm.group(1), sm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * _BYTES[dtype]
        out[kind] += total
        counts[kind] += 1
    out["counts"] = counts
    return out


def _cost_dict(compiled) -> dict:
    """compiled.cost_analysis() as a dict."""
    return compiled.cost_analysis() or {}


def _lower_cell(cfg, shape, mesh):
    """Build abstract inputs + shardings for a cell and lower it."""
    params_abs = lm.abstract_params(cfg)
    pspecs = S.sanitize_tree(lm.param_specs(cfg), params_abs, mesh)
    psh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspecs,
                       is_leaf=lambda x: isinstance(x, P))

    with compat.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = S.opt_config_for(cfg)
            opt_abs = S.abstract_opt_state(cfg, opt_cfg)
            ospecs = S.sanitize_tree(
                adamw.state_specs(pspecs, opt_cfg), opt_abs, mesh)
            osh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), ospecs,
                               is_leaf=lambda x: isinstance(x, P))
            bsds, bsp = S.batch_specs(cfg, shape, with_labels=True)
            bsds = {k: v for k, v in bsds.items() if v is not None}
            bsp = {k: NamedSharding(mesh, S.sanitize_spec(
                v, bsds[k].shape, mesh)) for k, v in bsp.items()
                if k in bsds}
            fn = step_lib.make_train_step(cfg, opt_cfg)
            jfn = jax.jit(fn, in_shardings=(psh, osh, bsp),
                          out_shardings=(psh, osh, None),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(params_abs, opt_abs, bsds)
        elif shape.kind == "prefill":
            bsds, bsp = S.batch_specs(cfg, shape, with_labels=False)
            bsds = {k: v for k, v in bsds.items() if v is not None}
            bsp = {k: NamedSharding(mesh, S.sanitize_spec(
                v, bsds[k].shape, mesh)) for k, v in bsp.items()
                if k in bsds}
            fn = step_lib.make_prefill_step(cfg)
            jfn = jax.jit(fn, in_shardings=(psh, bsp))
            lowered = jfn.lower(params_abs, bsds)
        else:  # decode
            state_abs = S.abstract_decode_state(cfg, shape)
            sspecs = S.sanitize_tree(lm.decode_state_specs(cfg), state_abs,
                                     mesh)
            ssh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), sspecs,
                               is_leaf=lambda x: isinstance(x, P))
            dsds, dsp = S.decode_input_specs(cfg, shape)
            tok_sh = (NamedSharding(mesh, S.sanitize_spec(
                dsp["token"], dsds["token"].shape, mesh))
                if dsds.get("token") is not None else None)
            emb_sh = (NamedSharding(mesh, S.sanitize_spec(
                dsp["embed"], dsds["embed"].shape, mesh))
                if dsds.get("embed") is not None else None)
            pos_stream_sh = (NamedSharding(mesh, S.sanitize_spec(
                dsp["positions"], dsds["positions"].shape, mesh))
                if "positions" in dsds else None)

            fn = step_lib.make_decode_step(cfg)
            jfn = jax.jit(
                fn,
                in_shardings=(psh, ssh, tok_sh,
                              NamedSharding(mesh, P()), pos_stream_sh,
                              emb_sh),
                out_shardings=(None, ssh),
                donate_argnums=(1,))
            lowered = jfn.lower(params_abs, state_abs, dsds.get("token"),
                                dsds["pos"], dsds.get("positions"),
                                dsds.get("embed"))
    return lowered


def _probe_period(cfg) -> int:
    """Probe layer-count unit: the attention-pattern period (gemma3's 5:1
    layout needs whole periods so per-layer averages match the real mix)."""
    if cfg.attention == "local_global":
        return cfg.local_global_ratio + 1
    return 2


def _cost_probe(cfg, shape, mesh) -> dict | None:
    """XLA's cost_analysis counts while-loop bodies ONCE, so scanned-layer
    (and scanned-KV-block) FLOPs/bytes are undercounted by the trip count.
    Probe: lower UNROLLED variants at L = k and L = 2k layers with the KV
    scan collapsed to a single block, then extrapolate affinely in L —
    exact for costs of the form fixed + per_layer * L.
    """
    k = _probe_period(cfg)
    if cfg.num_layers < 2 * k:
        return None
    vals = {}
    for L in (k, 2 * k):
        cfg_p = dataclasses.replace(
            cfg, num_layers=L, scan_layers=False,
            attn_kv_block=shape.seq_len)
        lowered = _lower_cell(cfg_p, shape, mesh)
        compiled = lowered.compile()
        ca = _cost_dict(compiled)
        coll = collective_bytes(compiled.as_text())
        vals[L] = {"flops": ca.get("flops", 0.0),
                   "bytes": ca.get("bytes accessed", 0.0),
                   "coll": coll}
    L_real = cfg.num_layers
    lo, hi = vals[k], vals[2 * k]

    def extrap(a, b):
        per_layer = (b - a) / k
        return max(0.0, a + per_layer * (L_real - k))

    # the grad-accumulation microbatch scan is itself a while loop counted
    # once — scale per-step costs back up by the trip count
    accum = max(1, getattr(cfg, "grad_accum_steps", 1))
    coll_out = {}
    for key in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute"):
        coll_out[key] = extrap(lo["coll"][key], hi["coll"][key]) * accum
    return {
        "flops_per_device": extrap(lo["flops"], hi["flops"]) * accum,
        "bytes_accessed_per_device":
            extrap(lo["bytes"], hi["bytes"]) * accum,
        "collective_bytes": coll_out,
        "probe_layers": [k, 2 * k],
    }


def run_cell(arch: str, shape_name: str, mesh, *, verbose: bool = True,
             probe: bool = True, overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": reason}

    t0 = time.time()
    with compat.set_mesh(mesh):
        lowered = _lower_cell(cfg, shape, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = _cost_dict(compiled)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    n_dev = mesh.size
    result = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": dict(mesh.shape),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": ca.get("flops", 0.0),
        "bytes_accessed_per_device": ca.get("bytes accessed", 0.0),
        "argument_bytes_per_device": ma.argument_size_in_bytes,
        "output_bytes_per_device": ma.output_size_in_bytes,
        "temp_bytes_per_device": ma.temp_size_in_bytes,
        "alias_bytes_per_device": ma.alias_size_in_bytes,
        "peak_bytes_per_device": (ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
        "collective_bytes": coll,
        "model_params": get_config(arch).param_count(),
        "active_params": get_config(arch).active_param_count(),
    }
    if probe:
        try:
            with compat.set_mesh(mesh):
                pr = _cost_probe(cfg, shape, mesh)
            if pr is not None:
                result["probe"] = pr
        except Exception as e:
            result["probe_error"] = f"{type(e).__name__}: {e}"
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={tuple(mesh.shape.values())}"
              f" lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"flops/dev={result['flops_per_device']:.3e} "
              f"peak_bytes/dev={result['peak_bytes_per_device']:.3e}")
        print(f"  memory_analysis: {ma}")
        print(f"  collectives: { {k: v for k, v in coll.items() if k != 'counts'} }")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    tag = "multipod" if args.multi_pod else "singlepod"
    outdir = os.path.join(args.out, tag)
    os.makedirs(outdir, exist_ok=True)

    cells = []
    if args.all:
        for arch in ASSIGNED:
            for shape_name in SHAPES:
                cells.append((arch, shape_name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in cells:
        path = os.path.join(outdir, f"{arch}__{shape_name}.json")
        try:
            res = run_cell(arch, shape_name, mesh)
        except Exception as e:  # a failure here is a bug in our sharding
            traceback.print_exc()
            res = {"arch": arch, "shape": shape_name, "status": "error",
                   "error": f"{type(e).__name__}: {e}"}
            failures.append((arch, shape_name))
        with open(path, "w") as f:
            json.dump(res, f, indent=2)
    if failures:
        raise SystemExit(f"FAILED cells: {failures}")
    print(f"dry-run complete: {len(cells)} cells -> {outdir}")


if __name__ == "__main__":
    main()
