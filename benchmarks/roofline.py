"""Roofline analysis from the dry-run artifacts (deliverable (g)).

Per (arch x shape) on the single-pod mesh:
    compute term    = HLO_FLOPs_per_dev / peak_FLOPs          (197 TF bf16)
    memory term     = HLO_bytes_per_dev / HBM_bw              (819 GB/s)
    collective term = collective_bytes_per_dev / link_bw      (50 GB/s,
                      all-reduce counted 2x: reduce-scatter + all-gather)
plus the dominant term, MODEL_FLOPS = 6 N D (train) / 2 N D (prefill) /
2 N_active B + attention-KV flops (decode), and the usefulness ratio
MODEL_FLOPS / HLO_FLOPs which exposes remat recompute and padding waste.

Reads artifacts/dryrun/<mesh>/ written by repro.launch.dryrun. Emits CSV
rows and (with --markdown) the EXPERIMENTS.md table.
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from benchmarks.runlib import emit
from repro.configs.registry import SHAPES, get_config
# Machine model of one v5e-class chip (benchmarks/perf_iter.py reads
# these too).
PEAK_FLOPS = 197e12        # bf16 FLOPs/s per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link


def collective_term_from_ledger(led) -> float:
    """Seconds on the ICI link for traffic recorded by the
    ``repro.dist.collectives`` byte ledger — the shard_map code paths whose
    HLO the dry-run artifacts don't capture. psum counted 2x
    (reduce-scatter + all-gather halves), matching ``analyze``'s
    all-reduce accounting."""
    b = led.bytes_by_kind
    nbytes = (b["all-gather"] + b["all-to-all"] + b["ppermute"]
              + b["compressed-psum"] + 2 * b["psum"])
    return nbytes / LINK_BW


def model_flops_per_device(arch: str, shape_name: str, n_dev: int) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    N = cfg.active_param_count()
    B, S = shape.global_batch, shape.seq_len

    def attn_flops(fwd_factor: float) -> float:
        """Score+value matmuls: 4 B S_eff S H hd per layer (x0.5 causal)."""
        if cfg.mixer not in ("attn", "hymba"):
            return 0.0
        s_eff = min(S, cfg.window) if cfg.attention == "swa" else S
        per_layer = 4.0 * B * S * s_eff * cfg.num_heads * cfg.head_dim * 0.5
        return fwd_factor * per_layer * cfg.num_layers

    if shape.kind == "train":
        total = 6.0 * N * B * S + attn_flops(3.0)   # fwd + 2x bwd
    elif shape.kind == "prefill":
        total = 2.0 * N * B * S + attn_flops(1.0)
    else:  # decode: one token across the batch
        total = 2.0 * N * B
        if cfg.mixer in ("attn", "hymba"):
            from repro.models.lm import cache_len
            s_eff = cache_len(cfg, S)
            total += (4.0 * B * cfg.num_heads * cfg.head_dim * s_eff
                      * cfg.num_layers)
    return total / n_dev


def load_cells(mesh_tag: str) -> list[dict]:
    pat = os.path.join("artifacts", "dryrun", mesh_tag, "*.json")
    cells = []
    for path in sorted(glob.glob(pat)):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def analyze(cell: dict) -> dict | None:
    if cell.get("status") != "ok":
        return None
    n_dev = 1
    for v in cell["mesh"].values():
        n_dev *= v
    # prefer the unrolled cost-probe numbers: the scanned lowering's
    # cost_analysis counts while bodies once (see dryrun._cost_probe)
    probe = cell.get("probe")
    if probe:
        cell = {**cell,
                "flops_per_device": probe["flops_per_device"],
                "bytes_accessed_per_device":
                    probe["bytes_accessed_per_device"],
                "collective_bytes": {**probe["collective_bytes"],
                                     "counts": {}}}
    coll = cell["collective_bytes"]
    coll_bytes = (coll["all-gather"] + coll["reduce-scatter"]
                  + coll["all-to-all"] + coll["collective-permute"]
                  + 2 * coll["all-reduce"])
    t_comp = cell["flops_per_device"] / PEAK_FLOPS
    t_mem = cell["bytes_accessed_per_device"] / HBM_BW
    t_coll = coll_bytes / LINK_BW
    dominant = max(("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll), key=lambda kv: kv[1])
    mf = model_flops_per_device(cell["arch"], cell["shape"], n_dev)
    useful = mf / max(cell["flops_per_device"], 1e-9)
    bound = max(t_comp, t_mem, t_coll)
    return {
        **{k: cell[k] for k in ("arch", "shape")},
        "n_dev": n_dev,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dominant[0],
        "model_flops_per_dev": mf,
        "useful_flops_ratio": useful,
        "roofline_fraction": t_comp / bound if bound > 0 else 0.0,
        "peak_bytes_per_device": cell["peak_bytes_per_device"],
        "hbm_ok": cell["peak_bytes_per_device"] <= 16e9,
    }


def run(mesh_tag: str = "singlepod", markdown: bool = False) -> list[dict]:
    rows = []
    for cell in load_cells(mesh_tag):
        a = analyze(cell)
        if a is None:
            emit(f"roofline/{cell['arch']}/{cell['shape']}", 0.0,
                 f"status={cell['status']}")
            continue
        rows.append(a)
        emit(f"roofline/{a['arch']}/{a['shape']}",
             max(a["t_compute_s"], a["t_memory_s"], a["t_collective_s"])
             * 1e6,
             f"dominant={a['dominant']};comp={a['t_compute_s']:.2e}"
             f";mem={a['t_memory_s']:.2e};coll={a['t_collective_s']:.2e}"
             f";useful={a['useful_flops_ratio']:.2f}"
             f";frac={a['roofline_fraction']:.2f}")
    if markdown:
        print("\n| arch | shape | compute s | memory s | collective s | "
              "dominant | useful | roofline frac | peak GB/dev |")
        print("|---|---|---|---|---|---|---|---|---|")
        for a in rows:
            print(f"| {a['arch']} | {a['shape']} | {a['t_compute_s']:.2e} | "
                  f"{a['t_memory_s']:.2e} | {a['t_collective_s']:.2e} | "
                  f"{a['dominant']} | {a['useful_flops_ratio']:.2f} | "
                  f"{a['roofline_fraction']:.2f} | "
                  f"{a['peak_bytes_per_device'] / 1e9:.1f} |")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="singlepod",
                    choices=["singlepod", "multipod"])
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    run(args.mesh, args.markdown)
