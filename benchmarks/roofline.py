"""§Roofline: derive the three roofline terms per (arch x shape x mesh) from
the dry-run artifacts (reports/dryrun/*/*.json).

    compute term    = FLOPs_per_device / peak_FLOPs          (197 TF/s bf16)
    memory term     = bytes_per_device / HBM_bw              (819 GB/s)
    collective term = collective_bytes_per_device / link_bw  (~50 GB/s/link)

FLOPs/bytes/collective-bytes come from the trip-count-weighted HLO analysis
(launch/hlo_analysis.py) — NOT from compiled.cost_analysis(), which counts
scan bodies once. MODEL_FLOPS is the analytic 6·N·D / 6·N_active·D (or the
per-family equivalent) recorded by the step builders.

Also emits the expansion-step bandwidth sweep (DESIGN.md §8): corpus-side
HBM bytes per expansion for the pre-gathered vs index-fused engine across
fp32/bf16/int8 residency, and the HBM-roof time per step each implies —
the projected speedup of the fused path on the bandwidth-bound backend.

And the tile/occupancy model for the wide-block fused kernels
(kernels/autotune.py): per candidate Bt, the grid-step count, double-buffer
VMEM footprint, and the modeled kernel time ``steps × max(overhead,
tile_bytes / HBM_bw)`` — DMA of tile t+1 overlaps compute of tile t, so a
step costs whichever is longer, and per-step dispatch overhead amortizes
÷Bt. This is the structural reason the original grid=(Q, B) single-row
kernels lost wall-clock while winning the bytes model.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12      # TPU v5e bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link
VMEM_BYTES = 16 * 2**20  # v5e per-core VMEM
GRID_STEP_S = 1e-6       # per-grid-step dispatch overhead (order of mag.)


def load_reports(dryrun_dir: str = "reports/dryrun", mesh: str = "single"
                 ) -> List[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, mesh, "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def roofline_row(rep: dict) -> dict:
    n_dev = rep["n_devices"]
    hlo = rep["hlo_analysis"]
    flops_dev = hlo["flops"]
    # bf16-equivalent bytes: strips the XLA:CPU f32-upcast artifact (TPU runs
    # the activation path natively in bf16); falls back for older reports
    bytes_dev = hlo.get("bytes_bf16eq", hlo["bytes_accessed"])
    coll_dev = hlo["total_collective_bytes"]
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    model_flops = rep["static_meta"].get("model_flops", 0.0)
    model_flops_dev = model_flops / n_dev
    useful = model_flops_dev / flops_dev if flops_dev else 0.0
    bound = max(terms.values())
    # roofline fraction: useful model flops per second at the bound vs peak
    mfu_bound = (model_flops_dev / bound) / PEAK_FLOPS if bound > 0 else 0.0
    return {
        "arch": rep["arch"], "shape": rep["shape"], "mesh": rep["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": model_flops, "hlo_flops_dev": flops_dev,
        "useful_flops_ratio": useful, "roofline_fraction": mfu_bound,
        "temp_gib": rep["memory_analysis"]["temp_bytes"] / 2**30,
        "args_gib": rep["memory_analysis"]["argument_bytes"] / 2**30,
        "collective_breakdown": hlo["collective_bytes"],
    }


def expansion_sweep_rows(Q: int = 128, B: int = 32, C: int = 8,
                         D: int = 64):
    """Fused-vs-unfused × fp32/bf16/int8 expansion-step bandwidth model."""
    from benchmarks.common import expansion_bytes_model
    rows = []
    for mode, c in (("guitar", C), ("sl2g", B)):
        ref = expansion_bytes_model(Q, B, c, D, "float32", False)
        for fused in (False, True):
            dtypes = ("float32",) if not fused \
                else ("float32", "bfloat16", "int8")
            for dt in dtypes:
                by = expansion_bytes_model(Q, B, c, D, dt, fused)
                label = ("fused_" if fused else "pregather_") + dt
                rows.append(
                    f"roofline/expansion/{mode}/{label},0.00,"
                    f"bytes_per_step={by};bytes_per_eval={by / (Q * c):.0f};"
                    f"t_hbm={by / HBM_BW:.3e}s;x_vs_pregather={ref / by:.2f}")
    return rows


def tile_occupancy_rows(Q: int = 128, B: int = 32, C: int = 8, D: int = 64,
                        bts=(1, 4, 8, 16, 32)):
    """Tile/occupancy model for the wide-block fused kernels: per Bt, the
    grid-step count, the double-buffered VMEM tile footprint, and the
    modeled time ``steps × max(step_overhead, tile_bytes / HBM_bw)``
    (double-buffering overlaps tile t+1's DMA with tile t's compute, so a
    grid step costs whichever side is longer). Bt=1 is the pre-autotune
    rowwise grid — per-step overhead × M with nothing amortized."""
    kernels = {
        # kernel -> rows gathered per engine step
        "neighbor_rank_fused": Q * B,
        "deepfm_score_fused": Q * C,
        "deepfm_grad_fused": Q,
    }
    # every row comes from the kernel layout: float32 padded to 128 lanes
    row_bytes = 4 * -(-D // 128) * 128
    rows = []
    for kern, m in kernels.items():
        t_row = None
        for bt in bts:
            steps = -(-m // bt)
            tile_bytes = bt * row_bytes
            vmem = 2 * tile_bytes            # double buffer
            t_model = steps * max(GRID_STEP_S, tile_bytes / HBM_BW)
            if bt == 1:
                t_row = t_model
            rows.append(
                f"roofline/tile/{kern}@bt{bt},0.00,"
                f"grid_steps={steps};tile_kib={tile_bytes / 1024:.1f};"
                f"vmem_buf_kib={vmem / 1024:.1f};"
                f"vmem_frac={vmem / VMEM_BYTES:.4f};"
                f"t_model={t_model:.3e}s;"
                f"x_vs_rowwise={(t_row / t_model if t_row else 1.0):.2f}")
    return rows


def run(dryrun_dir: str = "reports/dryrun", mesh: str = "single"):
    rows = []
    table = []
    if mesh == "single":
        rows += expansion_sweep_rows()
        rows += tile_occupancy_rows()
    for rep in load_reports(dryrun_dir, mesh):
        r = roofline_row(rep)
        table.append(r)
        rows.append(
            f"roofline/{mesh}/{r['arch']}:{r['shape']},0.00,"
            f"compute={r['t_compute_s']:.3e}s;memory={r['t_memory_s']:.3e}s;"
            f"collective={r['t_collective_s']:.3e}s;dominant={r['dominant']};"
            f"useful={r['useful_flops_ratio']:.2f};"
            f"roofline_frac={r['roofline_fraction']:.3f}")
    out_path = os.path.join(dryrun_dir, f"roofline_{mesh}.json")
    if table:
        with open(out_path, "w") as f:
            json.dump(table, f, indent=1)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
