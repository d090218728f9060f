"""Roofline analysis from dry-run artifacts (deliverable g).

Three terms per (arch x shape) cell on the single-pod mesh (256 x v5e):

  compute    T_c = FLOPs_global / (chips * 197e12 bf16 FLOP/s)
  memory     T_m = bytes_global / (chips * 819e9 B/s HBM)
  collective T_x = collective_bytes_per_device / 50e9 B/s ICI link

FLOPs/bytes come from the jaxpr walk (scan trip counts folded in — XLA's
cost_analysis counts while bodies once, see dryrun.py); collective bytes
come from the result-shape census over the SPMD-partitioned HLO (shapes in
the partitioned module are already per-device shards). The byte term is an
un-fused upper bound on HBM traffic (every op's operands+results counted),
so T_m is pessimistic; T_c is exact for the jaxpr; the dominant-term calls
below are robust to that bias (noted per-cell).

    PYTHONPATH=src python -m repro.launch.roofline [--dir experiments/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(dirname: str, mesh: str = "pod16x16") -> List[Dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(dirname, f"*__{mesh}.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def analyze(rec: Dict) -> Dict:
    chips = rec["n_devices"]
    t_c = rec["jaxpr_flops"] / (chips * PEAK_FLOPS)
    # fused-traffic model when available (un-fused census is a ~2-6x
    # overcount; EXPERIMENTS.md §Perf iteration 1)
    nbytes = rec.get("jaxpr_bytes_fused", rec["jaxpr_bytes"])
    t_m = nbytes / (chips * HBM_BW)
    t_x = rec["collectives"]["total_bytes"] / LINK_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dom = max(terms, key=terms.get)
    useful = rec["model_flops"] / max(rec["jaxpr_flops"], 1.0)
    # roofline fraction: useful model flops vs what the dominant term allows
    t_ideal = rec["model_flops"] / (chips * PEAK_FLOPS)
    frac = t_ideal / max(terms[dom], 1e-30)
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "dominant": dom, "useful_ratio": useful,
        "roofline_frac": frac,
        "hbm_gb_per_dev": (rec["memory"]["argument_bytes"]
                           + rec["memory"]["temp_bytes"]) / 1e9,
    }


_ADVICE = {
    ("compute",): "raise useful-FLOP ratio (less remat recompute, fp8 "
                  "matmul inputs)",
    ("memory",): "cut bytes: fuse elementwise chains, larger microbatch, "
                 "bf16 collectives/state, ring SWA cache",
    ("collective",): "reshard: keep FSDP gathers off the critical path, "
                     "bf16 gradient all-reduce, 2D all-gather",
}


def advice(dom: str) -> str:
    return _ADVICE[(dom,)]


def table(rows: List[Dict]) -> str:
    rows = sorted(rows, key=lambda r: (r["arch"],
                                       SHAPE_ORDER.index(r["shape"])))
    out = ["| arch | shape | T_compute (s) | T_memory (s) | T_collective (s) "
           "| dominant | 6ND/HLO | roofline frac | HBM GB/dev |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3f} | "
            f"{r['t_memory_s']:.3f} | {r['t_collective_s']:.3f} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.2f} | {r['hbm_gb_per_dev']:.1f} |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun"))
    ap.add_argument("--mesh", default="pod16x16")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    recs = [r for r in load(args.dir, args.mesh) if r.get("ok")]
    rows = [analyze(r) for r in recs]
    md = table(rows)
    print(md)
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    print(f"\ndominant-term census: {doms}")
    if args.out:
        with open(args.out, "w") as f:
            f.write(md + "\n")


if __name__ == "__main__":
    main()
