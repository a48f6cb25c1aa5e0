"""Roofline table (deliverable (g)): reads the dry-run artifacts produced by
``python -m repro.launch.dryrun --all`` and emits the per-(arch x shape)
three-term roofline with the dominant bottleneck. Single-pod (16x16) mesh
per the spec; the 2x16x16 artifacts prove the pod axis shards."""
from __future__ import annotations

import glob
import json
import os

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")


def load_reports(mesh: str = "16x16"):
    reps = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"*_{mesh}.json"))):
        if mesh == "16x16" and "2x16x16" in os.path.basename(path):
            continue
        with open(path) as f:
            reps.append(json.load(f))
    return reps


def run(refresh: bool = False):
    reps = load_reports()
    rows = [("bench_roofline/arch_x_shape", "compute_s", "memory_s",
             "collective_s", "dominant", "useful_flop_frac", "mfu_ub")]
    for r in reps:
        t = r["roofline"]
        rows.append((
            f"{r['arch']}@{r['shape']}",
            f"{t['compute_s']:.5f}",
            f"{t['memory_s']:.5f}",
            f"{t['collective_s']:.5f}",
            t["dominant"].replace("_s", ""),
            round(t.get("useful_flop_fraction", 0), 3),
            round(t.get("mfu_upper_bound", 0), 4),
        ))
    if len(reps) < 33:
        rows.append((f"WARNING_only_{len(reps)}_reports_run_dryrun_all",
                     "", "", "", "", "", ""))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit_csv
    from repro.common.compilation import enable_compile_cache

    enable_compile_cache()
    emit_csv(run())
