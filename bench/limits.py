#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python bench/limits.py --workload table2_fleet.flood --seconds 5 \
        --seeds 1-12 --control-seeds 13-15

Sets the cell up once, then runs one window per seed at the cell's own
load and prints, per seed, each number the run compares: for `--seeds`
as the program gives it, for `--control-seeds` with the control (the
plain reference in a lower precision, or with one guarantee broken) in
the program's place.  One JSON line per window; exits 2 without a chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]

from run import cell_plan, load_benchmark  # noqa: E402

CONTROL = "bfloat16"      # the serving reference one precision down


def seeds(spec: str) -> list[int]:
    out = []
    for part in filter(None, spec.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from harness import device as D

    plan = cell_plan(load_benchmark(), args.workload)
    try:
        devices = D.require_chips(plan["cell"]["chips"])
    except D.NoAccelerator as exc:
        print(f"limits: {exc}", file=sys.stderr)
        return 2
    D.enable_compile_cache()
    cfg, traffic = plan["config"], plan["traffic"]
    runs = [(s, False) for s in seeds(args.seeds)] + \
        [(s, True) for s in seeds(args.control_seeds)]
    if cfg["kind"] == "fleet":
        from harness.fleet_cell import FleetCell

        cell = FleetCell(cfg)
        try:
            cell.setup()
            for seed, control in runs:
                c = cell.measure(seed, args.seconds, traffic,
                                 CONTROL if control else None)
                print(json.dumps({"seed": seed, "control": control,
                                  "mismatched_labels": c["mismatched"],
                                  "missing_labels": c["missing"],
                                  "attempted": c["attempted"]}), flush=True)
        finally:
            cell.close()
    else:
        from harness.campaign_cell import CampaignCell

        cell = CampaignCell(cfg)
        try:
            cell.setup()
            for seed, control in runs:
                w = cell.window(seed, args.seconds, traffic)
                v = cell.judge(w["calls"], seed, control=control)
                print(json.dumps({"seed": seed, "control": control,
                                  "error_gap": v["error_gap"],
                                  "area_rel_gap": v["area_rel_gap"],
                                  "differ": v["n_differ"],
                                  "checked": v["checked"],
                                  "genomes": w["genomes"]}), flush=True)
        finally:
            cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
