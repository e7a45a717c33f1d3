#!/usr/bin/env python3
"""Find the open-loop knee of a serving configuration once, on the chip.

    python bench/knee.py --config table2_fleet --traffic zipf_open \
        --seconds 8 --rates 4,8,16,32,64

Sets the configuration up once, then offers the open-loop mix
(`bench/traffic/<traffic>.json`, its rate given here) at each rate
(frames per second) for `--seconds`, and prints per rate the reading latency
percentiles, how late the generator sent, and the median frame latency of
the first and last quarter of the window (a backlog that grows shows as a
last quarter far above the first).  The knee is the highest rate the
fleet sustains: every answer back and right, and no growing backlog (the
last quarter's median frame latency under twice the first's); the cell's
mix then offers 0.8 of it.  The sweep stops after the first rate that is
not sustained, and prints the knee last.  Exits 2 without a chip; one
process holds the chip throughout.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from harness import device as D
    from harness.fleet_cell import FleetCell

    config = json.loads((BENCH_DIR / "configs" / f"{args.config}.json")
                        .read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{args.traffic}.json")
                     .read_text())
    try:
        D.require_chips(1)
    except D.NoAccelerator as exc:
        print(f"knee: {exc}", file=sys.stderr)
        return 2
    D.enable_compile_cache()
    cell = FleetCell(config)
    knee = None
    try:
        cell.setup()
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(mix, rate_frames_per_s=rate)
            c = cell.measure(args.seed, args.seconds, traffic)
            print(json.dumps({"rate_frames_per_s": rate, **{
                k: c[k] for k in ("n_frames", "p50_ms", "p99_ms", "max_ms",
                                  "late_p99_ms", "late_max_ms",
                                  "first_quarter_ms", "last_quarter_ms",
                                  "mismatched", "missing", "failed")}}),
                  flush=True)
            if not sustained(c):
                break
            knee = rate
    finally:
        cell.close()
    print(json.dumps({"knee_frames_per_s": knee}), flush=True)
    return 0


def sustained(c: dict) -> bool:
    """Every answer back and right, and no backlog growing over the window."""
    return (c["mismatched"] == 0 and c["missing"] == 0 and c["failed"] == 0
            and c["last_quarter_ms"] < 2.0 * c["first_quarter_ms"])


if __name__ == "__main__":
    sys.exit(main())
