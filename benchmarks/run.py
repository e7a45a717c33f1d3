"""The paper's tables and figures — one module each.

Speed is measured on the chip by `bench/` (see `BENCHMARK.json`), not
here.

Prints ``name,us_per_call,derived`` CSV rows: `name` is bench/row id,
`us_per_call` the wall time of producing that row's experiment, `derived`
a compact JSON payload with the row's metrics.

Env: REPRO_BENCH_FULL=1 switches from quick budgets to paper-scale budgets.
Usage: PYTHONPATH=src python -m benchmarks.run [--only table2,fig4,...]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

BENCH_MODULES = {
    "table2": "benchmarks.table2_accuracy",
    "fig4": "benchmarks.fig4_pc_pareto",
    "fig5": "benchmarks.fig5_pcc_pareto",
    "fig6": "benchmarks.fig6_area_estimate",
    "fig7": "benchmarks.fig7_tnn_pareto",
    "fig8": "benchmarks.fig8_nsga2",
    "table3": "benchmarks.table3_sota",
    "variation": "benchmarks.variation_robustness",
}
BENCHES = list(BENCH_MODULES)


def _load(name: str):
    import importlib
    return importlib.import_module(BENCH_MODULES[name])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    names = [n.strip() for n in args.only.split(",")] if args.only else BENCHES
    unknown = [n for n in names if n not in BENCH_MODULES]
    if unknown:
        raise SystemExit(
            f"unknown bench name(s) {', '.join(unknown)}; "
            f"valid: {', '.join(BENCHES)}")

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.perf_counter()
        try:
            rows = _load(name).run()
            us = (time.perf_counter() - t0) * 1e6
            per_row = us / max(len(rows), 1)
            for row in rows:
                rid = row.pop("bench", name)
                extra = {k: v for k, v in row.items()}
                print(f"{rid},{per_row:.0f},{json.dumps(extra)}")
        except Exception as e:   # noqa: BLE001 — benches report and continue
            failures += 1
            us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{us:.0f},{json.dumps({'error': str(e)[:200]})}")
            import traceback
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark groups failed")


if __name__ == "__main__":
    main()
