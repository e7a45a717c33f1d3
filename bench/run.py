#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload table2_fleet.flood --seed 7 \
        --seconds 20 --trace 0

The cell, its configuration and its traffic mix are found by name:
`BENCHMARK.json` names the cell's configuration and mix, the
configuration is `bench/configs/<config>.json` (its `kind` picks the
driver, `harness/<kind>_cell.py`), the mix is `bench/traffic/<mix>.json`,
and each per-layer metric is read by `bench/metrics/<metric>.py`.  A new
cell, mix or metric is a new file and a new entry; no file here changes.

With `--trace 0` the result carries the cell's end-to-end metrics; with
`--trace 1` the window is traced with the JAX profiler and the result
carries the cell's per-layer metrics, the device's busy and window
seconds, and a breakdown of device time and idle gaps.  Either way the
run checks what the timed path produced against the plain reference and
prints each number compared beside its limit, last on standard error and
last in the result line.  The last line of standard output is the result.

Exits 2 with no result when JAX finds no TPU or fewer chips than the cell
asks for, and 1 when the run fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_plan(bench: dict, workload: str) -> dict:
    """Everything the benchmark file says about one cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{', '.join(sorted(cells))}")
    cell = cells[workload]

    def applies(m: dict) -> bool:
        return workload in m.get("workloads", [workload])

    config = json.loads((BENCH_DIR / "configs" /
                         f"{cell['config']}.json").read_text())
    traffic = json.loads((BENCH_DIR / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def read_metric(name: str, run: dict):
    """`bench/metrics/<name>.py`'s `read(run)`: a number, or None when
    the run gave it nothing to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    from harness import device as D

    try:
        plan = cell_plan(load_benchmark(), args.workload)
        import repro  # noqa: F401 — the system under test must be here

        devices = D.require_chips(plan["cell"]["chips"])
    except D.NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    except Exception:                        # noqa: BLE001 — no result
        traceback.print_exc()
        return 2
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if args.trace else None
    try:
        D.enable_compile_cache()
        peaks = D.peaks(devices[0].device_kind)
        driver = importlib.import_module(
            f"harness.{plan['config']['kind']}_cell")
        res = driver.run(plan["cell"], plan["config"], plan["traffic"],
                         args.seed, args.seconds, trace_dir, T_START,
                         devices)
        line = report(plan, res, peaks, trace_dir, len(devices))
    except Exception:                        # noqa: BLE001 — report, exit 1
        traceback.print_exc()
        return 1
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


def report(plan: dict, res: dict, peaks: dict, trace_dir, n_chips: int
           ) -> dict:
    """The result line, and the lines before it on standard error."""
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in res["compared"].items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    for note in res["notes"]:
        print(f"bench: {note}", file=sys.stderr)
    print(f"bench: compiles inside the window: {res['compiles_in_window']}",
          file=sys.stderr)
    units = {m["name"]: m["unit"] for m in
             plan["end_to_end"] + plan["per_layer"]}
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"]}
    device = dict(res["device"])
    if trace_dir is None:
        vals = dict(res["e2e"], setup_s=res["setup_s"])
        metrics = {m["name"]: vals[m["name"]] for m in plan["end_to_end"]}
    else:
        from harness.xplane import find_xplane, reduce_trace

        tr = reduce_trace(find_xplane(trace_dir))
        run = {"cell": plan["cell"]["name"], "layer": res["layer"],
               "trace": tr, "peaks": peaks, "n_chips": n_chips}
        metrics = {}
        for m in plan["per_layer"]:
            v = read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = v
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["metrics"] = {k: {"value": v, "unit": units[k]}
                       for k, v in metrics.items()}
    line["device"] = device
    for k, c in compared.items():
        print(f"bench: compared {k} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    line["compared"] = compared
    return line


if __name__ == "__main__":
    sys.exit(main())
