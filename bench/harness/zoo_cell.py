"""Zoo serving cells: many tenants, several seeded draws of each Table-2
classifier, behind a localhost `FleetServer` with the megakernel on.

The fleet cell (`fleet_cell.FleetCell`) with three changes: the
emit directory holds each tenant's draw (`zoo_reference.py`), the fleet
is built as `python -m repro.serve --emit-dir <zoo> --backend pallas
--megakernel` builds it (`megakernel` from the configuration), and the
load generator child is `zoo_loadgen.py`.  A window also records the change of the program's
span and counter table (`repro.obs`) over it, where the fused-launch
readers find their counters; a program without those counters leaves
them out.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

from harness import device as D
from harness import zoo_reference as Z
from harness.fleet_cell import Child, FleetCell

ZOO_LOADGEN = Path(__file__).resolve().parent / "zoo_loadgen.py"


def build_zoo_emit_dir(tenants: list[str], emit_dir: Path) -> None:
    """Lower each tenant's draw and register it with its dataset."""
    import numpy as np

    from repro.compile import lower_classifier
    from repro.compile.artifact import register_tenant, save_program
    from repro.core.tnn import TrainedTNN, exact_netlists

    for name in tenants:
        w = Z.seeded_weights(name)
        tnn = TrainedTNN(w1t=w.w1, w2t=w.w2,
                         thresholds=np.asarray(w.thresholds),
                         train_acc=0.0, test_acc=0.0, name=name)
        cc = lower_classifier(tnn, *exact_netlists(tnn))
        path = save_program(cc, emit_dir / f"{name}_program.npz")
        register_tenant(emit_dir, {"name": name, "program": path,
                                   "dataset": Z.split(name)[0]})


def obs_table() -> dict | None:
    """The program's span and counter table, or None without one."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()


def table_delta(before: dict | None, after: dict | None) -> dict | None:
    if before is None or after is None:
        return None
    zero = {"n": 0, "s": 0.0}
    return {k: {"n": v["n"] - before.get(k, zero)["n"],
                "s": v["s"] - before.get(k, zero)["s"]}
            for k, v in after.items()}


class ZooChild(Child):
    """`Child` running the zoo's load generator."""

    def __init__(self, spec: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, str(ZOO_LOADGEN), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env)


class ZooCell(FleetCell):
    def setup(self) -> None:
        from repro.serve import ClassifierFleet
        from repro.serve.server import FleetServer

        cfg = self.config
        emit = self.scratch / "emit"
        build_zoo_emit_dir(self.tenants, emit)
        self.fleet = ClassifierFleet.from_emit_dir(
            emit, backends=cfg["backend"], max_batch=cfg["max_batch"],
            deadline_ms=cfg["deadline_ms"], replicas=cfg["replicas"],
            megakernel=cfg["megakernel"])
        self.server = FleetServer(self.fleet, "127.0.0.1", 0)
        self.port = self.server.start_background()[1]

    def start_child(self, seed: int, seconds: float, traffic: dict,
                    control: str | None = None) -> Child:
        return ZooChild({"seed": seed, "seconds": seconds,
                         "traffic": traffic, "tenants": self.tenants,
                         "replicas": self.config["replicas"],
                         "control": control})

    def window(self, child: Child, trace_dir: Path | None = None) -> dict:
        before = obs_table()
        w = super().window(child, trace_dir)
        w["spans"] = table_delta(before, obs_table())
        return w


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir: Path | None, t_start: float, devices: list) -> dict:
    """One run of a zoo cell; returns what `run.py` reports."""
    clock = D.CompileClock()
    zc = ZooCell(config)
    child = None
    try:
        child = zc.start_child(seed, seconds, traffic)   # imports meanwhile
        zc.setup()
        zc.connect(child)
        compiles0 = clock.compiles
        setup_s = time.perf_counter() - t_start
        w = zc.window(child, trace_dir)
        compiles = clock.compiles - compiles0
        dev = D.device_record(devices)
    finally:
        if child is not None:
            child.close()
        zc.close()
    c = w["child"]
    layer = {"fleet": w["delta"], "window_s": w["window_s"],
             **zc.work(w["delta"])}
    if w["spans"] is not None:
        layer["spans"] = w["spans"]
    return {
        "setup_s": setup_s, "window_s": w["window_s"],
        "compiles_in_window": compiles,
        "attempted": c["attempted"], "failed": c["failed"],
        "e2e": {"readings_per_s": c["correct_in_window"] / seconds,
                "p99_ms": c["p99_ms"]},
        "compared": {"mismatched_labels": (c["mismatched"], 0),
                     "missing_labels": (c["missing"], 0)},
        "notes": [f"generator: {c['n_frames']} frames, send late p99 "
                  f"{c['late_p99_ms']:.3f} ms, max {c['late_max_ms']:.3f} ms; "
                  f"latency p50 {c['p50_ms']:.3f} ms, p99 {c['p99_ms']:.3f} ms"],
        "layer": layer,
        "device": dev,
    }
