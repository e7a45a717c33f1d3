"""Serving cells: a `ClassifierFleet` behind a localhost `FleetServer`,
driven by the load-generator child over TCP.

Set-up lowers the configuration's classifiers (seeded ternary weights,
`repro.compile.lower_classifier`), saves them into a scratch emit
directory, loads them with `ClassifierFleet.from_emit_dir` (which warms
every replica's program), starts the server and the child, and lets the
child send a warm-up round.  A window then runs the mix for `seconds`;
the fleet's counters are read on both sides of it, and the child judges
every answer against the plain reference.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path

from harness import device as D
from harness import reference as R
from harness import work
from harness.xplane import WINDOW

LOADGEN = Path(__file__).resolve().parent / "loadgen.py"


def build_emit_dir(tenants: list[str], emit_dir: Path) -> None:
    """Lower each tenant's seeded classifier and register it."""
    import numpy as np

    from repro.compile import lower_classifier
    from repro.compile.artifact import register_tenant, save_program
    from repro.core.tnn import TrainedTNN, exact_netlists

    for name in tenants:
        w = R.seeded_weights(name)
        tnn = TrainedTNN(w1t=w.w1, w2t=w.w2,
                         thresholds=np.asarray(w.thresholds),
                         train_acc=0.0, test_acc=0.0, name=name)
        cc = lower_classifier(tnn, *exact_netlists(tnn))
        path = save_program(cc, emit_dir / f"{name}_program.npz")
        register_tenant(emit_dir, {"name": name, "program": path,
                                   "dataset": name})


class Child:
    """The load generator process and its line protocol."""

    def __init__(self, spec: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, str(LOADGEN), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env)

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def expect(self, event: str) -> dict:
        while True:
            line = self.proc.stdout.readline()
            if not line:
                raise RuntimeError(f"load generator exited (rc "
                                   f"{self.proc.wait()}) before {event!r}")
            if line.startswith("{"):
                msg = json.loads(line)
                if msg.get("event") == event:
                    return msg

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout):
            f.close()


class FleetCell:
    def __init__(self, config: dict):
        self.config = config
        self.tenants = [t["name"] for t in config["tenants"]]
        self.scratch = Path(tempfile.mkdtemp(prefix="bench-fleet-"))
        self.fleet = self.server = None

    def setup(self) -> None:
        from repro.serve import ClassifierFleet
        from repro.serve.server import FleetServer

        cfg = self.config
        emit = self.scratch / "emit"
        build_emit_dir(self.tenants, emit)
        self.fleet = ClassifierFleet.from_emit_dir(
            emit, backends=cfg["backend"], max_batch=cfg["max_batch"],
            deadline_ms=cfg["deadline_ms"], replicas=cfg["replicas"])
        self.server = FleetServer(self.fleet, "127.0.0.1", 0)
        self.port = self.server.start_background()[1]

    def counters(self) -> dict:
        s = self.fleet.stats_summary()
        return {"n_batches": s["fleet"]["n_batches"],
                "busy_s": s["fleet"]["busy_s"],
                "readings": {n: s["tenants"][n]["n_readings"]
                             for n in self.tenants}}

    def start_child(self, seed: int, seconds: float, traffic: dict,
                    control: str | None = None) -> Child:
        return Child({"seed": seed, "seconds": seconds, "traffic": traffic,
                      "tenants": self.tenants,
                      "replicas": self.config["replicas"],
                      "control": control})

    def connect(self, child: Child) -> None:
        """Point a started child at the server; returns once its warm-up
        round of frames has been answered."""
        child.send({"port": self.port})
        child.expect("ready")

    def window(self, child: Child, trace_dir: Path | None = None) -> dict:
        """One measured window; returns the child's judgement, the fleet
        counters' change over it and the window's host seconds."""
        import jax

        before = self.counters()
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        span = (jax.profiler.TraceAnnotation(WINDOW)
                if trace_dir is not None else nullcontext())
        t0 = time.perf_counter()
        with span:
            child.send({"go": True})
            child.expect("window_end")
        window_s = time.perf_counter() - t0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        after = self.counters()
        result = child.expect("result")
        return {"child": result, "window_s": window_s,
                "delta": {"n_batches": after["n_batches"] - before["n_batches"],
                          "busy_s": after["busy_s"] - before["busy_s"],
                          "readings": {n: after["readings"][n]
                                       - before["readings"][n]
                                       for n in self.tenants}}}

    def measure(self, seed: int, seconds: float, traffic: dict,
                control: str | None = None) -> dict:
        """A whole window with a fresh child: start, connect, run, judge."""
        child = self.start_child(seed, seconds, traffic, control)
        try:
            self.connect(child)
            return self.window(child)["child"]
        finally:
            child.close()

    def close(self) -> None:
        import shutil

        try:
            if self.server is not None:
                self.server.stop()
            if self.fleet is not None:
                self.fleet.shutdown(drain=False)
        finally:
            shutil.rmtree(self.scratch, ignore_errors=True)

    def work(self, delta: dict) -> dict:
        """Bytes and model operations of the readings served in a window."""
        topo = {t["name"]: t["topology"] for t in self.config["tenants"]}
        n = delta["readings"]
        return {"bytes": sum(work.serving_window_bytes(topo[t][0], n[t])
                             for t in self.tenants),
                "model_ops": sum(work.tnn_ops_per_reading(topo[t]) * n[t]
                                 for t in self.tenants)}


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir: Path | None, t_start: float, devices: list) -> dict:
    """One run of a serving cell; returns what `run.py` reports."""
    clock = D.CompileClock()
    fc = FleetCell(config)
    child = None
    try:
        child = fc.start_child(seed, seconds, traffic)   # imports meanwhile
        fc.setup()
        fc.connect(child)
        compiles0 = clock.compiles
        setup_s = time.perf_counter() - t_start
        w = fc.window(child, trace_dir)
        compiles = clock.compiles - compiles0
        dev = D.device_record(devices)
    finally:
        if child is not None:
            child.close()
        fc.close()
    c = w["child"]
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
        "layer": {"fleet": w["delta"], "window_s": w["window_s"],
                  **fc.work(w["delta"])},
        "device": dev,
    }
