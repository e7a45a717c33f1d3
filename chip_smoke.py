#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on a TPU.

    python chip_smoke.py                 # one chip: build, serve, evolve
    python chip_smoke.py --four-chips    # four chips: the 4-replica fleet

Everything runs in this one process (a chip belongs to one process), from
seeds only, through the entry points a user calls:

  build   `repro.compile.export` trains a small TNN for each of the five
          Table-2 datasets and emits its circuit into `<out>/emit`;
  serve   `ClassifierFleet.from_emit_dir` with every tenant on `pallas`,
          then on `pallas` with the megakernel, then on `swar`, each
          behind a `FleetServer` on localhost; a `FleetClient` sends every
          tenant a few hundred readings as SUBMIT_BATCH frames, and every
          label must equal `CircuitProgram(backend="np").predict`;
  evolve  one `repro.evolve` campaign epoch (tnn/cardio, serial) on
          `pallas`, whose Pareto front must equal the same seed on `np`.

`--four-chips` runs only the fleet with 4 replicas per tenant over 4
local devices, next to its comparison: the one-device labels and the np
reference.  Every replica's device and dispatch count is printed, and
all four devices must have taken dispatches.

Each phase prints a line; any failure exits non-zero.  The last line of
stdout is `{"ok": true, "device": {...}}`.  The script stops at once,
with no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
DATASETS = ("arrhythmia", "breast_cancer", "cardio", "redwine", "whitewine")
TNN_EPOCHS = 2           # training budget; circuit widths are the dataset's
READINGS = 320           # readings per tenant per serve phase
MAX_BATCH = 256
DEADLINE_MS = 200.0
FOUR_CHIP_READINGS = 512
FOUR_CHIP_MAX_BATCH = 64     # 8 dispatches per tenant, spread over 4
TIMEOUT_S = 300.0
EVOLVE_ARGS = ["--problem", "tnn", "--dataset", "cardio", "--workers", "0",
               "--islands", "2", "--pop", "8", "--epochs", "1",
               "--gens-per-epoch", "2", "--seed", "0", "--tnn-epochs", "2",
               "--cgp-points", "2", "--cgp-iters", "60",
               "--pcc-samples", "3000"]


class SmokeError(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


class CompileClock:
    """Seconds and count of JAX compilations, from JAX's own events."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.compiles += name.endswith("backend_compile_duration")

    def snapshot(self) -> tuple[float, int]:
        return self.seconds, self.compiles


@contextlib.contextmanager
def _phase(clock: CompileClock, out: dict):
    """Wall and compile seconds of the enclosed block, into `out`."""
    c0, n0 = clock.snapshot()
    t0 = time.perf_counter()
    yield
    c1, n1 = clock.snapshot()
    out.update(wall_s=time.perf_counter() - t0, compile_s=c1 - c0,
               compiles=n1 - n0)


def _timing(t: dict) -> str:
    return (f"wall {t['wall_s']:.2f}s, compile {t['compile_s']:.2f}s "
            f"({t['compiles']} compiles)")


def build(emit_dir: Path, log, clock: CompileClock) -> None:
    from repro.compile import export

    for name in DATASETS:
        t = {}
        with _phase(clock, t), contextlib.redirect_stdout(log):
            res = export.main(name, str(emit_dir), epochs=TNN_EPOCHS,
                              n_verify=512, n_serve=512)
        rep = res["report"]
        print(f"[build] {name}: {rep['n_features']} inputs, "
              f"{rep['n_classes']} classes, {rep['n_gates']} gates, "
              f"RTL == program; {_timing(t)}", flush=True)


def streams_and_refs(emit_dir: Path, n: int) -> dict:
    """Per tenant: (readings, np-reference labels, program path)."""
    from repro.compile.artifact import load_manifest, load_program
    from repro.data.tabular import make_dataset

    out = {}
    for i, row in enumerate(load_manifest(emit_dir)):
        x_test = make_dataset(row["dataset"]).x_test
        idx = np.random.default_rng(i).integers(0, x_test.shape[0], size=n)
        x = x_test[idx]
        path = emit_dir / row["program"]
        ref = load_program(path, backend="np").predict(x)
        out[row["name"]] = (x, ref.astype(np.int32), path)
    return out


def serve(emit_dir: Path, data: dict, clock: CompileClock, *, backend: str,
          megakernel: bool = False, replicas: int = 1,
          max_batch: int = MAX_BATCH) -> dict:
    """One fleet behind a localhost server; returns labels and stats."""
    from repro.serve import ClassifierFleet
    from repro.serve.client import FleetClient
    from repro.serve.server import FleetServer

    load, run = {}, {}
    with _phase(clock, load):           # warmup compiles every replica
        fleet = ClassifierFleet.from_emit_dir(
            emit_dir, backends=backend, max_batch=max_batch,
            deadline_ms=DEADLINE_MS, replicas=replicas,
            megakernel=megakernel)
    server = FleetServer(fleet, "127.0.0.1", 0)
    try:
        host, port = server.start_background()
        with _phase(clock, run), FleetClient(host, port) as client:
            handles = {name: client.submit_many(name, x, DEADLINE_MS)
                       for name, (x, _, _) in data.items()}
            labels = {name: np.array([h.result(TIMEOUT_S) for h in hs],
                                     dtype=np.int32)
                      for name, hs in handles.items()}
        summary = fleet.stats_summary()
        errors = list(fleet.errors)
    finally:
        server.stop()
        fleet.shutdown(drain=True)
    _check(not errors, f"serve[{backend}]: dispatch errors {errors}")
    for name, (_, ref, _) in data.items():
        _check(np.array_equal(labels[name], ref),
               f"serve[{backend}, megakernel={megakernel}]: {name} labels "
               f"differ from the np reference in "
               f"{int((labels[name] != ref).sum())} of {ref.size}")
    n = sum(v.size for v in labels.values())
    mode = f"{backend}+megakernel" if megakernel else backend
    line = (f"[serve] {mode}: {len(data)} tenants x replicas={replicas}, "
            f"{n} readings served over SUBMIT_BATCH, labels == np reference;"
            f" load {_timing(load)}; serve {_timing(run)}, "
            f"{n / run['wall_s']:.0f} readings/s, "
            f"slo_miss {summary['fleet']['n_slo_miss']}")
    if megakernel:
        mk = summary["megakernel"]
        _check(mk["launches"] >= 1, "megakernel: no fused launch")
        line += (f"; megakernel launches {mk['launches']}, peak tenants "
                 f"per launch {mk['peak_tenants_per_launch']}")
    print(line, flush=True)
    return {"labels": labels, "summary": summary}


def evolve(out_dir: Path, log, clock: CompileClock) -> None:
    from repro.evolve.__main__ import main as evolve_main

    fronts = {}
    # np first: its run builds the Phase 1-2 products (TNN training, PC
    # libraries) into the phase cache, so the pallas line times the epoch
    for i, backend in enumerate(("np", "pallas")):
        path = out_dir / f"front_{backend}.json"
        t = {}
        with _phase(clock, t), contextlib.redirect_stdout(log):
            evolve_main(EVOLVE_ARGS + [
                "--backend", backend, "--out", str(path),
                "--phase-cache", str(out_dir / "phase_cache")])
        fronts[backend] = json.loads(path.read_text())["archive"]
        built = "" if i else " (incl. the Phase 1-2 products)"
        print(f"[evolve] tnn_cardio epoch on {backend}: "
              f"{len(fronts[backend])} Pareto designs; {_timing(t)}{built}",
              flush=True)
    _check(len(fronts["np"]) >= 1, "evolve: empty Pareto front")
    _check(fronts["pallas"] == fronts["np"],
           "evolve: the pallas front differs from the np front")
    print(f"[evolve] pallas front == np front "
          f"({len(fronts['np'])} designs)", flush=True)


def four_chips(emit_dir: Path, clock: CompileClock) -> None:
    import jax

    from repro.compile.artifact import load_program

    devices = jax.devices()[:4]
    data = streams_and_refs(emit_dir, FOUR_CHIP_READINGS)
    t = {}
    with _phase(clock, t):
        for name, (x, ref, path) in data.items():
            one = load_program(path, backend="pallas",
                               devices=(devices[0],)).predict(x)
            _check(np.array_equal(one, ref),
                   f"one-device pallas labels differ from np for {name}")
    print(f"[four-chips] one-device pallas labels == np reference on "
          f"{devices[0]}; {_timing(t)}", flush=True)
    res = serve(emit_dir, data, clock, backend="pallas", replicas=4,
                max_batch=FOUR_CHIP_MAX_BATCH)
    per_device: dict[str, int] = {}
    for name, row in sorted(res["summary"]["tenants"].items()):
        for rep in row["replicas"]:
            dev = ",".join(rep["devices"])
            per_device[dev] = per_device.get(dev, 0) + rep["n_dispatches"]
            print(f"[four-chips] {name} replica {rep['index']} on {dev}: "
                  f"{rep['n_dispatches']} dispatches, "
                  f"{rep['n_readings']} readings", flush=True)
    print(f"[four-chips] dispatches per device: {per_device}", flush=True)
    _check(len(per_device) == 4 and all(per_device.values()),
           f"four-chips: not every device took dispatches: {per_device}")


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeError(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < n_chips:
        raise SmokeError(f"needs {n_chips} chips; JAX found {len(devices)}")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica fleet over 4 chips")
    ap.add_argument("--out-dir", default=str(ROOT / "chip_smoke_out"),
                    help="emit dir, logs and phase cache (wiped first)")
    args = ap.parse_args(argv)
    out_dir = Path(args.out_dir)
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    # the TPU runtime writes its logs into the run's output
    os.environ.setdefault("TPU_LOG_DIR", str(out_dir / "tpu_logs"))
    try:
        devices = require_tpu(4 if args.four_chips else 1)
    except Exception as exc:                 # noqa: BLE001 — no TPU
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.runtime import enable_compile_cache

        cache = enable_compile_cache()
        emit_dir = out_dir / "emit"
        emit_dir.mkdir()
        print(f"[setup] {len(devices)} x {devices[0].device_kind}; "
              f"compile cache {cache}; output {out_dir}", flush=True)
        clock = CompileClock()
        with open(out_dir / "log.txt", "w") as log:
            build(emit_dir, log, clock)
            if args.four_chips:
                four_chips(emit_dir, clock)
            else:
                data = streams_and_refs(emit_dir, READINGS)
                serve(emit_dir, data, clock, backend="pallas")
                serve(emit_dir, data, clock, backend="pallas",
                      megakernel=True)
                serve(emit_dir, data, clock, backend="swar")
                evolve(out_dir, log, clock)
    except Exception:                        # noqa: BLE001 — report, exit 1
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
