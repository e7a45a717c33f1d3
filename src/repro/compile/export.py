"""Compile-and-export CLI: train -> compile -> emit RTL -> verify -> serve.

The CI smoke path for the whole evolve->compile->emit->serve layer: trains
a quick exact TNN on one Table-2 dataset, lowers it, writes the structural
Verilog + EGFET report, re-evaluates the emitted RTL with the independent
`vread` reader against the compiled device program, and runs a short
sensor-stream serving burst.

Usage:  PYTHONPATH=src python -m repro.compile.export [dataset] [out_dir]
"""
from __future__ import annotations

import sys

import numpy as np

from repro.core import tnn as T
from repro.core.ternary import abc_binarize
from repro.data.tabular import make_dataset
from repro.compile.ir import lower_classifier
from repro.compile.program import CircuitProgram
from repro.compile.verilog import egfet_report, write_artifacts
from repro.compile.vread import VerilogDesign, eval_classifier_verilog
from repro.serve.engine import CircuitServingEngine


def main(dataset: str = "breast_cancer", out_dir: str = "artifacts",
         epochs: int = 6, n_verify: int = 2048, n_serve: int = 1024) -> dict:
    ds = make_dataset(dataset)
    tnn = T.train_tnn(ds, T.TNNTrainConfig(
        n_hidden=ds.spec.topology[1], epochs=epochs, lr=1e-2))
    hidden_nls, out_nls = T.exact_netlists(tnn)
    cc = lower_classifier(tnn, hidden_nls, out_nls)
    paths = write_artifacts(cc, out_dir, base=f"tnn_{dataset}",
                            dataset=dataset)
    report = egfet_report(cc)
    print(f"[compile] {dataset}: acc={tnn.test_acc:.3f} "
          f"gates={cc.ir.n_gates} depth={cc.ir.depth} "
          f"area={report['total_area_mm2']:.2f}mm^2 "
          f"power={report['total_power_mw']:.3f}mW "
          f"({report['power_source']})")
    print(f"[emit] {paths['verilog']}  {paths['report']}")
    print(f"[emit] tenant tnn_{dataset} -> {paths['manifest']} "
          f"(serve with: python -m repro.serve --emit-dir {out_dir})")

    # independent RTL re-evaluation vs the compiled device program
    rng = np.random.default_rng(0)
    xbits = rng.integers(0, 2, size=(n_verify, cc.n_features)).astype(np.uint8)
    prog = CircuitProgram.from_classifier(cc)
    design = VerilogDesign.parse(open(paths["verilog"]).read())
    rtl = eval_classifier_verilog(design, xbits)
    dev = prog.predict_bits(xbits)
    if not (rtl == dev).all():
        raise SystemExit("emitted RTL disagrees with compiled program")
    print(f"[verify] RTL == device program on {n_verify} random vectors")

    # serving smoke: classify a sensor stream, report throughput
    engine = CircuitServingEngine(prog, max_batch=256)
    engine.warmup()
    reps = int(np.ceil(n_serve / ds.x_test.shape[0]))
    stream = np.tile(ds.x_test, (reps, 1))[:n_serve]
    labels = engine.classify_stream(stream)
    xb_stream = np.asarray(abc_binarize(stream, tnn.thresholds)).astype(np.uint8)
    ref = T.predict_with_circuits(tnn, xb_stream, hidden_nls, out_nls)
    if not (labels == ref).all():
        raise SystemExit("serving labels disagree with reference path")
    s = engine.stats.summary()
    print(f"[serve] {s['n_readings']} readings in {s['n_batches']} batches: "
          f"{s['readings_per_s']:.0f} readings/s "
          f"(p50 {s['p50_ms']:.2f} ms/batch)")
    return {"report": report, "paths": paths, "serve": s}


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main(*sys.argv[1:3])
