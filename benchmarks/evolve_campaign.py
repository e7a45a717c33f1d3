"""Campaign fitness-eval throughput: np vs SWAR (PR 1 baseline) vs Pallas.

Two workload shapes, both measured as fitness evaluations per second:

  * ``circuit``  — raw population x packed-word gate simulation (the CGP
    mutant workload of BENCH_cgp.json) through `repro.evolve.evaluator`'s
    three backends.  ``swar`` is the PR 1 `lax.scan` device path — the
    baseline the acceptance criterion names; ``pallas`` is the new kernel
    (compiled on TPU, interpret-mode on this CPU container, where the scan
    remains the fastest device path — the JSON records both honestly).
  * ``tnn_objective`` — the real campaign objective: full population NSGA-II
    fitness (hidden-gene gathers + output-plane gate sim + argmax accuracy)
    for a Table-2 problem, per eval backend.
  * ``campaign`` — end-to-end island-campaign wall clock on the synthetic
    problem: generations/s including migration, archive folding and
    checkpointing.
  * ``evolve_parallel`` — the island executor's scaling story: the same
    campaign stepped serially vs over 2 and 4 spawned workers, on a synth
    problem whose ``wait_ms`` knob blocks per fitness row — the
    device-dispatch stand-in for an expensive objective (this container
    has one visible core, so blocking overlap is the scaling the
    executor can honestly demonstrate here).  ``speedup_4w >= 2`` is the
    acceptance criterion the committed row pins.
  * ``zoo_compile`` — the batch compiler cold vs warm: a tiny
    dataset x variant sweep built from scratch (phase cache + campaigns +
    emit), then rebuilt with everything cached (manifest fingerprint
    skip), plus a forced recompile that still rides the warm phase cache.

Run directly to (re)generate the committed artifact:

    PYTHONPATH=src python -m benchmarks.evolve_campaign [BENCH_evolve.json]
"""
from __future__ import annotations

import json
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import QUICK
from benchmarks.cgp_throughput import _mutant_population, _time
from repro.core.cgp import _population_of
from repro.core.circuits import eval_vectors
from repro.evolve import Campaign, CampaignConfig, build_synth_problem
from repro.evolve.evaluator import BACKENDS, population_pc_errors


def measure_circuit(n: int, lam: int, reps: int, seed: int = 0) -> dict:
    pop = _population_of(_mutant_population(n, lam, seed))
    packed, true = eval_vectors(n)
    row = {"bench": "evolve_eval", "n": n, "lam": lam}
    for backend in BACKENDS:
        def run(b=backend):
            mae, _ = population_pc_errors(pop, packed, true, backend=b)
            np.asarray(mae)
        row[f"{backend}_evals_per_s"] = round(lam / _time(run, reps), 1)
    row["pallas_vs_swar"] = round(row["pallas_evals_per_s"]
                                  / row["swar_evals_per_s"], 3)
    return row


def measure_fused_kernel(n: int, lam: int, reps: int, seed: int = 0) -> dict:
    """Fused megakernel vs the pre-fusion two-stage Pallas path.

    Apples-to-apples on one host, one run: ``unfused`` reconstructs the
    old `population_eval_uint` (kernel emits output *words*, then the
    host-side Python loop builds one `(P, W, 32)` int32 plane per output
    bit); ``fused`` is the single `pallas_call` whose decode never leaves
    the kernel.  The committed BENCH row is the measured evidence behind
    the fused-decode acceptance criterion.
    """
    import jax.numpy as jnp

    from repro.kernels import circuit_sim as CS
    from repro.kernels import pallas_circuit_sim as PS

    pop = _population_of(_mutant_population(n, lam, seed))
    packed, _ = eval_vectors(n)
    words32 = CS.pack_words32(packed)
    plan = (pop.op.astype(np.int16), pop.in0, pop.in1, pop.outputs)
    n_out = pop.outputs.shape[1]

    def run_unfused():
        outw = PS.simulate_population(*plan, words32, pop.n_inputs)
        P, _, W = outw.shape
        shifts = jnp.arange(32, dtype=jnp.uint32)
        acc = jnp.zeros((P, W, 32), dtype=jnp.int32)
        for o in range(n_out):
            bits = ((outw[:, o, :, None] >> shifts)
                    & jnp.uint32(1)).astype(jnp.int32)
            acc = acc + (bits << o)
        np.asarray(acc.reshape(P, W * 32))

    def run_fused():
        np.asarray(PS.fused_eval_uint(*plan, words32, pop.n_inputs))

    row = {"bench": "evolve_fused_kernel", "n": n, "lam": lam}
    for name, fn in (("unfused", run_unfused), ("fused", run_fused)):
        fn()                                   # compile outside the timer
        row[f"{name}_evals_per_s"] = round(lam / _time(fn, reps), 1)
    row["fused_vs_unfused"] = round(row["fused_evals_per_s"]
                                    / row["unfused_evals_per_s"], 3)
    return row


def roofline_rows(combos) -> list[dict]:
    """Analytic roofline placement per kernel variant, per workload shape
    (plus one padded multi-tenant fleet launch) — see
    `repro.roofline.kernel_model` for the traffic model."""
    from repro.roofline.kernel_model import (CircuitShape, fleet_roofline,
                                             variant_rows)
    rows = []
    for (n, lam) in combos:
        pop = _population_of(_mutant_population(n, lam, 0))
        packed, _ = eval_vectors(n)
        shape = CircuitShape(P=pop.op.shape[0], G=pop.op.shape[1],
                             n_in=pop.n_inputs, W=2 * packed.shape[1],
                             n_out=pop.outputs.shape[1])
        for v in variant_rows(shape):
            rows.append({"bench": "kernel_roofline", "n": n, "lam": lam, **v})
    # a 4-tenant serving-fleet launch at max_batch=1024 (32 words/tenant)
    tenant_shapes = [CircuitShape(P=1, G=g, n_in=f, W=32, n_out=o)
                     for g, f, o in ((180, 21, 64), (340, 30, 32),
                                     (260, 11, 32), (260, 11, 32))]
    rl, eff = fleet_roofline(tenant_shapes)
    rows.append({"bench": "kernel_roofline", "variant": "fleet_megakernel",
                 "tenants": len(tenant_shapes), "ops": rl.flops,
                 "hbm_bytes": rl.bytes_accessed,
                 "arith_intensity": round(rl.flops / rl.bytes_accessed, 3),
                 "dominant": rl.dominant, "bound_s": rl.bound_s,
                 "padding_efficiency": round(eff, 3)})
    return rows


def measure_tnn_objective(dataset: str, pop_size: int, reps: int) -> dict:
    from repro.evolve.problems import build_tnn_problem
    prob = build_tnn_problem(dataset, epochs=4 if QUICK else 12,
                             cgp_iters=60 if QUICK else 500,
                             pcc_samples=4000 if QUICK else 30000)
    rng = np.random.default_rng(0)
    pop = np.stack([rng.integers(0, prob.domains) for _ in range(pop_size)])
    row = {"bench": "evolve_tnn_objective", "dataset": dataset,
           "pop": pop_size, "n_genes": int(prob.domains.shape[0])}
    for backend in BACKENDS:
        prob.approx.eval_backend = backend
        row[f"{backend}_evals_per_s"] = round(
            pop_size / _time(lambda: prob.objective(pop), reps), 1)
    row["pallas_vs_swar"] = round(row["pallas_evals_per_s"]
                                  / row["swar_evals_per_s"], 3)
    return row


def measure_campaign(reps: int) -> dict:
    p = build_synth_problem()
    cfg = CampaignConfig(n_islands=4, pop_size=16, n_epochs=4,
                         gens_per_epoch=4, migrate_k=2, seed=0)

    def run():
        with tempfile.TemporaryDirectory() as d:
            Campaign(p.domains, p.objective, cfg, checkpoint_dir=d,
                     name=p.name).run()

    t = _time(run, reps)
    gens = cfg.n_islands * cfg.total_generations
    return {"bench": "evolve_campaign", "islands": cfg.n_islands,
            "pop": cfg.pop_size, "generations": gens,
            "wall_s": round(t, 3), "gens_per_s": round(gens / t, 1),
            "fitness_evals_per_s": round(
                gens * cfg.pop_size / t, 1)}


def measure_parallel_campaign(epochs: int, wait_ms: float = 1.0) -> dict:
    """Serial vs 2- vs 4-worker epoch stepping on one expensive objective.

    Each mode steps the *same* campaign shape for `epochs` epochs after a
    warm-up epoch (executor spawn + worker problem builds stay out of the
    timed region — that cost is amortized over a real campaign's life).
    The objective blocks ``wait_ms`` per evaluated row — the
    device-dispatch stand-in (`build_synth_problem(wait_ms=...)`): this
    container exposes a single CPU core, so only a *blocking* objective
    can demonstrate the executor's overlap; the committed row measures
    exactly that.  Parallel workers keep per-worker memo caches, so they
    lose the cross-island dedup hits the serial memo gets — the measured
    speedup is net of that (honest, not best-case).  Fitness runs on the
    host (`np`), so the workers are pinned to the CPU and the row obeys
    the one-process-per-chip rule on a TPU too.
    """
    from repro.evolve.problems import ProblemSpec

    spec = ProblemSpec("synth", {"n_genes": 10, "domain": 6,
                                 "wait_ms": wait_ms})
    row = {"bench": "evolve_parallel", "islands": 4, "pop": 16,
           "gens_per_epoch": 5, "epochs": epochs, "wait_ms": wait_ms}
    gens = 4 * 5 * epochs
    for workers in (0, 2, 4):
        p = spec.build()
        cfg = CampaignConfig(n_islands=4, pop_size=16,
                             n_epochs=epochs + 1, gens_per_epoch=5,
                             migrate_k=2, seed=0, workers=workers)
        with Campaign(p.domains, p.objective, cfg, name=p.name,
                      problem_spec=spec) as c:
            c.step_epoch()                     # warm-up: spawn + init
            t0 = time.perf_counter()
            for _ in range(epochs):
                c.step_epoch()
            t = time.perf_counter() - t0
        key = "serial" if workers == 0 else f"workers{workers}"
        row[f"{key}_wall_s"] = round(t, 3)
        row[f"{key}_gens_per_s"] = round(gens / t, 1)
    row["speedup_2w"] = round(row["workers2_gens_per_s"]
                              / row["serial_gens_per_s"], 3)
    row["speedup_4w"] = round(row["workers4_gens_per_s"]
                              / row["serial_gens_per_s"], 3)
    return row


def measure_zoo_compile() -> dict:
    """Cold vs warm zoo build on a tiny sweep (1 dataset x 2 variants).

    ``cold_s``   — empty emit dir + empty phase cache: trains, searches,
                   compiles and emits everything.
    ``warm_s``   — identical second invocation: every entry's manifest
                   fingerprint matches and its bundle verifies, so the
                   build is pure skip (the >=10x acceptance criterion).
    ``forced_s`` — ``force=True`` with the phase cache still warm: full
                   campaign + emit per entry, but Phase 1/2 is a cache
                   load — isolates what the phase cache alone buys.
    """
    import shutil

    from repro.compile.zoo import build_zoo, make_entries
    from repro.evolve.problems import clear_phase_memo

    budgets = dict(islands=2, pop=8, epochs=1, gens_per_epoch=2,
                   migrate_k=1, tnn_epochs=2, cgp_points=1, cgp_iters=30,
                   pcc_samples=500)
    entries = make_entries(["breast_cancer"], ["base", "lean"], **budgets)
    emit = tempfile.mkdtemp(prefix="bench_zoo_emit_")
    cache = tempfile.mkdtemp(prefix="bench_zoo_phase_")
    row = {"bench": "zoo_compile", "entries": len(entries), **budgets}
    try:
        clear_phase_memo()      # genuinely cold: no in-process products
        t0 = time.perf_counter()
        rep = build_zoo(entries, emit, workers=1, cache_dir=cache)
        row["cold_s"] = round(time.perf_counter() - t0, 3)
        row["cold_built"] = len(rep["built"])
        t0 = time.perf_counter()
        rep = build_zoo(entries, emit, workers=1, cache_dir=cache)
        row["warm_s"] = round(time.perf_counter() - t0, 3)
        row["warm_cached"] = len(rep["cached"])
        clear_phase_memo()      # forced path rides the *disk* cache only
        t0 = time.perf_counter()
        build_zoo(entries, emit, workers=1, cache_dir=cache, force=True)
        row["forced_s"] = round(time.perf_counter() - t0, 3)
    finally:
        shutil.rmtree(emit, ignore_errors=True)
        shutil.rmtree(cache, ignore_errors=True)
    row["warm_speedup"] = round(row["cold_s"] / max(row["warm_s"], 1e-3), 1)
    row["forced_speedup"] = round(row["cold_s"] / row["forced_s"], 2)
    return row


def run(combos=None) -> list[dict]:
    reps = 3 if QUICK else 10
    combos = combos or ([(8, 32), (12, 32)] if QUICK
                        else [(8, 16), (8, 32), (8, 64), (12, 32)])
    rows = [measure_circuit(n, lam, reps) for (n, lam) in combos]
    rows += [measure_fused_kernel(n, lam, reps) for (n, lam) in combos]
    rows += roofline_rows(combos)
    rows.append(measure_tnn_objective("breast_cancer", 24, reps))
    rows.append(measure_campaign(max(1, reps // 3)))
    rows.append(measure_parallel_campaign(epochs=2 if QUICK else 4))
    rows.append(measure_zoo_compile())
    return rows


def main(out_path: str = "BENCH_evolve.json") -> None:
    t0 = time.perf_counter()
    rows = run()
    payload = {
        "bench": "evolve_campaign",
        "note": "campaign fitness evals/s: np (NetlistPopulation) vs swar "
                "(PR 1 lax.scan baseline) vs pallas "
                "(kernels.pallas_circuit_sim; interpret-mode on CPU, "
                "compiled on TPU), plus end-to-end island-campaign rate",
        "backend": "cpu-interpret" if _cpu() else "tpu",
        "rows": rows,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    for r in rows:
        print(r)
    print(f"wrote {out_path}")


def _cpu() -> bool:
    import jax
    return jax.default_backend() != "tpu"


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "BENCH_evolve.json")
