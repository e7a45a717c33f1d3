"""Campaign cells: fresh `repro.evolve` campaigns back to back.

Set-up builds the TNN problem at the configuration's Phase 1-2 budgets
(`build_tnn_problem`; the products are cached in `bench/.phase_cache`, so
only a checkout's first run trains and evolves them) and warms the
objective at every population size a generation can hand it.  A window
then runs passes over the mix's fixed pool of campaign seeds, each pass
in an order drawn from `seed`, each campaign whole (every epoch), with
an empty fitness memo and a checkpoint directory of its own, until
`seconds` have passed; the pass in progress finishes, and the rate is
taken over all of it.  So every seed does the same work, whole passes
of the same campaigns, in another order: the work of a campaign depends
on its own seed (how often its memo hits, how many objective calls its
islands make), and a window cut by the clock alone would weigh each run
by where the cut fell.

A recorder between the campaign's memo and the objective times every call
and keeps the genomes and objective values it returned; once the window
has closed, a sample of them drawn from the seed is scored again by the
plain reference's gate-by-gate walk.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

from harness import device as D
from harness import reference as R
from harness import work
from harness.xplane import WINDOW

PHASE_CACHE = D.BENCH_DIR / ".phase_cache"
N_CHECK = 192           # genomes of the window scored again by the reference
AREA_LIMIT = 1e-9       # widest relative area gap (PERF.md: limits)


class Recorder:
    """The objective as the campaign sees it, timed and recorded."""

    def __init__(self, objective):
        self.objective = objective
        self.on = False
        self.calls: list[tuple[float, np.ndarray, np.ndarray]] = []

    def __call__(self, pop: np.ndarray) -> np.ndarray:
        if not self.on:
            return self.objective(pop)
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.objective"):
            F = self.objective(pop)
        self.calls.append((time.perf_counter() - t0, np.array(pop), F))
        return F


def phase_products(config: dict):
    """The seeded TNN (`reference.seeded_weights`, the fleet's whitewine
    tenant) and its Phase-2 circuit libraries at the configuration's
    budgets, built with the program's CGP and PCC library builders as
    `repro.evolve` builds them for a trained TNN, and kept in
    `bench/.phase_cache` so that only a checkout's first run builds them."""
    from repro.core.cgp import evolve_pc_library
    from repro.core.pcc import build_pcc_library, pc_pareto
    from repro.core.tnn import TrainedTNN
    from repro.evolve import phase_cache as PC

    ph = config["phase"]
    key = PC.phase_key(f"{config['dataset']}:seeded", ph["seed"], 0,
                       ph["cgp_points"], ph["cgp_iters"], ph["pcc_samples"])
    try:
        return PC.load_phase(PHASE_CACHE, key)
    except FileNotFoundError:
        pass
    except PC.PhaseCacheCorruptError:
        PC.drop_entry(PHASE_CACHE, key)
    w = R.seeded_weights(config["dataset"])
    tnn = TrainedTNN(w1t=w.w1, w2t=w.w2, thresholds=np.asarray(w.thresholds),
                     train_acc=0.0, test_acc=0.0, name=config["dataset"])
    sizes, pcc_sizes = set(), set()
    for p, n in tnn.hidden_sizes():
        if p >= 1 and n >= 1:
            sizes.update([p, n])
            pcc_sizes.add((p, n))
    sizes.add(max(tnn.out_nnz, 1))
    pc_libs = {n: evolve_pc_library(n, n_points=ph["cgp_points"],
                                    max_iters=ph["cgp_iters"], seed=ph["seed"])
               for n in sorted(sizes)}
    pcc_lib = build_pcc_library(sorted(pcc_sizes), pc_libs,
                                n_samples=ph["pcc_samples"], seed=ph["seed"])
    pc_out = pc_pareto(pc_libs[max(tnn.out_nnz, 1)])
    PC.save_phase(PHASE_CACHE, key, tnn, pc_libs, pcc_lib, pc_out)
    return tnn, pc_libs, pcc_lib, pc_out


def reference_problem(dataset: str, pcc_lib, pc_out) -> R.ApproxProblem:
    """The objective's inputs as plain data for the reference: the seeded
    wiring, readings, thresholds and classes are the benchmark's own; only
    the candidates' raw popcount gate lists come from the Phase-2
    libraries (the problem's data).  The reference decides itself which
    neurons are genes and composes each hidden candidate itself."""
    w = R.seeded_weights(dataset)
    ds = R.make_dataset(dataset)

    def gates(nl) -> R.GateList:
        return R.GateList(nl.n_inputs, np.asarray(nl.op), np.asarray(nl.in0),
                          np.asarray(nl.in1), np.asarray(nl.outputs))

    genes = R.hidden_genes(w.w1)
    cands = []
    for i in genes:
        size = (int((w.w1[:, i] == 1).sum()), int((w.w1[:, i] == -1).sum()))
        cands.append([(gates(e.pc_pos), gates(e.pc_neg))
                      for e in pcc_lib.get(*size)])
    return R.ApproxProblem(
        w1=w.w1, w2=w.w2,
        xbin=(ds.x_train > w.thresholds[None, :]).astype(np.uint8),
        y=ds.y_train, hidden_genes=genes, hidden_cands=cands,
        out_cands=[gates(nl) for nl in pc_out])


class CampaignCell:
    def __init__(self, config: dict):
        self.config = config
        self.scratch = Path(tempfile.mkdtemp(prefix="bench-campaign-"))

    def setup(self) -> None:
        from repro.core.ternary import abc_binarize
        from repro.core.tnn import TNNApproxProblem

        c = self.config
        tnn, _, pcc_lib, pc_out = phase_products(c)
        ds = R.make_dataset(c["dataset"])
        self.problem = TNNApproxProblem(
            tnn=tnn, pcc_lib=pcc_lib, pc_out_lib=pc_out,
            xbin=np.asarray(abc_binarize(ds.x_train, tnn.thresholds)),
            y=ds.y_train, eval_backend=c["backend"])
        self.domains = self.problem.domains()
        self.ref_problem = reference_problem(c["dataset"], pcc_lib, pc_out)
        self.recorder = Recorder(self.problem.objective)
        # a generation hands the objective only its memo misses: every
        # population size from 1 to pop is a shape the window may use
        rng = np.random.default_rng(0)
        dom = self.domains
        for n in range(1, c["campaign"]["pop"] + 1):
            self.recorder(rng.integers(0, dom[None, :], size=(n, len(dom))))
        self.n_samples = int(self.problem.xbin.shape[0])
        self.topology = tuple(int(v) for v in tnn.topology)
        # bytes one genome's output neuron moves, per output candidate
        self.out_bytes = np.array([work.campaign_launch_bytes(
            int(nl.n_gates), int(nl.n_outputs), int(tnn.out_nnz),
            self.n_samples) for nl in pc_out])

    def window(self, seed: int, seconds: float, traffic: dict,
               trace_dir: Path | None = None) -> dict:
        import jax

        from repro.evolve.campaign import Campaign
        from repro.evolve.config import CampaignConfig

        c = self.config["campaign"]
        self.recorder.calls.clear()
        self.recorder.on = True
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        span = (jax.profiler.TraceAnnotation(WINDOW)
                if trace_dir is not None else nullcontext())
        pool = np.asarray(traffic["campaign_seeds"], dtype=np.int64)
        order = np.random.default_rng([seed, 3])
        genomes, campaigns, epochs, passes = 0, 0, 0, 0
        t0 = time.perf_counter()
        with span:
            while time.perf_counter() - t0 < seconds:
                for campaign_seed in order.permutation(pool):
                    cfg = CampaignConfig(
                        n_islands=c["islands"], pop_size=c["pop"],
                        n_epochs=c["epochs"],
                        gens_per_epoch=c["gens_per_epoch"],
                        migrate_k=c["migrate_k"], workers=c["workers"],
                        seed=int(campaign_seed),
                        eval_backend=self.config["backend"])
                    ckpt = self.scratch / f"ckpt{campaigns}"
                    camp = Campaign(self.domains, self.recorder, cfg,
                                    checkpoint_dir=str(ckpt),
                                    seed_population=np.zeros(
                                        (1, len(self.domains)),
                                        dtype=np.int64),
                                    name=f"tnn_{self.config['dataset']}")
                    try:
                        for _ in range(cfg.n_epochs):
                            with jax.profiler.TraceAnnotation("bench.epoch"):
                                camp.step_epoch()
                            epochs += 1
                        row = camp.cache_history[-1]
                        genomes += row["hits"] + row["misses"]
                    finally:
                        camp.close()
                        shutil.rmtree(ckpt, ignore_errors=True)
                    campaigns += 1
                passes += 1
        window_s = time.perf_counter() - t0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        self.recorder.on = False
        return {"window_s": window_s, "genomes": genomes,
                "passes": passes, "campaigns": campaigns, "epochs": epochs,
                "calls": list(self.recorder.calls)}

    def work(self, calls: list) -> dict:
        """Bytes and model operations of the objective calls of a window."""
        nh = len(self.domains) - self.topology[2]
        byt = sum(float(self.out_bytes[pop[:, nh:]].sum())
                  for _, pop, _ in calls)
        rows = sum(len(pop) for _, pop, _ in calls)
        return {"bytes": byt,
                "model_ops": rows * self.n_samples
                * work.tnn_ops_per_reading(self.topology),
                "objective_s": sum(t for t, _, _ in calls),
                "objective_rows": rows}

    def judge(self, calls: list, seed: int, control: bool = False) -> dict:
        """Score a seeded sample of the window's genomes with the reference;
        the widest gap of the error rate and the widest relative gap of
        the area to what the program returned.  With `control`, the
        reference's broken twin stands in the program's place."""
        pops = np.concatenate([pop for _, pop, _ in calls])
        F = np.concatenate([f for _, _, f in calls])
        rng = np.random.default_rng([seed, 5])
        pick = rng.choice(len(pops), size=min(N_CHECK, len(pops)),
                          replace=False)
        ref = R.approx_objectives(self.ref_problem, pops[pick])
        got = (R.approx_objectives(self.ref_problem, pops[pick], control=True)
               if control else F[pick])
        err_gap = np.abs(got[:, 0] - ref[:, 0])
        area_gap = np.abs(got[:, 1] - ref[:, 1]) / ref[:, 1]
        return {"checked": int(len(pick)), "error_gap": float(err_gap.max()),
                "area_rel_gap": float(area_gap.max()),
                "n_differ": int(((err_gap > 0) | (area_gap > AREA_LIMIT))
                                .sum())}

    def close(self) -> None:
        shutil.rmtree(self.scratch, ignore_errors=True)


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir: Path | None, t_start: float, devices: list) -> dict:
    """One run of a campaign cell; returns what `run.py` reports."""
    clock = D.CompileClock()
    cc = CampaignCell(config)
    try:
        cc.setup()
        compiles0 = clock.compiles
        setup_s = time.perf_counter() - t_start
        w = cc.window(seed, seconds, traffic, trace_dir)
        compiles = clock.compiles - compiles0
        dev = D.device_record(devices)
        verdict = cc.judge(w["calls"], seed)
        layer = dict(cc.work(w["calls"]), window_s=w["window_s"])
    finally:
        cc.close()
    return {
        "setup_s": setup_s, "window_s": w["window_s"],
        "compiles_in_window": compiles,
        "attempted": w["genomes"], "failed": 0,
        "e2e": {"evals_per_s": w["genomes"] / w["window_s"]},
        "compared": {"error_gap": (verdict["error_gap"], 0.0),
                     "area_rel_gap": (verdict["area_rel_gap"], AREA_LIMIT)},
        "notes": [f"passes {w['passes']}, campaigns {w['campaigns']}, "
                  f"epochs {w['epochs']}, window {w['window_s']:.3f} s, "
                  f"genomes {w['genomes']}, objective calls "
                  f"{len(w['calls'])} ({layer['objective_rows']} rows), "
                  f"reference checked {verdict['checked']} genomes, "
                  f"{verdict['n_differ']} differ"],
        "layer": layer,
        "device": dev,
    }
