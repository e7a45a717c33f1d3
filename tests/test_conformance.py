"""Cross-backend conformance: differential fuzz over every circuit evaluator.

Five executors claim to compute the same function of (netlist, test
vectors):

  1. `Netlist.simulate` / `eval_uint` — the serial uint64 reference,
  2. `NetlistPopulation` — structure-of-arrays batched numpy,
  3. `kernels.circuit_sim.simulate_population` — jitted uint32-SWAR scan,
  4. `kernels.pallas_circuit_sim` — the Pallas kernel (interpret on CPU),
  5. `CircuitProgram` (jax + np backends) over the lowered `CircuitIR`,
plus the emitted-Verilog route: `compile.verilog.emit_netlist_module` ->
`compile.vread.VerilogDesign`, an evaluator that never sees the IR.

Any two of them disagreeing on any vector of any random netlist is a
failure.  The seeded sweep always runs; when hypothesis is installed the
same oracle is additionally driven by shrinking random shapes (example
budget scales with REPRO_CONFORMANCE_EXAMPLES — the nightly CI job raises
it), and the `slow`-marked sweep covers larger populations and widths.

The fused-megakernel matrix extends the oracle to the serving path: the
single-launch fused kernel (`fused_eval_uint`) and the multi-tenant
`fleet_eval_words` (heterogeneous plans padded to one gate budget) must
match `predict_with_circuits` on all five golden datasets, and a
hypothesis property pins that padding mixed gate counts / input widths /
word widths into one launch never leaks bits across tenants.
"""
import os

import numpy as np
import pytest

from repro.compile.program import CircuitProgram
from repro.compile.verilog import emit_netlist_module
from repro.compile.vread import VerilogDesign
from repro.core import circuits as C
from repro.kernels import circuit_sim as CS
from repro.kernels import pallas_circuit_sim as PS


def _rand_bits(rng, S, n):
    return (rng.random((S, n)) < 0.5).astype(np.uint8)


def assert_conformance(pop: C.NetlistPopulation, bits: np.ndarray,
                       check_programs: bool = True) -> None:
    """All evaluators must agree on every vector for every individual."""
    S = bits.shape[0]
    packed = C.pack_vectors(bits)
    ref = pop.eval_uint(packed)[:, :S]                       # batched numpy

    for p in range(pop.size):                                # serial reference
        nl = pop.netlist(p)
        np.testing.assert_array_equal(
            nl.eval_uint(packed)[:S], ref[p],
            err_msg=f"NetlistPopulation row {p} != Netlist.eval_uint")

    words32 = CS.pack_words32(packed)
    swar = np.asarray(CS.population_eval_uint(
        pop.op.astype(np.int32), pop.in0, pop.in1, pop.outputs, words32,
        pop.n_inputs))[:, :S]
    np.testing.assert_array_equal(swar, ref, err_msg="SWAR scan != numpy")

    pallas = np.asarray(PS.population_eval_uint(
        pop.op, pop.in0, pop.in1, pop.outputs, words32, pop.n_inputs))[:, :S]
    np.testing.assert_array_equal(pallas, ref,
                                  err_msg="Pallas kernel != numpy")

    if not check_programs:
        return
    for p in range(pop.size):
        nl = pop.netlist(p, name=f"fuzz{p}")
        for backend in ("jax", "np"):
            got = CircuitProgram.from_netlist(nl, backend=backend)
            np.testing.assert_array_equal(
                got.eval_bits(bits), ref[p],
                err_msg=f"CircuitProgram[{backend}] != numpy (row {p})")
        design = VerilogDesign.parse(emit_netlist_module(nl, "fuzz"))
        np.testing.assert_array_equal(
            design.eval_uint("fuzz", bits), ref[p],
            err_msg=f"Verilog reader != numpy (row {p})")


def _fuzz_case(seed: int, max_inputs=8, max_gates=32, max_pop=8,
               max_vectors=200, check_programs=True) -> None:
    rng = np.random.default_rng(seed)
    n_in = int(rng.integers(1, max_inputs + 1))
    n_gates = int(rng.integers(0, max_gates + 1))
    n_out = int(rng.integers(1, min(8, n_in + n_gates) + 1))
    P = int(rng.integers(1, max_pop + 1))
    S = int(rng.integers(1, max_vectors + 1))
    pop = C.random_netlist_population(rng, n_in, n_gates, n_out, P)
    assert_conformance(pop, _rand_bits(rng, S, n_in),
                       check_programs=check_programs)


N_EXAMPLES = int(os.environ.get("REPRO_CONFORMANCE_EXAMPLES", "20"))


@pytest.mark.parametrize("seed", range(N_EXAMPLES))
def test_random_netlists_all_backends_agree(seed):
    _fuzz_case(seed)


def test_per_individual_word_planes_agree():
    """Device paths must also match when every genome gets its own words —
    the TNN integration's output-plane shape."""
    rng = np.random.default_rng(1234)
    pop = C.random_netlist_population(rng, 6, 20, 3, 5)
    S = 150
    bits = np.stack([_rand_bits(rng, S, 6) for _ in range(pop.size)])
    packed = C.pack_vectors(bits)                        # (P, n_in, W)
    ref = pop.eval_uint(packed)[:, :S]
    words32 = CS.pack_words32(packed)
    swar = np.asarray(CS.population_eval_uint(
        pop.op.astype(np.int32), pop.in0, pop.in1, pop.outputs, words32,
        pop.n_inputs))[:, :S]
    pallas = np.asarray(PS.population_eval_uint(
        pop.op, pop.in0, pop.in1, pop.outputs, words32, pop.n_inputs))[:, :S]
    np.testing.assert_array_equal(swar, ref)
    np.testing.assert_array_equal(pallas, ref)


def test_degenerate_shapes_agree():
    """Gateless netlists, single-word batches, repeated output taps."""
    rng = np.random.default_rng(99)
    for (n_in, n_gates, n_out, P, S) in [(1, 0, 1, 1, 1), (2, 0, 2, 3, 5),
                                         (4, 1, 4, 2, 64), (3, 40, 1, 6, 65),
                                         (8, 16, 8, 4, 33)]:
        pop = C.random_netlist_population(rng, n_in, n_gates, n_out, P)
        assert_conformance(pop, _rand_bits(rng, S, n_in))


def test_zero_width_word_plane_returns_empty():
    """Regression (PR 9): `W == 0` used to hand pallas_call a zero-size
    grid/block; now both kernel entry points short-circuit to empty
    results, mirroring the gateless-plan pad guard."""
    from repro.kernels import dispatch as D

    rng = np.random.default_rng(7)
    pop = C.random_netlist_population(rng, 4, 10, 2, 3)
    empty = np.zeros((4, 0), dtype=np.uint32)
    words = np.asarray(PS.simulate_population(
        pop.op, pop.in0, pop.in1, pop.outputs, empty, 4))
    assert words.shape == (3, 2, 0)
    ints = np.asarray(PS.population_eval_uint(
        pop.op, pop.in0, pop.in1, pop.outputs, empty, 4))
    assert ints.shape == (3, 0)
    fleet = D.fleet_eval_words(
        [(pop.op[0], pop.in0[0], pop.in1[0], pop.outputs[0], 4)],
        [empty], backend="pallas")
    assert fleet[0].shape == (0,)


@pytest.mark.parametrize("W", (8, 128, 129, 700))
def test_pallas_word_tiles_match_reference(monkeypatch, W):
    """The word tile is the whole word axis up to 128 words and 128 past
    it, the last tile zero-padded: a plane of one tile, exactly one, one
    word over (127 pad words) and six tiles stay bit-identical to the
    host reference through `program_eval_words` and
    `population_eval_uint`."""
    from repro.kernels import dispatch as D

    tiles = []
    real = PS._fused_padded

    def spy(plan, outputs, words32, **kw):
        tiles.append((kw["block_words"], words32.shape[-1]))
        return real(plan, outputs, words32, **kw)

    monkeypatch.setattr(PS, "_fused_padded", spy)
    rng = np.random.default_rng(11)
    pop = C.random_netlist_population(rng, 5, 12, 2, 4)
    S = W * 32
    bits = _rand_bits(rng, S, 5)
    ref = pop.eval_uint(C.pack_vectors(bits))[:, :S]

    words32 = np.asarray(CS.pack_bits32(bits))
    got = D.program_eval_words(pop.op[:1], pop.in0[:1], pop.in1[:1],
                               pop.outputs[:1], words32, 5, backend="pallas")
    np.testing.assert_array_equal(got[0, :S], ref[0])
    got = D.population_eval_uint(pop.op, pop.in0, pop.in1, pop.outputs,
                                 C.pack_vectors(bits), 5, backend="pallas")
    np.testing.assert_array_equal(got[:, :S], ref)

    bw = min(W, 128)
    assert [t[0] for t in tiles] == [bw, bw]
    assert all(wp % bw == 0 and wp >= W for _, wp in tiles)


def test_np_backend_odd_width_repack_matches_swar():
    """Regression (PR 9): the np backend's uint32->uint64 lane repack for
    odd-width word planes reinterpreted bytes (`.view(np.uint64)`), which
    is only the documented lane contract on little-endian hosts.  Pin
    np/swar/pallas bit-identity through `program_eval_words` on odd
    widths, and the repack itself against an arithmetic lane combine."""
    from repro.kernels import dispatch as D

    rng = np.random.default_rng(21)
    pop = C.random_netlist_population(rng, 6, 18, 3, 1)
    for W32 in (1, 3, 5):
        words32 = rng.integers(0, 2**32, size=(6, W32), dtype=np.uint32)
        outs = {b: D.program_eval_words(pop.op, pop.in0, pop.in1,
                                        pop.outputs, words32, 6, backend=b)
                for b in ("np", "swar", "pallas")}
        np.testing.assert_array_equal(
            outs["np"], outs["swar"],
            err_msg=f"np != swar on odd width W32={W32}")
        np.testing.assert_array_equal(
            outs["swar"], outs["pallas"],
            err_msg=f"swar != pallas on odd width W32={W32}")


@pytest.mark.slow
def test_fuzz_sweep_large():
    """Bigger populations / word planes; nightly raises the budget."""
    for seed in range(max(N_EXAMPLES, 30)):
        _fuzz_case(10_000 + seed, max_inputs=10, max_gates=96, max_pop=24,
                   max_vectors=2100, check_programs=False)


# ---------------------------------------------------------------------------
# Fleet serving path: emitted artifact -> ClassifierFleet -> labels must
# match predict_with_circuits on every golden vector, on every backend
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def golden_fleet():
    """Emit all five golden classifiers into one fleet dir (+ references)."""
    import tempfile

    from test_golden import GOLDEN_DIR, golden_classifier
    from repro.compile.verilog import write_artifacts
    from repro.core.ternary import abc_binarize
    from repro.core.tnn import TrainedTNN, predict_with_circuits
    from repro.data.tabular import DATASETS

    tmp = tempfile.TemporaryDirectory(prefix="golden_fleet_")
    refs = {}
    for name in sorted(DATASETS):
        cc, _ = golden_classifier(name)
        write_artifacts(cc, tmp.name, base=f"tnn_{name}", dataset=name)
        x = np.load(GOLDEN_DIR / f"{name}.npz")["x"]
        # offline oracle: the pre-compile netlist evaluator over ABC bits
        tnn = TrainedTNN(w1t=cc.w1t, w2t=cc.w2t, thresholds=cc.thresholds,
                         train_acc=0.0, test_acc=0.0, name=name)
        xbin = np.asarray(abc_binarize(x, cc.thresholds)).astype(np.uint8)
        labels = predict_with_circuits(tnn, xbin, cc.hidden_nls, cc.out_nls)
        refs[f"tnn_{name}"] = (x, labels)
    yield tmp.name, refs
    tmp.cleanup()


@pytest.mark.parametrize("backend", ("np", "swar", "pallas"))
def test_fleet_serving_matches_predict_with_circuits(golden_fleet, backend):
    """The whole serving stack — manifest load, router, micro-batcher,
    backend dispatch through kernels.dispatch — must be label-transparent:
    every golden vector of every Table-2 dataset gets the exact
    `predict_with_circuits` label, per tenant, on np/swar/pallas."""
    from repro.serve import ClassifierFleet

    emit_dir, refs = golden_fleet
    fleet = ClassifierFleet.from_emit_dir(emit_dir, backends=backend,
                                          max_batch=64, deadline_ms=5_000.0)
    try:
        handles = {tenant: [fleet.submit(tenant, row) for row in x]
                   for tenant, (x, _) in sorted(refs.items())}
        fleet.flush(timeout=120)
        for tenant, (_, want) in refs.items():
            got = np.array([r.result(timeout=120) for r in handles[tenant]],
                           dtype=np.int32)
            np.testing.assert_array_equal(
                got, want, err_msg=f"fleet[{backend}] != "
                                   f"predict_with_circuits ({tenant})")
        assert fleet.errors == []
    finally:
        fleet.shutdown(drain=True)


@pytest.mark.parametrize("variant", ("fused", "fleet"))
def test_megakernel_matches_predict_with_circuits(golden_fleet, variant):
    """Megakernel matrix: the fused single-program kernel and the
    multi-tenant `fleet_eval_words` launch must both reproduce
    `predict_with_circuits` labels on all five golden datasets.

    `fused` routes each golden program through the single-`pallas_call`
    gate-walk+decode path one tenant at a time; `fleet` pools all five
    tenants' plan tables into ONE padded multi-program launch — 5 tenants
    with different gate/feature/class counts sharing a kernel, every
    label still bit-exact."""
    from repro.compile.artifact import load_program
    from repro.kernels import dispatch as D

    emit_dir, refs = golden_fleet
    progs, planes = {}, {}
    for tenant, (x, _) in sorted(refs.items()):
        prog = load_program(f"{emit_dir}/{tenant}_program.npz",
                            backend="pallas")
        progs[tenant] = prog
        planes[tenant] = prog.pack_input_bits(prog.binarize(x))
    if variant == "fused":
        for tenant, (x, want) in refs.items():
            got = progs[tenant].predict(x)
            np.testing.assert_array_equal(
                got, want,
                err_msg=f"fused megakernel != predict_with_circuits "
                        f"({tenant})")
    else:
        order = sorted(refs)
        outs = D.fleet_eval_words([progs[t].plan() for t in order],
                                  [planes[t] for t in order],
                                  backend="pallas")
        for tenant, out in zip(order, outs):
            x, want = refs[tenant]
            np.testing.assert_array_equal(
                out[: x.shape[0]].astype(np.int32), want,
                err_msg=f"fleet megakernel != predict_with_circuits "
                        f"({tenant})")


def test_megakernel_fleet_serving_matches(golden_fleet):
    """Serving-path megakernel: all five golden tenants on the pallas
    backend with `megakernel=True` — the scheduler must carry every due
    tenant in one fused launch and still hand back exact labels."""
    from repro.serve import ClassifierFleet

    emit_dir, refs = golden_fleet
    fleet = ClassifierFleet.from_emit_dir(
        emit_dir, backends="pallas", max_batch=64, deadline_ms=5_000.0,
        megakernel=True, autostart=False, warmup=False)
    try:
        handles = {tenant: [fleet.submit(tenant, row) for row in x]
                   for tenant, (x, _) in sorted(refs.items())}
        fleet.start()
        fleet.flush(timeout=120)
        for tenant, (_, want) in refs.items():
            got = np.array([r.result(timeout=120) for r in handles[tenant]],
                           dtype=np.int32)
            np.testing.assert_array_equal(
                got, want,
                err_msg=f"megakernel fleet != predict_with_circuits "
                        f"({tenant})")
        assert fleet.errors == []
        mk = fleet.stats_summary()["megakernel"]
        assert mk["launches"] >= 1
        # every tenant was due before start(): the first pass must have
        # fused at least 4 of the 5 into one launch
        assert mk["peak_tenants_per_launch"] >= 4, mk
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Hypothesis-driven variant (shrinks failures to minimal netlists)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:

    @settings(max_examples=N_EXAMPLES, deadline=None)
    @given(st.integers(1, 8), st.integers(0, 32), st.integers(1, 6),
           st.integers(1, 8), st.integers(1, 200), st.integers(0, 2**31 - 1))
    def test_hypothesis_netlists_all_backends_agree(n_in, n_gates, n_out,
                                                    P, S, seed):
        rng = np.random.default_rng(seed)
        n_out = min(n_out, n_in + n_gates)
        pop = C.random_netlist_population(rng, n_in, n_gates, n_out, P)
        assert_conformance(pop, _rand_bits(rng, S, n_in),
                           check_programs=False)

    @settings(max_examples=N_EXAMPLES, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 7),    # n_in per tenant
                              st.integers(0, 40),   # n_gates per tenant
                              st.integers(1, 5),    # n_out per tenant
                              st.integers(1, 100)),  # vectors per tenant
                    min_size=2, max_size=6),
           st.integers(0, 2**31 - 1))
    def test_hypothesis_fleet_megakernel_padding_never_leaks(shapes, seed):
        """Mixed per-tenant gate counts through the multi-program
        megakernel: every tenant's plan is padded to the common
        (G_max, n_in_max, W_max) tables, and NONE of that padding — pad
        gates, pad input rows, pad words, pad output taps — may change
        any tenant's decoded integers vs evaluating that tenant alone."""
        from repro.kernels import dispatch as D

        rng = np.random.default_rng(seed)
        plans, words_list, refs = [], [], []
        for (n_in, n_gates, n_out, S) in shapes:
            n_out = min(n_out, n_in + n_gates)
            pop = C.random_netlist_population(rng, n_in, n_gates, n_out, 1)
            bits = _rand_bits(rng, S, n_in)
            packed = C.pack_vectors(bits)
            refs.append((S, pop.eval_uint(packed)[0, :S]))
            plans.append((pop.op[0], pop.in0[0], pop.in1[0],
                          pop.outputs[0], n_in))
            words_list.append(np.asarray(CS.pack_bits32(bits)))
        outs = D.fleet_eval_words(plans, words_list, backend="pallas")
        for t, ((S, want), out) in enumerate(zip(refs, outs)):
            np.testing.assert_array_equal(
                out[:S], want,
                err_msg=f"fleet megakernel tenant {t} (shape "
                        f"{shapes[t]}) != Netlist reference")
