"""The batched TNN objective: one launch a call scores every output neuron
of every genome, and agrees with the serial `_eval_one` row by row."""
import numpy as np
import pytest

from repro import obs
from repro.core import circuits as C
from repro.core import tnn as T
from repro.core.pcc import PCCEntry, PCCLibrary
from repro.evolve.problems import CampaignProblem, attach_tnn_drift

# w2t (H=6, C=3): every column has two +1, two -1 and two zero weights,
# at different rows, so the order of an output neuron's inputs matters
W2T = np.array([[1, -1, 0],
                [-1, 0, 1],
                [0, 1, -1],
                [1, 0, -1],
                [0, -1, 1],
                [-1, 1, 0]], dtype=np.int8)
# w1t (F=8, H=6): four neurons with both signs (genes), one with no -1
# weight (constant 1) and one PCC size left out of the library (fixed)
W1T = np.array([[1, -1, 1, 0, 1, 1],
                [-1, 1, 0, 1, 1, 0],
                [1, 0, -1, -1, 0, 1],
                [0, 1, 1, -1, 1, -1],
                [1, -1, 0, 1, 0, 0],
                [-1, 0, -1, 0, 1, -1],
                [0, 1, 1, -1, 0, 1],
                [1, -1, 0, 1, 1, -1]], dtype=np.int8)
S = 150                     # readings: three words, the last one partial


def _rewired(nl: C.Netlist, src: int, dst: int) -> C.Netlist:
    """`nl` with input `src` read as input `dst`: an approximate popcount
    that is not symmetric in its inputs."""
    in0 = np.where(nl.in0 == src, dst, nl.in0).astype(np.int32)
    in1 = np.where(nl.in1 == src, dst, nl.in1).astype(np.int32)
    return C.Netlist(nl.n_inputs, nl.op.copy(), in0, in1, nl.outputs.copy(),
                     name=f"{nl.name}_{src}as{dst}")


def _pcc_entries(p: int, n: int) -> list[PCCEntry]:
    exact_p, exact_n = C.popcount_netlist(p), C.popcount_netlist(n)
    pairs = [(exact_p, exact_n), (_rewired(exact_p, 0, p - 1), exact_n),
             (exact_p, _rewired(exact_n, n - 1, 0))]
    return [PCCEntry(p, n, a, b, a.cost().area_mm2 + b.cost().area_mm2,
                     0.0, 0.0, 1.0) for a, b in pairs]


def _problem(backend: str, w2t: np.ndarray = W2T,
             n_rows: int = S) -> T.TNNApproxProblem:
    tnn = T.TrainedTNN(w1t=W1T, w2t=w2t, thresholds=np.zeros(8),
                       train_acc=0.0, test_acc=0.0, name="toy")
    sizes = sorted({s for s in tnn.hidden_sizes() if s[0] >= 1 and s[1] >= 1})
    lib = PCCLibrary({s: _pcc_entries(*s) for s in sizes[:-1]})
    nnz = max(tnn.out_nnz, 1)
    exact = C.popcount_netlist(nnz)
    outs = [exact]
    if nnz > 1:
        outs += [_rewired(exact, 0, 1), _rewired(exact, nnz - 1, 0)]
    rng = np.random.default_rng(7)
    return T.TNNApproxProblem(
        tnn=tnn, pcc_lib=lib, pc_out_lib=outs,
        xbin=(rng.random((n_rows, 8)) < 0.5).astype(np.uint8),
        y=rng.integers(0, w2t.shape[1], n_rows), eval_backend=backend)


def _population(prob: T.TNNApproxProblem, P: int) -> np.ndarray:
    dom = prob.domains()
    rng = np.random.default_rng(P)
    pop = rng.integers(0, dom[None, :], size=(P, len(dom)))
    pop[0] = 0                                      # the all-exact genome
    return pop


def _eval_n() -> int:
    return obs.snapshot().get("tnn.objective.eval", {"n": 0})["n"]


def test_problem_has_signed_outputs_and_genes():
    prob = _problem("np")
    assert ((W2T == 1).any(axis=0) & (W2T == -1).any(axis=0)).all()
    assert prob.tnn.out_nnz == 4
    assert len(prob.hidden_idx) >= 2
    assert len(prob.hidden_idx) < sum(p >= 1 and n >= 1
                                      for p, n in prob.tnn.hidden_sizes())


@pytest.mark.parametrize("P", [1, 3, 8])
@pytest.mark.parametrize("backend", ["np", "swar", "pallas"])
def test_objective_matches_eval_one(backend, P):
    prob = _problem(backend)
    pop = _population(prob, P)
    before = _eval_n()
    got = prob.objective(pop)
    assert _eval_n() - before == 1          # one launch round trip a call
    want = np.array([prob._eval_one(x) for x in pop])
    np.testing.assert_array_equal(got, want)


def test_objective_without_output_inputs_launches_nothing():
    prob = _problem("np", w2t=np.zeros_like(W2T))
    assert prob.tnn.out_nnz == 0
    pop = _population(prob, 3)
    before = _eval_n()
    got = prob.objective(pop)
    assert _eval_n() == before
    want = np.array([prob._eval_one(x) for x in pop])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[:, 0], 1.0 - (prob.y == 0).mean())


def _packed_by_bits(prob: T.TNNApproxProblem, pop: np.ndarray) -> np.ndarray:
    """The launch's input planes packed from gathered uint8 bits: every
    genome's (S, H) hidden bits, each output neuron's inputs through one
    (C, nnz) gather, NOTs applied, then one `pack_vectors`."""
    P = pop.shape[0]
    hbits = np.repeat(prob.fixed_hbits[None], P, axis=0)
    for g, cache in enumerate(prob.hidden_bit_cache):
        hbits[:, :, prob.hidden_idx[g]] = cache[pop[:, g]]
    bits = hbits[:, :, prob._out_idx] ^ prob._out_neg        # (P, S, C, nnz)
    packed = C.pack_vectors(bits.transpose(0, 2, 1, 3))
    return packed.reshape(P * prob._out_idx.shape[0], prob.tnn.out_nnz, -1)


@pytest.mark.parametrize("signed", [True, False], ids=["not", "plain"])
@pytest.mark.parametrize("P", [1, 5])
@pytest.mark.parametrize("n_rows", [128, 100])
def test_packed_planes_match_pack_vectors(monkeypatch, n_rows, P, signed):
    w2t = W2T if signed else np.abs(W2T)
    prob = _problem("np", w2t=w2t, n_rows=n_rows)
    assert (prob._out_neg == 1).any() == signed
    pop = _population(prob, P)
    seen = []
    eval_uint = C.NetlistPopulation.eval_uint

    def spy(self, inputs):
        seen.append(inputs.copy())
        return eval_uint(self, inputs)

    monkeypatch.setattr(C.NetlistPopulation, "eval_uint", spy)
    prob.objective(pop)
    assert len(seen) == 1
    want = _packed_by_bits(prob, pop)
    assert seen[0].dtype == want.dtype == np.uint64
    assert seen[0].shape == want.shape == (P * 3, 4, -(-n_rows // 64))
    np.testing.assert_array_equal(seen[0], want)     # tail bits included


def test_objective_matches_eval_one_after_drift():
    prob = _problem("np")
    pop = _population(prob, 6)
    cp = CampaignProblem(name="toy", domains=prob.domains(),
                         objective=prob.objective, approx=prob)
    attach_tnn_drift(cp, rate=0.5, seed=3)
    y0 = prob.y.copy()
    for r in (1, 2):
        cp.drift(r)
        got = prob.objective(pop)
        want = np.array([prob._eval_one(x) for x in pop])
        np.testing.assert_array_equal(got, want)
    assert not np.array_equal(prob.y, y0)             # the rows did move
