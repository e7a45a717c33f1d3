"""Bespoke ternary neural networks (Sec. 3.2) — QAT + circuit-accurate path.

Semantics (and the invariant the tests pin down):

  hidden neuron i :  h'_i = +1  iff  sum_{w=+1} x - sum_{w=-1} x >= 0
                     == PCC( x[w=+1], x[w=-1] )            (Eq. 2)
  output neuron o :  score_o = #XNOR matches = (logits_o + nnz_o) / 2
                     where logits_o = sum_i w_io h'_i
  With zero counts balanced across output neurons (same N), nnz_o is the
  same constant, so  argmax(score) == argmax(logits)  — exactly the paper's
  +N/2 correction-term argument.  Hence the JAX training forward and the
  integer circuit path must produce identical predictions (tested).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import circuits as C
from repro.core.nsga2 import NSGA2Config, NSGA2Result, nsga2
from repro.core.pcc import PCCLibrary, PCCEntry
from repro.core.ternary import (
    TERNARY_THRESHOLD,
    abc_binarize,
    abc_fit_thresholds,
    binary_step_ste,
    ternarize,
    ternary_ste,
)
from repro.data.tabular import TabularDataset
from repro.hw.egfet import Gate, HwCost, gate_cost, interface_cost
from repro.optim import adamw


# ---------------------------------------------------------------------------
# Training (QAT)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TNNTrainConfig:
    n_hidden: int
    epochs: int = 15            # paper: 10-20
    lr: float = 5e-3            # paper: 1e-3..1e-2 (Bayesian-opt'd)
    batch_size: int = 64
    seed: int = 0
    threshold: float = TERNARY_THRESHOLD
    weight_decay: float = 0.0


@dataclass
class TrainedTNN:
    w1t: np.ndarray             # (F, H) int8 ternary codes
    w2t: np.ndarray             # (H, C) int8, zero-balanced columns
    thresholds: np.ndarray      # (F,) ABC V_q per feature
    train_acc: float
    test_acc: float
    name: str = ""

    @property
    def topology(self) -> tuple[int, int, int]:
        return (self.w1t.shape[0], self.w1t.shape[1], self.w2t.shape[1])

    def hidden_sizes(self) -> list[tuple[int, int]]:
        return [(int((self.w1t[:, i] == 1).sum()), int((self.w1t[:, i] == -1).sum()))
                for i in range(self.w1t.shape[1])]

    @property
    def out_nnz(self) -> int:
        """Non-zero inputs per output neuron (equal across neurons)."""
        nnz = (self.w2t != 0).sum(axis=0)
        assert (nnz == nnz[0]).all(), "output zero counts not balanced"
        return int(nnz[0])


def _forward_logits(params, xbin, threshold):
    w1q = ternary_ste(params["w1"], threshold)
    a = xbin @ w1q
    # surrogate-gradient window scaled to the integer popcount-sum magnitude,
    # otherwise hidden units saturate and w1 receives no learning signal
    h = binary_step_ste(a, grad_width=jnp.sqrt(float(xbin.shape[-1])))
    w2q = ternary_ste(params["w2"], threshold)
    return h @ w2q, h


def _loss_fn(params, xbin, y, threshold, n_hidden):
    logits, _ = _forward_logits(params, xbin, threshold)
    logits = logits / jnp.sqrt(float(n_hidden))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def balance_zero_counts(w2_latent: np.ndarray, threshold: float) -> np.ndarray:
    """Ternarize output weights and equalize per-column zero counts.

    The paper requires the same number N of zero-valued connections in every
    output neuron so the +N/2 correction term cancels in the argmax.  We
    project to N* = median zero count, moving the least-important weights:
      * columns with too few zeros: demote smallest-|latent| nonzeros to 0,
      * columns with too many zeros: promote largest-|latent| zeros to +-1.
    (Projecting to max() instead can zero out entire columns — catastrophic
    for narrow TNNs; see tests/test_tnn.py::test_balance_preserves_accuracy.)
    """
    codes = np.asarray(ternarize(jnp.asarray(w2_latent), threshold)).astype(np.int8)
    zeros = (codes == 0).sum(axis=0)
    N = int(np.median(zeros))
    for o in range(codes.shape[1]):
        delta = N - int(zeros[o])
        if delta > 0:        # need more zeros: demote weakest nonzeros
            nz = np.where(codes[:, o] != 0)[0]
            order = nz[np.argsort(np.abs(w2_latent[nz, o]), kind="stable")]
            codes[order[:delta], o] = 0
        elif delta < 0:      # need fewer zeros: promote strongest zeros
            z = np.where(codes[:, o] == 0)[0]
            order = z[np.argsort(-np.abs(w2_latent[z, o]), kind="stable")]
            for r in order[: -delta]:
                s = np.sign(w2_latent[r, o])
                codes[r, o] = np.int8(s if s != 0 else 1)
    return codes


def train_tnn(ds: TabularDataset, cfg: TNNTrainConfig) -> TrainedTNN:
    """Quantization-aware training of a (F, H, C) bespoke TNN."""
    thresholds = abc_fit_thresholds(ds.x_train)
    xb_tr = np.asarray(abc_binarize(ds.x_train, thresholds))
    xb_te = np.asarray(abc_binarize(ds.x_test, thresholds))
    F, H, Cc = ds.spec.n_features, cfg.n_hidden, ds.spec.n_classes

    rng = np.random.default_rng(cfg.seed)
    params = {
        "w1": jnp.asarray(rng.normal(0, 0.7, size=(F, H)), jnp.float32),
        "w2": jnp.asarray(rng.normal(0, 0.7, size=(H, Cc)), jnp.float32),
    }
    ocfg = adamw.AdamWConfig(lr=cfg.lr, weight_decay=cfg.weight_decay, grad_clip=1.0)
    ostate = adamw.init(params)

    @jax.jit
    def step(params, ostate, xb, y):
        loss, grads = jax.value_and_grad(_loss_fn)(params, xb, y, cfg.threshold, H)
        params, ostate = adamw.apply_updates(params, grads, ostate, ocfg)
        return params, ostate, loss

    n = xb_tr.shape[0]
    xb_j, y_j = jnp.asarray(xb_tr), jnp.asarray(ds.y_train.astype(np.int32))
    for epoch in range(cfg.epochs):
        perm = rng.permutation(n)
        for s in range(0, n, cfg.batch_size):
            idx = perm[s:s + cfg.batch_size]
            params, ostate, _ = step(params, ostate, xb_j[idx], y_j[idx])

    w1t = np.asarray(ternarize(params["w1"], cfg.threshold)).astype(np.int8)
    w2t = balance_zero_counts(np.asarray(params["w2"]), cfg.threshold)
    tnn = TrainedTNN(w1t=w1t, w2t=w2t, thresholds=thresholds,
                     train_acc=0.0, test_acc=0.0, name=ds.name)
    tnn.train_acc = float((predict_exact(tnn, xb_tr) == ds.y_train).mean())
    tnn.test_acc = float((predict_exact(tnn, xb_te) == ds.y_test).mean())
    return tnn


def search_tnn(ds: TabularDataset, hidden_options: list[int],
               lr_options: list[float] | None = None, seeds: tuple[int, ...] = (0, 1),
               epochs: int = 15) -> TrainedTNN:
    """Scaled-down version of the paper's exhaustive/Bayesian hyperparameter
    search (Sec. 5): best test accuracy, ties broken by fewer neurons."""
    lrs = lr_options or [2e-3, 5e-3, 1e-2]
    best: TrainedTNN | None = None
    for h in hidden_options:
        for lr in lrs:
            for seed in seeds:
                t = train_tnn(ds, TNNTrainConfig(n_hidden=h, lr=lr, seed=seed,
                                                 epochs=epochs))
                if (best is None or t.test_acc > best.test_acc + 1e-9
                        or (abs(t.test_acc - best.test_acc) <= 1e-9
                            and t.w1t.shape[1] < best.w1t.shape[1])):
                    best = t
    assert best is not None
    return best


# ---------------------------------------------------------------------------
# Circuit-accurate integer inference
# ---------------------------------------------------------------------------
def predict_exact(tnn: TrainedTNN, xbin: np.ndarray) -> np.ndarray:
    """Exact integer path (popcounts + comparators), vectorized in numpy."""
    x = xbin.astype(np.int64)
    w1 = tnn.w1t.astype(np.int64)
    a = x @ w1
    hbit = (a >= 0).astype(np.int64)                      # {0,1}
    w2 = tnn.w2t.astype(np.int64)
    # score_o = sum_{w=+1} h + sum_{w=-1} (1-h)
    score = hbit @ (w2 == 1) + (1 - hbit) @ (w2 == -1)
    return np.argmax(score, axis=1).astype(np.int32)


def hidden_exact_netlist(n_pos: int, n_neg: int) -> C.Netlist:
    """Exact PCC for one hidden neuron, incl. degenerate shapes."""
    if n_neg == 0:
        # sum_pos >= 0 is always true -> constant 1 (zero hardware)
        b = C._Builder(max(n_pos, 1))
        one = b.const(1)
        return b.finish([one], name=f"pcc_{n_pos}x0_const1")
    if n_pos == 0:
        # 0 >= sum_neg  iff  all neg inputs are 0  ->  NOR tree
        b = C._Builder(n_neg)
        acc = 0
        for i in range(1, n_neg):
            acc = b.gate(Gate.OR, acc, i)
        out = b.gate(Gate.NOT, acc) if n_neg > 1 else b.gate(Gate.NOT, 0)
        return b.finish([out], name=f"pcc_0x{n_neg}_nor")
    return C.compose_pcc(C.popcount_netlist(n_pos), C.popcount_netlist(n_neg),
                         n_pos, n_neg)


def _hidden_inputs(tnn: TrainedTNN, xbin: np.ndarray, i: int) -> np.ndarray:
    """Concatenated [pos..., neg...] input matrix (S, n_pos+n_neg) for neuron i."""
    col = tnn.w1t[:, i]
    pos = xbin[:, col == 1]
    neg = xbin[:, col == -1]
    return np.concatenate([pos, neg], axis=1)


def _output_bits(tnn: TrainedTNN, hbits: np.ndarray, o: int) -> np.ndarray:
    """XNOR-simplified input bits (S, nnz) for output neuron o."""
    col = tnn.w2t[:, o]
    plus = hbits[:, col == 1]              # wire
    minus = 1 - hbits[:, col == -1]        # NOT gate
    return np.concatenate([plus, minus], axis=1)


def predict_with_circuits(tnn: TrainedTNN, xbin: np.ndarray,
                          hidden_nls: list[C.Netlist],
                          out_nls: list[C.Netlist]) -> np.ndarray:
    """Inference through explicit (possibly approximate) netlists."""
    S = xbin.shape[0]
    H = tnn.w1t.shape[1]
    hbits = np.empty((S, H), dtype=np.uint8)
    for i in range(H):
        sizes = tnn.hidden_sizes()[i]
        if sizes == (0, 0):
            hbits[:, i] = 1
            continue
        inp = _hidden_inputs(tnn, xbin, i)
        packed = C.pack_vectors(inp)
        hbits[:, i] = hidden_nls[i].eval_uint(packed)[:S].astype(np.uint8)
    Cc = tnn.w2t.shape[1]
    scores = np.empty((S, Cc), dtype=np.int64)
    for o in range(Cc):
        bits = _output_bits(tnn, hbits, o)
        if bits.shape[1] == 0:
            scores[:, o] = 0
            continue
        packed = C.pack_vectors(bits)
        scores[:, o] = out_nls[o].eval_uint(packed)[:S]
    return np.argmax(scores, axis=1).astype(np.int32)


def exact_netlists(tnn: TrainedTNN) -> tuple[list[C.Netlist], list[C.Netlist]]:
    hidden = [hidden_exact_netlist(p, n) for (p, n) in tnn.hidden_sizes()]
    out = [C.popcount_netlist(max(tnn.out_nnz, 1))] * tnn.w2t.shape[1]
    return hidden, out


# ---------------------------------------------------------------------------
# Hardware cost accounting (EGFET)
# ---------------------------------------------------------------------------
def argmax_cost(n_classes: int, score_bits: int) -> HwCost:
    """(C-1) comparators + (C-1) score-wide 2:1 muxes (value propagation)."""
    cmp_cost = C.comparator_geq_netlist(score_bits).cost()
    mux_bit = gate_cost(Gate.AND) + gate_cost(Gate.ANDN) + gate_cost(Gate.OR)
    total = HwCost(0.0, 0.0)
    for _ in range(n_classes - 1):
        total = total + cmp_cost + mux_bit.scale(score_bits)
    return total


def tnn_hw_cost(tnn: TrainedTNN,
                hidden_nls: list[C.Netlist],
                out_nls: list[C.Netlist],
                interface: str | None = "abc") -> HwCost:
    """Full-system cost: neurons + output NOT gates + argmax + interface."""
    total = HwCost(0.0, 0.0)
    for nl in hidden_nls:
        total = total + nl.cost()
    for nl in out_nls:
        total = total + nl.cost()
    n_not = int((tnn.w2t == -1).sum())          # XNOR -> NOT for w = -1
    total = total + gate_cost(Gate.NOT).scale(n_not)
    total = total + argmax_cost(tnn.w2t.shape[1],
                                C.popcount_width(max(tnn.out_nnz, 1)))
    if interface:
        total = total + interface_cost(tnn.w1t.shape[0], interface)
    return total


# ---------------------------------------------------------------------------
# Phase 3 — NSGA-II integration problem
# ---------------------------------------------------------------------------
@dataclass
class TNNApproxProblem:
    """Integer-chromosome encoding: one gene per non-degenerate hidden neuron
    (PCC library index) + one gene per output neuron (PC library index)."""

    tnn: TrainedTNN
    pcc_lib: PCCLibrary
    pc_out_lib: list[C.Netlist]
    xbin: np.ndarray
    y: np.ndarray
    # gate-simulation executor for the population-batched output plane:
    # "np" (NetlistPopulation reference), "swar" (lax.scan uint32 twin) or
    # "pallas" (kernels.pallas_circuit_sim) — all bit-identical, see
    # kernels.dispatch / tests/test_conformance.py
    eval_backend: str = "np"
    # derived
    hidden_idx: list[int] = field(default_factory=list)     # non-degenerate neurons
    hidden_cands: list[list[PCCEntry]] = field(default_factory=list)
    hidden_bit_cache: list[np.ndarray] = field(default_factory=list)  # (n_cand, S) u8
    fixed_hbits: np.ndarray | None = None                    # (S, H) exact base
    fixed_cost: HwCost = field(default_factory=lambda: HwCost(0, 0))
    _n_calls: int = field(default=0, init=False, repr=False,
                          compare=False)                    # span ids

    def __post_init__(self):
        S = self.xbin.shape[0]
        H = self.tnn.w1t.shape[1]
        sizes = self.tnn.hidden_sizes()
        self.fixed_hbits = np.empty((S, H), dtype=np.uint8)
        for i, (p, n) in enumerate(sizes):
            if p >= 1 and n >= 1 and (p, n) in self.pcc_lib.entries:
                cands = self.pcc_lib.get(p, n)
                self.hidden_idx.append(i)
                self.hidden_cands.append(cands)
                inp = C.pack_vectors(_hidden_inputs(self.tnn, self.xbin, i))
                cache = np.empty((len(cands), S), dtype=np.uint8)
                for k, e in enumerate(cands):
                    cache[k] = e.compose().eval_uint(inp)[:S].astype(np.uint8)
                self.hidden_bit_cache.append(cache)
                self.fixed_hbits[:, i] = cache[0]            # exact = index 0
            else:
                nl = hidden_exact_netlist(p, n)
                self.fixed_cost = self.fixed_cost + nl.cost()
                if (p, n) == (0, 0) or n == 0:
                    self.fixed_hbits[:, i] = 1
                else:
                    inp = C.pack_vectors(_hidden_inputs(self.tnn, self.xbin, i))
                    self.fixed_hbits[:, i] = nl.eval_uint(inp)[:S].astype(np.uint8)
        # output candidates: Pareto PC library for size out_nnz
        self.out_cands = self.pc_out_lib
        # fixed costs independent of gene choices
        self.fixed_cost = (self.fixed_cost
                           + gate_cost(Gate.NOT).scale(int((self.tnn.w2t == -1).sum()))
                           + argmax_cost(self.tnn.w2t.shape[1],
                                         C.popcount_width(max(self.tnn.out_nnz, 1))))
        # batched-objective caches: per-gene candidate areas + one padded
        # population over the output PC candidates (row-selected per genome)
        self._hidden_gene_areas = [np.array([e.est_area for e in cands])
                                   for cands in self.hidden_cands]
        self._out_areas = np.array([nl.cost().area_mm2 for nl in self.out_cands])
        self._out_pop = C.NetlistPopulation.from_netlists(self.out_cands)
        # every output neuron's inputs as one (C, nnz) gather of the hidden
        # bits, in `_output_bits` order (w = +1 wires, then w = -1 through a
        # NOT, the mask): approximate popcounts are not symmetric
        w2 = self.tnn.w2t
        cols = np.arange(w2.shape[1])
        self._out_idx = np.array(
            [np.concatenate([np.where(w2[:, o] == 1)[0],
                             np.where(w2[:, o] == -1)[0]]) for o in cols],
            dtype=np.int64).reshape(len(cols), self.tnn.out_nnz)
        self._out_neg = (w2[self._out_idx, cols[:, None]] == -1).astype(np.uint8)
        self.pack_rows()

    def pack_rows(self) -> None:
        """Pack the hidden-bit caches into the objective's uint64 words.

        Packing a bit column commutes with the per-genome gather, so each
        candidate's column is packed once here and `_objective` only
        gathers words.  A NOT (w = -1) is an XOR with all-ones words
        masked to the S real bits, so the tail bits stay 0 as
        `pack_vectors` leaves them.  Call again after replacing the rows
        (`fixed_hbits`, `hidden_bit_cache`).
        """
        S = self.fixed_hbits.shape[0]
        self._hidden_words = [C.pack_vectors(cache.T)      # (n_cand, W)
                              for cache in self.hidden_bit_cache]
        self._fixed_words = C.pack_vectors(self.fixed_hbits)  # (H, W)
        ones = C.pack_vectors(np.ones((S, 1), dtype=np.uint8))[0]
        self._neg_words = np.where(self._out_neg[..., None] == 1, ones,
                                   np.uint64(0))           # (C, nnz, W)

    # -- chromosome layout ---------------------------------------------------
    @property
    def n_genes(self) -> int:
        return len(self.hidden_idx) + self.tnn.w2t.shape[1]

    def domains(self) -> np.ndarray:
        d = [len(c) for c in self.hidden_cands]
        d += [len(self.out_cands)] * self.tnn.w2t.shape[1]
        return np.array(d, dtype=np.int64)

    def decode(self, x: np.ndarray) -> tuple[list[C.Netlist], list[C.Netlist]]:
        """Chromosome -> full netlist selection (for reporting/synthesis)."""
        sizes = self.tnn.hidden_sizes()
        hidden_nls: list[C.Netlist] = []
        gi = 0
        for i, (p, n) in enumerate(sizes):
            if i in self.hidden_idx:
                e = self.hidden_cands[self.hidden_idx.index(i)][int(x[gi])]
                hidden_nls.append(e.compose())
                gi += 1
            else:
                hidden_nls.append(hidden_exact_netlist(p, n))
        out_nls = [self.out_cands[int(g)] for g in x[len(self.hidden_idx):]]
        return hidden_nls, out_nls

    # -- objectives ------------------------------------------------------------
    def _eval_one(self, x: np.ndarray) -> tuple[float, float]:
        S = self.xbin.shape[0]
        hbits = self.fixed_hbits.copy()
        est_area = self.fixed_cost.area_mm2
        for g, (i, cands, cache) in enumerate(zip(self.hidden_idx,
                                                  self.hidden_cands,
                                                  self.hidden_bit_cache)):
            k = int(x[g])
            hbits[:, i] = cache[k]
            est_area += cands[k].est_area
        Cc = self.tnn.w2t.shape[1]
        scores = np.empty((S, Cc), dtype=np.int64)
        for o in range(Cc):
            nl = self.out_cands[int(x[len(self.hidden_idx) + o])]
            est_area += nl.cost().area_mm2
            bits = _output_bits(self.tnn, hbits, o)
            if bits.shape[1] == 0:
                scores[:, o] = 0
            else:
                scores[:, o] = nl.eval_uint(C.pack_vectors(bits))[:S]
        acc = float((np.argmax(scores, axis=1) == self.y).mean())
        return 1.0 - acc, est_area

    def objective(self, pop: np.ndarray) -> np.ndarray:
        """Population-parallel objectives: (N, n_genes) int -> (N, 2).

        Hidden-gene words come from the per-candidate words packed once
        (`pack_rows`) via one gather; every output neuron of every genome
        is scored in one launch a call: the N x C candidate popcounts
        stack into one `NetlistPopulation` of N·C rows (row n·C + o is
        genome n's neuron o) over per-row packed inputs.  Matches
        `_eval_one` (the serial reference) bit-for-bit.
        """
        pop = np.asarray(pop, dtype=np.int64)
        P = pop.shape[0]
        self._n_calls += 1
        with obs.span("tnn.objective", call=self._n_calls, P=P):
            return self._objective(pop)

    def _objective(self, pop: np.ndarray) -> np.ndarray:
        P = pop.shape[0]
        S = self.xbin.shape[0]
        nh = len(self.hidden_idx)
        Cc, nnz = self._out_idx.shape
        with obs.span("tnn.objective.gather"):
            est = np.full(P, self.fixed_cost.area_mm2)
            words = np.repeat(self._fixed_words[None], P, axis=0)  # (P, H, W)
            for g, cand_words in enumerate(self._hidden_words):
                words[:, self.hidden_idx[g]] = cand_words[pop[:, g]]
                est = est + self._hidden_gene_areas[g][pop[:, g]]
            for o in range(Cc):
                est = est + self._out_areas[pop[:, nh + o]]
            sub = self._out_pop.take(pop[:, nh:].reshape(-1))
        if nnz == 0:                     # no inputs: every score is 0
            scores = np.zeros((P, Cc, S), dtype=np.int64)
        else:
            with obs.span("tnn.objective.pack"):
                packed = words[:, self._out_idx] ^ self._neg_words  # (P,C,nnz,W)
                packed = packed.reshape(P * Cc, nnz, -1)
            with obs.span("tnn.objective.eval"):
                if self.eval_backend == "np":
                    out = sub.eval_uint(packed)
                else:
                    from repro.kernels.dispatch import population_eval_pop
                    out = population_eval_pop(sub, packed,
                                              backend=self.eval_backend)
                scores = out[:, :S].reshape(P, Cc, S)
        with obs.span("tnn.objective.score"):
            # argmax takes the first maximum: ties go to the lowest output
            acc = (np.argmax(scores, axis=1) == self.y[None, :]).mean(axis=1)
            return np.stack([1.0 - acc, est], axis=1)

    def optimize(self, cfg: NSGA2Config) -> NSGA2Result:
        seed = np.zeros((1, self.n_genes), dtype=np.int64)   # all-exact individual
        return nsga2(self.domains(), self.objective, cfg, seed_population=seed)
