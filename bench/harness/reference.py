"""Plain references the benchmark judges the program against.

Nothing here imports the program (`repro`): the readings, the seeded
ternary weights, the ternary network's forward pass and the gate-by-gate
netlist walk are written out again in plain numpy, so a fault in the
program cannot hide in its own reference.

* `make_dataset` is a copy of the synthetic stand-ins for the paper's five
  UCI sets (`repro.data.tabular`), so the benchmark draws its readings
  itself from `--seed`.
* `seeded_weights` builds an untrained Table-2 classifier from a fixed
  per-dataset seed, the construction of `tests/test_golden.py`.
* `tnn_labels` is the classifier's decision function: strict `>` ABC
  comparators, `sum(+1 inputs) - sum(-1 inputs) >= 0` hidden neurons,
  XNOR-popcount scores and a first-max argmax.
* `walk_netlist` evaluates one gate list on a bit matrix, one gate at a
  time, with each opcode's truth function written out.

* `approx_objectives` scores a campaign genome (error rate and area)
  from the seeded wiring and the candidates' raw gate lists: popcounts
  walked gate by gate and compared as integers, areas summed from a
  gate-area table over each candidate's live gates.

Each reference has a lower-precision or broken twin, the control that the
comparison must reject (`tnn_labels(..., dtype="bfloat16")`,
`approx_objectives(..., control=True)`).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np

TERNARY_THRESHOLD = 1.0 / 3.0

# Table 2 of arXiv:2407.20589 with the UCI shapes: (features, classes,
# samples, separation, informative fraction, majority prior, topology)
SPECS = {
    "arrhythmia": (274, 16, 452 * 4, 0.55, 0.25, 0.54, (274, 3, 16)),
    "breast_cancer": (10, 2, 699 * 2, 15.0, 0.9, 0.65, (10, 10, 2)),
    "cardio": (21, 3, 2126, 2.1, 0.7, 0.58, (21, 3, 3)),
    "redwine": (11, 6, 1599, 1.7, 0.7, 0.43, (11, 3, 6)),
    "whitewine": (11, 7, 2449, 0.9, 0.7, 0.45, (11, 11, 7)),
}


@dataclass
class Dataset:
    x_train: np.ndarray     # (N, F) float32 in [0, 1]
    y_train: np.ndarray     # (N,) int32
    x_test: np.ndarray
    y_test: np.ndarray


def _rng(tag: str) -> np.random.Generator:
    digest = hashlib.sha256(tag.encode()).digest()
    return np.random.default_rng(int.from_bytes(digest[:8], "little"))


def make_dataset(name: str, seed: int = 0) -> Dataset:
    """Seeded readings with the UCI dimensions; 70/30 split."""
    F, C, N, sep, inf_frac, major, _ = SPECS[name]
    rng = _rng(f"{name}:{seed}")
    n_inf = max(1, int(round(inf_frac * F)))
    if C > 8:       # many classes: XOR mixes of a few base patterns
        k = 4
        basis = rng.random((k, n_inf)) < 0.5
        codes = (np.arange(C)[:, None] >> np.arange(k)[None, :]) & 1
        protos = (codes @ basis.astype(np.int64)) % 2 == 1
    else:
        protos = rng.random((C, n_inf)) < 0.5
    flip_p = 0.5 / (1.0 + sep)
    lo_r, hi_r = 1e-6, 1.0 - 1e-6
    for _ in range(60):     # geometric class priors hitting the majority
        mid = 0.5 * (lo_r + hi_r)
        w = mid ** np.arange(C)
        if w[0] / w.sum() > major:
            lo_r = mid
        else:
            hi_r = mid
    w = (0.5 * (lo_r + hi_r)) ** np.arange(C)
    y = rng.choice(C, size=N, p=w / w.sum()).astype(np.int32)
    x = rng.normal(0.0, 1.0, size=(N, F))
    flips = rng.random((N, n_inf)) < flip_p
    bits = protos[y] ^ flips
    x[:, :n_inf] = (0.3 + 0.4 * bits
                    + rng.normal(0.0, 0.10, size=(N, n_inf))) * 2.5 - 1.25
    if n_inf >= 2:
        x[:, 0] += 0.4 * np.where(bits[:, 1], 1.0, -1.0) * (y % 2 * 2 - 1)
    lo, hi = x.min(axis=0, keepdims=True), x.max(axis=0, keepdims=True)
    x = (x - lo) / np.maximum(hi - lo, 1e-9)
    n_train = int(0.7 * N)
    perm = rng.permutation(N)
    tr, te = perm[:n_train], perm[n_train:]
    return Dataset(x[tr].astype(np.float32), y[tr], x[te].astype(np.float32),
                   y[te])


# -- seeded classifier ------------------------------------------------------
@dataclass
class Ternary:
    """An untrained ternary classifier: wiring and ABC thresholds."""

    w1: np.ndarray          # (F, H) int8 in {-1, 0, 1}
    w2: np.ndarray          # (H, C) int8, equal zero count per column
    thresholds: np.ndarray  # (F,) float32 medians of the training readings


def _ternarize(w: np.ndarray) -> np.ndarray:
    return (np.sign(w) * (np.abs(w) > TERNARY_THRESHOLD)).astype(np.int8)


def _balance_zero_counts(latent: np.ndarray) -> np.ndarray:
    """Ternarize, then give every output column the median zero count by
    zeroing its weakest weights or reviving its strongest zeros."""
    codes = _ternarize(latent)
    zeros = (codes == 0).sum(axis=0)
    target = int(np.median(zeros))
    for o in range(codes.shape[1]):
        delta = target - int(zeros[o])
        if delta > 0:
            nz = np.where(codes[:, o] != 0)[0]
            order = nz[np.argsort(np.abs(latent[nz, o]), kind="stable")]
            codes[order[:delta], o] = 0
        elif delta < 0:
            z = np.where(codes[:, o] == 0)[0]
            order = z[np.argsort(-np.abs(latent[z, o]), kind="stable")]
            for r in order[:-delta]:
                s = np.sign(latent[r, o])
                codes[r, o] = np.int8(s if s != 0 else 1)
    return codes


def seeded_weights(name: str) -> Ternary:
    """The fixed untrained classifier of dataset `name` (seed 'golden:<name>')."""
    F, H, C = SPECS[name][6]
    rng = _rng(f"golden:{name}")
    w1 = _ternarize(rng.normal(0.0, 0.7, size=(F, H)))
    w2 = _balance_zero_counts(rng.normal(0.0, 0.7, size=(H, C)))
    thresholds = np.median(make_dataset(name).x_train, axis=0)
    return Ternary(w1=w1, w2=w2, thresholds=thresholds)


# -- decision function ------------------------------------------------------
def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to bfloat16 (nearest even), kept as float32."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def tnn_labels(t: Ternary, x: np.ndarray, dtype: str = "float32"
               ) -> np.ndarray:
    """Class labels of raw readings `x` (S, F); `dtype="bfloat16"` is the
    control: readings and thresholds rounded to bfloat16 first."""
    x = np.asarray(x, dtype=np.float32)
    thr = np.asarray(t.thresholds, dtype=np.float32)
    if dtype == "bfloat16":
        x, thr = _bf16(x), _bf16(thr)
    elif dtype != "float32":
        raise ValueError(f"unknown reference precision {dtype!r}")
    xbin = (x > thr[None, :]).astype(np.int64)
    hbit = (xbin @ t.w1.astype(np.int64) >= 0).astype(np.int64)
    score = hbit @ (t.w2 == 1) + (1 - hbit) @ (t.w2 == -1)
    return np.argmax(score, axis=1).astype(np.int32)


# -- gate-by-gate netlist walk ---------------------------------------------
# opcodes of the program's gate enum, each with its truth function
_GATES = {
    0: lambda a, b: np.zeros_like(a),        # INPUT (never a live gate)
    1: lambda a, b: np.zeros_like(a),        # CONST0
    2: lambda a, b: np.ones_like(a),         # CONST1
    3: lambda a, b: a,                       # BUF
    4: lambda a, b: ~a,                      # NOT
    5: lambda a, b: a & b,                   # AND
    6: lambda a, b: a | b,                   # OR
    7: lambda a, b: a ^ b,                   # XOR
    8: lambda a, b: ~(a & b),                # NAND
    9: lambda a, b: ~(a | b),                # NOR
    10: lambda a, b: ~(a ^ b),               # XNOR
    11: lambda a, b: a & ~b,                 # ANDN
    12: lambda a, b: a | ~b,                 # ORN
}


@dataclass
class GateList:
    """A netlist as plain arrays: node ids < n_inputs are inputs, gate g is
    node n_inputs + g; `outputs` are LSB-first bits of an unsigned int."""

    n_inputs: int
    op: np.ndarray
    in0: np.ndarray
    in1: np.ndarray
    outputs: np.ndarray


def walk_netlist(nl: GateList, bits: np.ndarray) -> np.ndarray:
    """Evaluate `nl` on boolean inputs `bits` (S, n_inputs); returns the
    (S,) unsigned integers its output bits spell."""
    bits = np.asarray(bits, dtype=bool)
    S = bits.shape[0]
    nodes = [bits[:, i] for i in range(nl.n_inputs)]
    zero = np.zeros(S, dtype=bool)
    for g in range(len(nl.op)):
        a = nodes[nl.in0[g]] if nl.in0[g] < len(nodes) else zero
        b = nodes[nl.in1[g]] if nl.in1[g] < len(nodes) else zero
        nodes.append(_GATES[int(nl.op[g])](a, b))
    out = np.zeros(S, dtype=np.int64)
    for k, node in enumerate(nl.outputs):
        out |= nodes[int(node)].astype(np.int64) << k
    return out


# -- area of a design --------------------------------------------------------
# mm^2 per gate opcode in the printed EGFET library the paper costs its
# circuits with; inputs, rails and wires are free
GATE_AREA = {0: 0.0, 1: 0.0, 2: 0.0, 3: 0.0, 4: 0.045, 5: 0.11, 6: 0.11,
             7: 0.22, 8: 0.08, 9: 0.08, 10: 0.22, 11: 0.13, 12: 0.13}
NOT, AND, OR, XNOR, ANDN, ORN = 4, 5, 6, 10, 11, 12


def live_area(nl: GateList) -> float:
    """Area of the gates that some output of `nl` depends on."""
    live = {int(o) for o in nl.outputs}
    area = 0.0
    for g in range(len(nl.op) - 1, -1, -1):
        if nl.n_inputs + g not in live:
            continue
        op = int(nl.op[g])
        area += GATE_AREA[op]
        if op > 2:                       # not an input or a rail
            live.add(int(nl.in0[g]))
            if op > NOT:                 # two-input gate
                live.add(int(nl.in1[g]))
    return area


def count_width(n: int) -> int:
    """Bits of a count of `n` inputs (0..n)."""
    return max(1, int(n).bit_length())


def comparator_area(j: int) -> float:
    """A j-bit `a >= b` ripple comparator: one ORN on the low bits, then
    per bit an ANDN (greater), an XNOR (equal), an AND and an OR."""
    return GATE_AREA[ORN] + (j - 1) * (GATE_AREA[ANDN] + GATE_AREA[XNOR]
                                       + GATE_AREA[AND] + GATE_AREA[OR])


@dataclass
class ApproxProblem:
    """What the campaign's objective scores, as plain data: the ternary
    wiring, the scored readings, and per gene the candidate gate lists
    (a hidden gene's candidate is a pair of popcounts, positive and
    negative inputs; an output gene's is one popcount)."""

    w1: np.ndarray                     # (F, H) int8
    w2: np.ndarray                     # (H, C) int8
    xbin: np.ndarray                   # (S, F) 0/1 readings
    y: np.ndarray                      # (S,) int32 true classes
    hidden_genes: list[int]            # neuron index of each hidden gene
    hidden_cands: list[list[tuple[GateList, GateList]]]
    out_cands: list[GateList]          # output popcount candidates


def hidden_genes(w1: np.ndarray) -> list[int]:
    """Hidden neurons with both positive and negative inputs: one gene
    each.  The others are fixed: no negative input makes the neuron a
    constant 1, no positive input a NOR of the negative ones."""
    return [i for i in range(w1.shape[1])
            if (w1[:, i] == 1).any() and (w1[:, i] == -1).any()]


def fixed_area(w1: np.ndarray, w2: np.ndarray) -> float:
    """Area no gene chooses: the NOR trees of neurons with no positive
    input, one NOT a negative output weight, and the argmax's C-1
    comparators and score-wide muxes (AND, ANDN and OR a bit)."""
    area = 0.0
    for i in range(w1.shape[1]):
        n_pos, n_neg = int((w1[:, i] == 1).sum()), int((w1[:, i] == -1).sum())
        if n_pos == 0 and n_neg > 0:
            area += (n_neg - 1) * GATE_AREA[OR] + GATE_AREA[NOT]
    area += GATE_AREA[NOT] * int((w2 == -1).sum())
    j = count_width(int((w2[:, 0] != 0).sum()))
    mux = GATE_AREA[AND] + GATE_AREA[ANDN] + GATE_AREA[OR]
    return area + (w2.shape[1] - 1) * (comparator_area(j) + mux * j)


def approx_objectives(p: ApproxProblem, genomes: np.ndarray,
                      control: bool = False) -> np.ndarray:
    """(error rate, area in mm^2) of each genome (G, n_genes).

    A hidden gene's neuron fires when its positive popcount is at least
    its negative one; the class is the first output with the highest
    popcount.  The area is the fixed hardware plus each chosen
    candidate's live gates.

    `control=True` breaks the objective's guarantees the way a tempting
    shortcut would: only the readings that fill whole 32-bit words are
    scored (the last partial word is left out), and areas are summed in
    float32."""
    xbin = np.asarray(p.xbin, dtype=np.int64)
    y = p.y
    if control:
        keep = xbin.shape[0] // 32 * 32
        xbin, y = xbin[:keep], y[:keep]
    dt = np.float32 if control else np.float64
    exact_h = (xbin @ p.w1.astype(np.int64) >= 0)
    H, C = p.w2.shape
    nh = len(p.hidden_genes)
    hid_bits: dict[tuple[int, int], np.ndarray] = {}
    hid_area = [[dt(live_area(a)) + dt(live_area(b)) for a, b in cands]
                for cands in p.hidden_cands]
    out_area = [dt(live_area(nl)) for nl in p.out_cands]
    base = dt(fixed_area(p.w1, p.w2))
    out = np.empty((len(genomes), 2), dtype=np.float64)
    for r, x in enumerate(np.asarray(genomes, dtype=np.int64)):
        h = exact_h.copy()
        area = base
        for g, i in enumerate(p.hidden_genes):
            k = int(x[g])
            if (g, k) not in hid_bits:
                pos, neg = p.hidden_cands[g][k]
                col = p.w1[:, i]
                hid_bits[g, k] = (walk_netlist(pos, xbin[:, col == 1])
                                  >= walk_netlist(neg, xbin[:, col == -1]))
            h[:, i] = hid_bits[g, k]
            area = dt(area + hid_area[g][k])
        scores = np.zeros((xbin.shape[0], C), dtype=np.int64)
        for o in range(C):
            k = int(x[nh + o])
            area = dt(area + out_area[k])
            col = p.w2[:, o]
            inp = np.concatenate([h[:, col == 1], ~h[:, col == -1]], axis=1)
            if inp.shape[1]:
                scores[:, o] = walk_netlist(p.out_cands[k], inp)
        out[r] = (1.0 - float((np.argmax(scores, axis=1) == y).mean()),
                  float(area))
    return out
