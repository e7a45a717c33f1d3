"""Analytic roofline placement for the circuit-simulation kernel variants.

`roofline.analysis` extracts terms from *compiled* XLA artifacts; the
Pallas circuit kernels need the complementary view — a first-principles
count of the word-ops and HBM bytes each variant moves for a given
workload shape, which shows *why* the fused megakernel wins: not a faster gate loop, but orders of magnitude less traffic.

Workload: P programs x G gates x W uint32 words (32 test vectors per
word), n_in packed input rows, n_out output taps.  Every gate applies the
4-term ANF form (xor/and over 32-lane words — ~6 word-ops), so all
variants share one compute term:

    ops = P * G * W * ANF_OPS_PER_GATE_WORD

What separates them is bytes:

  * ``swar``    — the `lax.scan` twin keeps a (P, n_in+G, W) value carry
    live across gate steps; XLA materializes the carry per step, so each
    gate pays a gather read (2 operand rows) and a row write, and the
    LSB-first decode then expands each output's words into a (P, W, 32)
    int32 bit plane on the host side of the kernel boundary.
  * ``pallas_unfused`` — the pre-fusion two-stage path: the kernel walks
    gates in VMEM scratch (plan + words in, (P, n_out, W) words out), but
    the decode stage re-reads those words and builds the same per-output
    (P, W, 32) planes.
  * ``pallas_fused`` — gate walk + output extraction + decode in ONE
    launch: plan tables and the word plane stream in, the value plane
    never leaves VMEM, and the ONLY output traffic is the decoded
    (P, W*32) int32 plane.
  * ``fleet``   — the multi-tenant variant: same fused traffic but over
    tables padded to (T, G_max+1) / (T, n_in_max, W_max); `efficiency`
    reports real work / padded work, the price of one-launch dispatch.

All byte counts are HBM-side (VMEM-resident traffic is free by
construction — that is the point of the fusion); `Roofline.dominant`
then places each variant on the same TPU-v5e roofline the rest of the
repo uses.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.roofline.analysis import Roofline

ANF_OPS_PER_GATE_WORD = 6     # r = m0 ^ (ma&a) ^ (mb&b) ^ (mab&(a&b))
_PLAN_BYTES_PER_GATE = 4 + 4 + 4 * 4   # in0 + in1 + four uint32 ANF masks
_WORD = 4                     # uint32
_INT = 4                      # int32 decoded outputs
_WORD_TILE = 128              # pallas_circuit_sim.DEFAULT_BLOCK_WORDS


@dataclass
class CircuitShape:
    """One population-eval workload: P programs x G gates x W words."""
    P: int
    G: int
    n_in: int
    W: int
    n_out: int
    shared_words: bool = True

    @property
    def vectors(self) -> int:
        return self.W * 32

    def _words_bytes(self) -> int:
        rows = self.n_in if self.shared_words else self.P * self.n_in
        return rows * self.W * _WORD

    def _plan_bytes(self) -> int:
        return self.P * (self.G * _PLAN_BYTES_PER_GATE + self.n_out * 4)

    def _decode_plane_bytes(self) -> int:
        # the unfused decode builds one (P, W, 32) int32 bit plane per
        # output bit (write + accumulate read), then the final int plane
        per_output = 2 * self.P * self.W * 32 * _INT
        return self.n_out * per_output + self.P * self.vectors * _INT

    @property
    def ops(self) -> float:
        return float(self.P * self.G * self.W * ANF_OPS_PER_GATE_WORD)


def swar_roofline(s: CircuitShape) -> Roofline:
    # per gate step the scan carry pays 2 gathered operand rows (read) and
    # one result row (write) at HBM, per program
    carry = s.P * s.G * 3 * s.W * _WORD
    out_words = s.P * s.n_out * s.W * _WORD
    byt = s._plan_bytes() + s._words_bytes() + carry + out_words \
        + out_words + s._decode_plane_bytes()
    return Roofline(flops=s.ops, bytes_accessed=float(byt),
                    collective_bytes=0.0)


def pallas_unfused_roofline(s: CircuitShape) -> Roofline:
    # stage 1: plan + words in, output words out (value plane in VMEM);
    # stage 2: output words back in, decode planes out
    out_words = s.P * s.n_out * s.W * _WORD
    byt = s._plan_bytes() + s._words_bytes() + out_words \
        + out_words + s._decode_plane_bytes()
    return Roofline(flops=s.ops, bytes_accessed=float(byt),
                    collective_bytes=0.0)


def pallas_fused_roofline(s: CircuitShape) -> Roofline:
    # one launch: the grid walks one population row at a time; a shared
    # word plane that spans several 128-word tiles is re-streamed once per
    # row (a single tile stays resident), and the only output is the
    # decoded int plane
    tiles = s.P if s.shared_words and s.W > _WORD_TILE else 1
    byt = s._plan_bytes() + tiles * s._words_bytes() \
        + s.P * s.vectors * _INT
    return Roofline(flops=s.ops, bytes_accessed=float(byt),
                    collective_bytes=0.0)


def fleet_roofline(shapes: list[CircuitShape]) -> tuple[Roofline, float]:
    """Padded multi-tenant launch over per-tenant shapes (P=1 each).

    Returns the roofline of the ONE fused launch plus its padding
    efficiency (real gate-word work / padded gate-word work) — the cost
    of forcing T heterogeneous plans into common (G_max, n_in_max,
    W_max) tables.
    """
    T = len(shapes)
    if T == 0:
        raise ValueError("fleet_roofline needs at least one tenant shape")
    G_max = max(s.G for s in shapes) + 1      # +1 trailing CONST0 pad gate
    n_in_max = max(s.n_in for s in shapes)
    W_max = max(s.W for s in shapes)
    n_out_max = max(s.n_out for s in shapes)
    padded = CircuitShape(P=T, G=G_max, n_in=n_in_max, W=W_max,
                          n_out=n_out_max, shared_words=False)
    real_ops = sum(s.ops for s in shapes)
    eff = real_ops / padded.ops if padded.ops else 1.0
    return pallas_fused_roofline(padded), eff


def variant_rows(s: CircuitShape) -> list[dict]:
    """One BENCH-ready row per single-program kernel variant."""
    rows = []
    for name, rl in (("swar", swar_roofline(s)),
                     ("pallas_unfused", pallas_unfused_roofline(s)),
                     ("pallas_fused", pallas_fused_roofline(s))):
        rows.append({
            "variant": name,
            "ops": rl.flops,
            "hbm_bytes": rl.bytes_accessed,
            "arith_intensity": round(rl.flops / rl.bytes_accessed, 3),
            "dominant": rl.dominant,
            "bound_s": rl.bound_s,
        })
    return rows
