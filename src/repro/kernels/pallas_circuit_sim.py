"""Pallas kernel: population-parallel gate-level circuit simulation.

The campaign hot loop — (population of genomes) x (packed test words) —
as a Pallas kernel instead of the `lax.scan` SWAR twin in
`kernels/circuit_sim.py`.  Three entry points share one kernel body:

  * `simulate_population` — output *words* `(P, n_out, W)`, the
    conformance-suite surface (bit-identical to both host evaluators);
  * `fused_eval_uint` — gate walk, output-word extraction and LSB-first
    integer decode in ONE `pallas_call`: the value plane never leaves
    VMEM and each grid cell writes its decoded int32 tile directly;
  * `fleet_eval_words` — the **multi-program megakernel**: T tenants'
    plan tables padded to a common gate budget, grid over
    (tenant x word-tile), so a serving fleet evaluates its whole manifest
    in one launch instead of per-tenant batches.

Grid layout is (population rows, word tiles).  Each program instance
owns one population row (one genome, or one tenant) and a `bw`-wide tile
of packed test words:

  * the row's plan — gate taps and the per-gate ANF coefficient masks —
    arrives as a `(1, 6, G)` int32 **SMEM** block, so every gate reads
    its operands' node indices as scalars;
  * the value plane is a `(n_inputs + G, bw)` int32 VMEM scratch; gate g
    reads rows `in0[g]`/`in1[g]` and writes row `n_inputs + g` with
    `pl.ds`, a dynamic *sublane* index, which Mosaic lowers;
  * `bw` is either the whole word axis or a multiple of 128 lanes;
  * the decode writes a `(32, bw)` tile (row s = bit s of every word)
    and the jitted wrapper lays the tiles out as `(P, W*32)`.

Gates apply through the same algebraic normal form
r = m0 ^ (ma & a) ^ (mb & b) ^ (mab & (a & b)) as both host evaluators,
with the masks precomputed on the host, so the body is branch-free
regardless of opcode mix.  Words travel as int32 (a bitcast of the
uint32 lanes): every operation on them is bitwise, so the bits are
unchanged.

Bit-compatibility contract (pinned by tests/test_conformance.py):
identical output words to `NetlistPopulation.simulate` (lane-split via
`pack_words32`) and to `circuit_sim.simulate_population`, for both shared
`(n_inputs, W)` and per-individual `(P, n_inputs, W)` word planes; the
fused decode matches `circuit_sim.population_eval_uint` integer for
integer, and the fleet kernel matches per-tenant dispatch on every
tenant regardless of gate-count/feature-count/output-width skew
(padding must never leak into outputs).

The kernel compiles on a TPU and runs in interpret mode on the CPU; on
any other platform it refuses to run (`_interpret`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.circuit_sim import _C0_TBL, _CA_TBL, _CAB_TBL, _CB_TBL

DEFAULT_BLOCK_WORDS = 128
LANES = 128
# kernel names, so a profile's device rows read the same after a refactor
GATE_WALK = "tnn_gate_walk"         # one program (or a genome population)
FLEET_WALK = "tnn_fleet_walk"       # the multi-program megakernel


def _interpret() -> bool:
    """Interpret mode on the CPU, compiled on a TPU, an error elsewhere."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"the Pallas circuit kernel runs compiled on a TPU or "
                       f"interpreted on the CPU, not on {platform!r}")


def _word_tile(W: int, block_words: int | None) -> int:
    """Word-tile width: the whole word axis, or a multiple of 128 lanes."""
    bw = DEFAULT_BLOCK_WORDS if block_words is None else int(block_words)
    if bw <= 0 or bw % LANES:
        raise ValueError(f"block_words must be a positive multiple of "
                         f"{LANES}, got {block_words}")
    return W if W <= bw else bw


def _kernel(plan_ref, outputs_ref, words_ref, out_ref, vals_ref, *,
            n_inputs: int, n_gates: int, n_out: int, decode: bool):
    # plan_ref (1, 6, G) and outputs_ref (1, 1, n_out) int32 in SMEM;
    # words_ref (n_inputs, bw) shared or (1, n_inputs, bw) per-row;
    # vals_ref (n_inputs + G, bw) int32 VMEM scratch; out_ref (1, 32, bw)
    # decoded or (1, n_out, bw) output words.
    bw = vals_ref.shape[1]
    words = words_ref[0] if len(words_ref.shape) == 3 else words_ref[...]
    vals_ref[pl.ds(0, n_inputs), :] = words
    vals_ref[pl.ds(n_inputs, n_gates), :] = jnp.zeros((n_gates, bw),
                                                      jnp.int32)

    def body(g, carry):
        a = vals_ref[pl.ds(plan_ref[0, 0, g], 1), :]
        b = vals_ref[pl.ds(plan_ref[0, 1, g], 1), :]
        r = (plan_ref[0, 2, g] ^ (plan_ref[0, 3, g] & a)
             ^ (plan_ref[0, 4, g] & b) ^ (plan_ref[0, 5, g] & (a & b)))
        vals_ref[pl.ds(n_inputs + g, 1), :] = r
        return carry

    jax.lax.fori_loop(0, n_gates, body, 0)
    if decode:
        # LSB-first: vector s of word w is bit (s % 32), so tile row s
        # collects bit s of every word, one output bit per integer bit
        shifts = jax.lax.broadcasted_iota(jnp.int32, (32, bw), 0)
        acc = jnp.zeros((32, bw), jnp.int32)
        for o in range(n_out):                 # n_out is static and small
            row = vals_ref[pl.ds(outputs_ref[0, 0, o], 1), :]
            acc = acc | ((jax.lax.shift_right_logical(row, shifts) & 1) << o)
        out_ref[0] = acc
    else:
        for o in range(n_out):
            out_ref[0, pl.ds(o, 1), :] = vals_ref[
                pl.ds(outputs_ref[0, 0, o], 1), :]


@partial(jax.jit, static_argnames=("n_inputs", "block_words", "decode",
                                   "interpret", "name"))
def _fused_padded(plan, outputs, words32, *, n_inputs: int,
                  block_words: int, decode: bool, interpret: bool,
                  name: str = GATE_WALK):
    """`plan` (P, 6, G) int32, `outputs` (P, 1, n_out) int32, `words32`
    (n_inputs, Wp) or (P, n_inputs, Wp) uint32 with Wp a multiple of
    `block_words`.  Returns (P, Wp*32) int32 decoded integers, or
    (P, n_out, Wp) uint32 output words when `decode` is False.  `name`
    names the kernel and its scope."""
    P, _, G = plan.shape
    n_out = outputs.shape[2]
    Wp = words32.shape[-1]
    bw = block_words
    words = jax.lax.bitcast_convert_type(words32, jnp.int32)
    words_spec = (pl.BlockSpec((n_inputs, bw), lambda p, w: (0, w))
                  if words.ndim == 2 else
                  pl.BlockSpec((1, n_inputs, bw), lambda p, w: (p, 0, w)))
    rows = 32 if decode else n_out
    with jax.named_scope(name):
        out = pl.pallas_call(
            partial(_kernel, n_inputs=n_inputs, n_gates=G, n_out=n_out,
                    decode=decode),
            grid=(P, Wp // bw),
            in_specs=[pl.BlockSpec((1, 6, G), lambda p, w: (p, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, 1, n_out), lambda p, w: (p, 0, 0),
                                   memory_space=pltpu.SMEM),
                      words_spec],
            out_specs=pl.BlockSpec((1, rows, bw), lambda p, w: (p, 0, w)),
            out_shape=jax.ShapeDtypeStruct((P, rows, Wp), jnp.int32),
            scratch_shapes=[pltpu.VMEM((n_inputs + G, bw), jnp.int32)],
            interpret=interpret,
            name=name,
        )(plan, outputs, words)
    if decode:
        return out.transpose(0, 2, 1).reshape(P, Wp * 32)
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


def _plan_table(op, in0, in1) -> np.ndarray:
    """(P, G) opcodes + operand taps -> the kernel's (P, 6, G) int32 plan:
    rows in0, in1 and the four ANF coefficient masks (as int32 bits).

    A gateless plan gets one dead CONST0 gate (zero-size blocks are
    illegal in pallas_call); outputs never tap it."""
    op = np.asarray(op, dtype=np.int64)
    in0 = np.asarray(in0, dtype=np.int32)
    in1 = np.asarray(in1, dtype=np.int32)
    if op.shape[1] == 0:
        from repro.hw.egfet import Gate
        P = op.shape[0]
        op = np.full((P, 1), int(Gate.CONST0), dtype=np.int64)
        in0 = in1 = np.zeros((P, 1), dtype=np.int32)
    masks = [tbl[op].view(np.int32) for tbl in (_C0_TBL, _CA_TBL, _CB_TBL,
                                                 _CAB_TBL)]
    return np.ascontiguousarray(np.stack([in0, in1, *masks], axis=1))


def _run(op, in0, in1, outputs, words32, n_inputs: int, *,
         block_words: int | None, decode: bool,
         name: str = GATE_WALK) -> jax.Array:
    with obs.span("dispatch.plan"):
        plan = _plan_table(op, in0, in1)
        outputs = np.asarray(outputs, dtype=np.int32)[:, None, :]
    with obs.span("dispatch.h2d"):
        words32 = jnp.asarray(words32, dtype=jnp.uint32)
        W = words32.shape[-1]
        bw = _word_tile(W, block_words)
        wpad = (-W) % bw
        if wpad:
            pad_width = [(0, 0)] * (words32.ndim - 1) + [(0, wpad)]
            words32 = jnp.pad(words32, pad_width)
        plan, outputs = jnp.asarray(plan), jnp.asarray(outputs)
    with obs.span("dispatch.launch"):
        return _fused_padded(plan, outputs, words32, n_inputs=n_inputs,
                             block_words=bw, decode=decode,
                             interpret=_interpret(), name=name)


def simulate_population(op, in0, in1, outputs, words32, n_inputs: int, *,
                        block_words: int | None = None) -> jax.Array:
    """Pallas twin of `circuit_sim.simulate_population`.

    op/in0/in1: (P, G) int; outputs: (P, n_out) int; words32: (n_inputs, W)
    shared or (P, n_inputs, W) per-individual uint32 words.  Returns
    (P, n_out, W) uint32, bit-identical to both existing evaluators.
    """
    P = np.shape(op)[0]
    n_out = np.shape(outputs)[1]
    W = np.shape(words32)[-1]
    if W == 0:
        # a zero-width word plane has nothing to simulate: no zero-size
        # grid or block may reach pallas_call
        return jnp.zeros((P, n_out, 0), dtype=jnp.uint32)
    out = _run(op, in0, in1, outputs, words32, n_inputs,
               block_words=block_words, decode=False)
    return out[:, :, :W]


def fused_eval_uint(op, in0, in1, outputs, words32, n_inputs: int, *,
                    block_words: int | None = None) -> jax.Array:
    """Fused gate-walk + decode: `(P, W*32)` int32 in one `pallas_call`.

    Bit-identical to `circuit_sim.population_eval_uint` (and therefore to
    decoding `simulate_population`'s words on the host), for shared and
    per-individual word planes.
    """
    P = np.shape(op)[0]
    W = np.shape(words32)[-1]
    if W == 0:
        return jnp.zeros((P, 0), dtype=jnp.int32)
    out = _run(op, in0, in1, outputs, words32, n_inputs,
               block_words=block_words, decode=True)
    return out[:, : W * 32]


population_eval_uint = fused_eval_uint


# ---------------------------------------------------------------------------
# Multi-program megakernel: T tenants' plans padded to one gate budget,
# grid over (tenant x word-tile), one launch for the whole manifest.
# ---------------------------------------------------------------------------
def fleet_eval_words(plans, words_list, *,
                     block_words: int | None = None) -> list[np.ndarray]:
    """Evaluate T single-program circuits over T word planes in ONE launch.

    `plans` is a list of `(op, in0, in1, outputs, n_inputs)` tuples —
    each a single program's plan (arrays may be `(G,)`/`(n_out,)` 1-D or
    `(1, G)`/`(1, n_out)` rows); `words_list` holds each tenant's packed
    `(n_inputs_t, W_t)` uint32 word plane.  Plans are padded to a common
    gate budget and feature count (node indices remapped so gate nodes
    land after the padded input rows), every tenant gets one trailing
    CONST0 pad gate, and padded output taps point at that known-zero node
    — so neither the gate-budget pad, the feature pad, the word pad nor
    the output pad can leak into any tenant's decoded integers.  Returns
    one `(W_t * 32,)` int32 array per tenant, bit-identical to running
    each plan through `fused_eval_uint` on its own.
    """
    from repro.hw.egfet import Gate

    if not plans:
        return []
    if len(plans) != len(words_list):
        raise ValueError(f"{len(plans)} plans but {len(words_list)} word "
                         "planes")
    norm = []
    for i, (op, in0, in1, outputs, n_in) in enumerate(plans):
        op = np.asarray(op, dtype=np.int16).reshape(-1)
        in0 = np.asarray(in0, dtype=np.int32).reshape(-1)
        in1 = np.asarray(in1, dtype=np.int32).reshape(-1)
        outputs = np.asarray(outputs, dtype=np.int32).reshape(-1)
        w = np.ascontiguousarray(words_list[i], dtype=np.uint32)
        if w.ndim != 2 or w.shape[0] != n_in:
            raise ValueError(f"plan {i}: word plane {w.shape} does not "
                             f"match n_inputs={n_in}")
        norm.append((op, in0, in1, outputs, int(n_in), w))

    with obs.span("dispatch.plan"):      # the padded manifest plan
        T = len(norm)
        n_in_max = max(p[4] for p in norm)
        G_max = max(p[0].shape[0] for p in norm) + 1  # +1: zero node
        n_out_max = max(p[3].shape[0] for p in norm)
        W_list = [p[5].shape[1] for p in norm]
        W_max = max(W_list)
        if W_max == 0:
            return [np.zeros(0, dtype=np.int32) for _ in norm]

        zero_node = n_in_max + G_max - 1    # the trailing CONST0 pad gate
        op_t = np.full((T, G_max), int(Gate.CONST0), dtype=np.int16)
        in0_t = np.zeros((T, G_max), dtype=np.int32)
        in1_t = np.zeros((T, G_max), dtype=np.int32)
        out_t = np.full((T, n_out_max), zero_node, dtype=np.int32)
        words_t = np.zeros((T, n_in_max, W_max), dtype=np.uint32)

        def remap(idx: np.ndarray, n_in: int) -> np.ndarray:
            # tenant node numbering: inputs 0..n_in-1, gates n_in.. —
            # shift the gate nodes past the padded input rows
            return np.where(idx >= n_in, idx + (n_in_max - n_in), idx)

        for t, (op, in0, in1, outputs, n_in, w) in enumerate(norm):
            G = op.shape[0]
            op_t[t, :G] = op
            in0_t[t, :G] = remap(in0, n_in)
            in1_t[t, :G] = remap(in1, n_in)
            out_t[t, : outputs.shape[0]] = remap(outputs, n_in)
            words_t[t, :n_in, : w.shape[1]] = w

    out = _run(op_t, in0_t, in1_t, out_t, words_t, n_in_max,
               block_words=block_words, decode=True, name=FLEET_WALK)
    with obs.span("dispatch.fetch"):
        out = np.asarray(out)
    return [out[t, : W_list[t] * 32] for t in range(T)]
