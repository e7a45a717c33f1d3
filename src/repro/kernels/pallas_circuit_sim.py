"""Pallas kernel: population-parallel gate-level circuit simulation.

The campaign hot loop — (population of genomes) x (packed test words) —
as a Pallas kernel instead of the `lax.scan` SWAR twin in
`kernels/circuit_sim.py`.  Three entry points share one kernel body:

  * `simulate_population` — output *words* `(P, n_out, W)`, the
    conformance-suite surface (bit-identical to both host evaluators);
  * `fused_eval_uint` — gate walk, output-word extraction and LSB-first
    integer decode in ONE `pallas_call`: the value plane never leaves
    VMEM and each grid cell writes its decoded int32 tile directly;
  * `fleet_walk` — the **multi-program megakernel**: a `FleetTable`
    holds every tenant's plan padded to a common gate budget, on the
    device, built once a manifest generation (`fleet_table`); a launch
    passes only the due tenants' slots (scalar-prefetched, so each grid
    row reads its tenant's plan block straight from the table) and their
    word planes, grid over (slot x word-tile), and each slot walks only
    its own gates.  Slot counts are padded to the table's few launch
    shapes (`launch_buckets`), so a warmed fleet never compiles again.
    `fleet_eval_words` is the one-shot form over ad-hoc plans.

Grid layout is (population rows, word tiles).  Each program instance
owns one population row (one genome, or one tenant) and a `bw`-wide tile
of packed test words:

  * the row's plan — gate taps and the per-gate ANF coefficient masks —
    arrives as a `(1, 6, G)` int32 **SMEM** block, so every gate reads
    its operands' node indices as scalars;
  * the value plane is a `(n_inputs + G, bw)` int32 VMEM scratch; gate g
    reads rows `in0[g]`/`in1[g]` and writes row `n_inputs + g` with
    `pl.ds`, a dynamic *sublane* index, which Mosaic lowers;
  * `bw` is the whole word axis, or `DEFAULT_BLOCK_WORDS` (128 lanes)
    when the axis is wider;
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

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.circuit_sim import _C0_TBL, _CA_TBL, _CAB_TBL, _CB_TBL

DEFAULT_BLOCK_WORDS = 128
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


def _word_tile(W: int) -> int:
    """Word-tile width: the whole word axis, or `DEFAULT_BLOCK_WORDS`."""
    return min(W, DEFAULT_BLOCK_WORDS)


def _kernel(plan_ref, outputs_ref, words_ref, out_ref, vals_ref, *,
            n_inputs: int, n_gates: int, n_out: int, decode: bool,
            walk=None):
    # plan_ref (1, 6, G) and outputs_ref (1, 1, n_out) int32 in SMEM;
    # words_ref (n_inputs, bw) shared or (1, n_inputs, bw) per-row;
    # vals_ref (n_inputs + G, bw) int32 VMEM scratch; out_ref (1, 32, bw)
    # decoded or (1, n_out, bw) output words.  `walk`, a traced scalar,
    # stops the walk after the row's own gates (the rest stay zero).
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

    jax.lax.fori_loop(0, n_gates if walk is None else walk, body, 0)
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
         decode: bool, name: str = GATE_WALK) -> jax.Array:
    with obs.span("dispatch.plan"):
        plan = _plan_table(op, in0, in1)
        outputs = np.asarray(outputs, dtype=np.int32)[:, None, :]
    with obs.span("dispatch.h2d"):
        words32 = jnp.asarray(words32, dtype=jnp.uint32)
        W = words32.shape[-1]
        bw = _word_tile(W)
        wpad = (-W) % bw
        if wpad:
            pad_width = [(0, 0)] * (words32.ndim - 1) + [(0, wpad)]
            words32 = jnp.pad(words32, pad_width)
        plan, outputs = jnp.asarray(plan), jnp.asarray(outputs)
    with obs.span("dispatch.launch"):
        return _fused_padded(plan, outputs, words32, n_inputs=n_inputs,
                             block_words=bw, decode=decode,
                             interpret=_interpret(), name=name)


def simulate_population(op, in0, in1, outputs, words32,
                        n_inputs: int) -> jax.Array:
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
    out = _run(op, in0, in1, outputs, words32, n_inputs, decode=False)
    return out[:, :, :W]


def fused_eval_uint(op, in0, in1, outputs, words32,
                    n_inputs: int) -> jax.Array:
    """Fused gate-walk + decode: `(P, W*32)` int32 in one `pallas_call`.

    Bit-identical to `circuit_sim.population_eval_uint` (and therefore to
    decoding `simulate_population`'s words on the host), for shared and
    per-individual word planes.
    """
    P = np.shape(op)[0]
    W = np.shape(words32)[-1]
    if W == 0:
        return jnp.zeros((P, 0), dtype=jnp.int32)
    out = _run(op, in0, in1, outputs, words32, n_inputs, decode=True)
    return out[:, : W * 32]


population_eval_uint = fused_eval_uint


# ---------------------------------------------------------------------------
# Multi-program megakernel: every tenant's plan padded to one gate budget in
# a device-resident table; a launch names the due tenants' slots.
# ---------------------------------------------------------------------------
def launch_buckets(n_rows: int) -> tuple[int, ...]:
    """Slot counts a table of `n_rows` tenants launches at: the powers of
    two below `n_rows`, then `n_rows` (1, 2, 4, 8, 16, 20 for 20)."""
    out, b = [], 1
    while b < n_rows:
        out.append(b)
        b *= 2
    return tuple(out) + (n_rows,)


@dataclass(frozen=True)
class FleetTable:
    """The padded plans of every program a fused launch may carry.

    Device arrays: `plan` (N + 1, 6, G) and `outputs` (N + 1, 1, n_out)
    int32, `gates` (N + 1,) int32, each row's own gate count.  Row N is
    empty (no inputs, no gates, outputs on the zero node): a launch's
    pad slots point there.  Program rows take `n_inputs` input rows (the
    largest) and word planes up to `words` wide (the widest), walked in
    tiles of `block_words`; `buckets` are the slot counts a launch is
    padded to, one launch shape each.  `row_inputs` and `row_gates` are
    the host copies of each row's own sizes."""

    plan: jax.Array
    outputs: jax.Array
    gates: jax.Array
    row_inputs: tuple[int, ...]
    row_gates: tuple[int, ...]
    n_inputs: int
    words: int
    block_words: int
    buckets: tuple[int, ...]
    device: object

    @property
    def empty_row(self) -> int:
        return len(self.row_gates) - 1

    @property
    def padded_words(self) -> int:
        return self.words + (-self.words) % self.block_words

    def bucket(self, n_slots: int) -> int:
        """The launch shape a launch of `n_slots` slots runs at."""
        for b in self.buckets:
            if b >= n_slots:
                return b
        raise ValueError(f"{n_slots} slots exceed the table's "
                         f"{self.buckets[-1]} rows")

    def gate_steps(self, slots, words_used) -> tuple[int, int]:
        """(real, walked) gate steps of a launch, one a gate and a word:
        real over the words holding readings, walked over every word a
        slot's grid row covers.  Pad slots walk no gates."""
        wp = self.padded_words
        real = walked = 0
        for s, w in zip(slots, words_used):
            g = self.row_gates[s]
            real += g * w
            walked += g * wp
        return real, walked


def fleet_table(plans, words: int, *,
                buckets: tuple[int, ...] | None = None) -> FleetTable:
    """Pad `plans`, one `(op, in0, in1, outputs, n_inputs)` tuple a
    program (1-D or `(1, G)` rows), into a `FleetTable` on the default
    device for word planes up to `words` wide, in the word tiles of
    `_word_tile`.

    Gate nodes shift past the largest input count, every row gets the
    common gate budget (largest + 1: the last node is never written, so
    it stays zero) and padded output taps point at that zero node, so no
    pad can reach any program's decoded integers."""
    from repro.hw.egfet import Gate

    if not plans:
        raise ValueError("a fleet table needs at least one plan")
    with obs.span("dispatch.plan"):
        norm = []
        for op, in0, in1, outputs, n_in in plans:
            norm.append((np.asarray(op, dtype=np.int64).reshape(-1),
                         np.asarray(in0, dtype=np.int32).reshape(-1),
                         np.asarray(in1, dtype=np.int32).reshape(-1),
                         np.asarray(outputs, dtype=np.int32).reshape(-1),
                         int(n_in)))
        N = len(norm)
        n_in_max = max(p[4] for p in norm)
        G = max(p[0].shape[0] for p in norm) + 1
        n_out = max(p[3].shape[0] for p in norm)
        zero_node = n_in_max + G - 1
        op_t = np.full((N + 1, G), int(Gate.CONST0), dtype=np.int64)
        in0_t = np.zeros((N + 1, G), dtype=np.int32)
        in1_t = np.zeros((N + 1, G), dtype=np.int32)
        out_t = np.full((N + 1, 1, n_out), zero_node, dtype=np.int32)
        gates = np.zeros(N + 1, dtype=np.int32)

        def remap(idx: np.ndarray, n_in: int) -> np.ndarray:
            # program numbering: inputs 0..n_in-1, gates n_in.. — shift
            # the gate nodes past the padded input rows
            return np.where(idx >= n_in, idx + (n_in_max - n_in), idx)

        for t, (op, in0, in1, outputs, n_in) in enumerate(norm):
            g = op.shape[0]
            op_t[t, :g] = op
            in0_t[t, :g] = remap(in0, n_in)
            in1_t[t, :g] = remap(in1, n_in)
            out_t[t, 0, : outputs.shape[0]] = remap(outputs, n_in)
            gates[t] = g
        plan = _plan_table(op_t, in0_t, in1_t)
    device = jax.devices()[0]
    with obs.span("dispatch.h2d"):
        plan, out_t, gates_d = (jax.device_put(a, device)
                                for a in (plan, out_t, gates))
    return FleetTable(plan=plan, outputs=out_t, gates=gates_d,
                      row_inputs=tuple(p[4] for p in norm) + (0,),
                      row_gates=tuple(int(g) for g in gates),
                      n_inputs=n_in_max, words=int(words),
                      block_words=_word_tile(int(words)),
                      buckets=buckets or launch_buckets(N), device=device)


def _fleet_kernel(slots_ref, gates_ref, *refs, **kw):
    _kernel(*refs, walk=gates_ref[slots_ref[pl.program_id(0)]], **kw)


@partial(jax.jit, static_argnames=("n_inputs", "block_words", "interpret"))
def _fleet_walk(slots, gates, plan, outputs, words32, *, n_inputs: int,
                block_words: int, interpret: bool):
    """`slots` (T,) rows of the table `plan` (N, 6, G) / `outputs`
    (N, 1, n_out) / `gates` (N,), and `words32` (T, n_inputs, Wp) uint32,
    Wp a multiple of `block_words` -> (T, Wp*32) int32 decoded integers.
    The slots and gate counts are prefetched into SMEM; each grid row's
    plan block is its slot's row of the table."""
    G = plan.shape[2]
    n_out = outputs.shape[2]
    T, _, Wp = words32.shape
    bw = block_words
    words = jax.lax.bitcast_convert_type(words32, jnp.int32)
    with jax.named_scope(FLEET_WALK):
        out = pl.pallas_call(
            partial(_fleet_kernel, n_inputs=n_inputs, n_gates=G,
                    n_out=n_out, decode=True),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(T, Wp // bw),
                in_specs=[
                    pl.BlockSpec((1, 6, G), lambda p, w, s, g: (s[p], 0, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, 1, n_out),
                                 lambda p, w, s, g: (s[p], 0, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, n_inputs, bw),
                                 lambda p, w, s, g: (p, 0, w))],
                out_specs=pl.BlockSpec((1, 32, bw),
                                       lambda p, w, s, g: (p, 0, w)),
                scratch_shapes=[pltpu.VMEM((n_inputs + G, bw), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((T, 32, Wp), jnp.int32),
            interpret=interpret,
            name=FLEET_WALK,
        )(slots, gates, plan, outputs, words)
    return out.transpose(0, 2, 1).reshape(T, Wp * 32)


def fleet_walk(table: FleetTable, slots, words_list) -> list[np.ndarray]:
    """Evaluate the programs in `slots` (rows of `table`) over their
    `(n_inputs_t, W_t)` uint32 word planes in ONE launch, padded to the
    table's next launch shape.  Returns one `(W_t * 32,)` int32 array a
    slot, bit-identical to running each program through
    `fused_eval_uint` on its own."""
    slots = [int(s) for s in slots]
    if len(slots) != len(words_list):
        raise ValueError(f"{len(slots)} slots but {len(words_list)} word "
                         "planes")
    T = table.bucket(len(slots))
    Wp = table.padded_words
    with obs.span("dispatch.pack"):      # the launch's slots and planes
        slot_arr = np.full(T, table.empty_row, dtype=np.int32)
        slot_arr[: len(slots)] = slots
        words_t = np.zeros((T, table.n_inputs, Wp), dtype=np.uint32)
        widths = []
        for i, (s, w) in enumerate(zip(slots, words_list)):
            w = np.asarray(w, dtype=np.uint32)
            if (w.ndim != 2 or w.shape[0] != table.row_inputs[s]
                    or w.shape[1] > table.words):
                raise ValueError(f"slot {s}: word plane {w.shape} does not "
                                 f"fit ({table.row_inputs[s]}, <= "
                                 f"{table.words})")
            words_t[i, : w.shape[0], : w.shape[1]] = w
            widths.append(w.shape[1])
    with obs.span("dispatch.h2d"):
        slot_d, words_d = (jax.device_put(a, table.device)
                           for a in (slot_arr, words_t))
    with obs.span("dispatch.launch"):
        out = _fleet_walk(slot_d, table.gates, table.plan, table.outputs,
                          words_d, n_inputs=table.n_inputs,
                          block_words=table.block_words,
                          interpret=_interpret())
    with obs.span("dispatch.fetch"):
        out = np.asarray(out)
    return [out[i, : widths[i] * 32] for i in range(len(slots))]


def warm_fleet_table(table: FleetTable) -> float:
    """Compile every launch shape of `table` (pad slots, empty planes) and
    return the wall seconds of one warm launch at its largest shape."""
    dt = 0.0
    for b in table.buckets + (table.buckets[-1],):
        t0 = time.perf_counter()
        fleet_walk(table, [table.empty_row] * b,
                   [np.zeros((0, 0), np.uint32)] * b)
        dt = time.perf_counter() - t0
    return dt


def fleet_eval_words(plans, words_list) -> list[np.ndarray]:
    """Evaluate T single-program circuits over T word planes in ONE launch.

    `plans` is a list of `(op, in0, in1, outputs, n_inputs)` tuples —
    each a single program's plan (arrays may be `(G,)`/`(n_out,)` 1-D or
    `(1, G)`/`(1, n_out)` rows); `words_list` holds each tenant's packed
    `(n_inputs_t, W_t)` uint32 word plane.  The plans become a one-shot
    `FleetTable` and run as one `fleet_walk`.  Returns one `(W_t * 32,)`
    int32 array per tenant, bit-identical to running each plan through
    `fused_eval_uint` on its own.
    """
    if not plans:
        return []
    if len(plans) != len(words_list):
        raise ValueError(f"{len(plans)} plans but {len(words_list)} word "
                         "planes")
    words_list = [np.asarray(w, dtype=np.uint32) for w in words_list]
    for i, ((*_, n_in), w) in enumerate(zip(plans, words_list)):
        if w.ndim != 2 or w.shape[0] != n_in:
            raise ValueError(f"plan {i}: word plane {w.shape} does not "
                             f"match n_inputs={n_in}")
    W_max = max(w.shape[1] for w in words_list)
    if W_max == 0:
        return [np.zeros(0, dtype=np.int32) for _ in plans]
    table = fleet_table(plans, W_max, buckets=(len(plans),))
    return fleet_walk(table, range(len(plans)), words_list)
