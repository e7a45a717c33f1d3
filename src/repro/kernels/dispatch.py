"""Backend dispatch + device sharding for population circuit simulation.

One entry point, three interchangeable bit-identical executors for the
population x packed-word gate-simulation hot loop:

  * ``np``     — `NetlistPopulation` structure-of-arrays uint64 simulation
    (host reference);
  * ``swar``   — the jitted `lax.scan` uint32-SWAR twin in
    `kernels.circuit_sim` (the PR 1 device path / benchmark baseline);
  * ``pallas`` — the Pallas kernel in `kernels.pallas_circuit_sim`
    (compiled on a TPU, interpret mode on the CPU).

Device placement: for a population the rows are split round-even across
`jax.local_devices()` (or an explicit device list) — fitness rows are
independent, so each device simulates its slice of genomes against the
(shared or per-individual) word plane and results concatenate on host.
A serving dispatch (`program_eval_words`) is one program over one batch
and runs on ONE device: the replica's pinned device, else the default
device.  Its word axis is never split — a 256-reading batch is 8 words,
and cutting it into per-chip shards would only add launches and host
syncs; a fleet spreads load over chips with replicas instead.

This lives in `kernels` (not `repro.evolve`) so consumers below the
orchestration layer — e.g. `core.tnn.TNNApproxProblem` — can select a
backend without importing upward; `repro.evolve.evaluator` re-exports it
as the campaign-facing API.
"""
from __future__ import annotations

import numpy as np

from repro import obs
from repro.core.circuits import NetlistPopulation

BACKENDS = ("np", "swar", "pallas")


def replica_devices(index: int, devices=None) -> tuple:
    """Round-robin device pin for serving-engine replica `index`.

    A fleet tenant running N engine replicas wants replica i's dispatches
    resident on local device ``i % n_devices`` so hot-tenant batches
    overlap across chips instead of queueing on one; the returned 1-tuple
    plugs straight into `CircuitProgram(devices=...)`, whose
    `program_eval_words` treats any explicit device list as a pinning
    request: the replica's whole batch runs on that device.  With one
    device every replica pins to it; on a four-chip host replicas 0-3
    land on chips 0-3.
    """
    if index < 0:
        raise ValueError("replica index must be >= 0")
    import jax

    devs = list(devices) if devices is not None else jax.local_devices()
    if not devs:
        raise ValueError("no devices to pin replicas to")
    return (devs[index % len(devs)],)


def _device_slices(P: int, n_dev: int) -> list[slice]:
    """Round-even contiguous row slices, one per device (empty ones drop)."""
    per = -(-P // n_dev)
    return [slice(s, min(s + per, P)) for s in range(0, P, per)]


def _device_eval_fn(backend: str):
    """The device executor of `backend`.  The Pallas one opens its own
    plan, h2d and launch spans; the SWAR scan is one jitted launch."""
    if backend == "pallas":
        from repro.kernels import pallas_circuit_sim as PS
        return PS.population_eval_uint
    from repro.kernels import circuit_sim as CS

    def launch(*args):
        with obs.span("dispatch.launch"):
            return CS.population_eval_uint(*args)
    return launch


def _eval_device(op, in0, in1, outputs, packed_u64, n_inputs, backend,
                 devices) -> np.ndarray:
    import jax

    from repro.kernels import circuit_sim as CS
    eval_fn = _device_eval_fn(backend)
    with obs.span("dispatch.pack"):
        words32 = CS.pack_words32(packed_u64)
    per_individual = words32.ndim == 3
    devices = list(devices) if devices is not None else jax.local_devices()
    P = op.shape[0]
    slices = (_device_slices(P, len(devices)) if len(devices) > 1
              else [slice(0, P)])
    outs = []
    for sl, dev in zip(slices, devices):
        shard = (op[sl], in0[sl], in1[sl], outputs[sl],
                 words32[sl] if per_individual else words32)
        if len(slices) > 1:
            with obs.span("dispatch.h2d"):
                shard = tuple(jax.device_put(a, dev) for a in shard)
        out = eval_fn(*shard, n_inputs)
        with obs.span("dispatch.fetch"):
            outs.append(np.asarray(out))
    return np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def population_eval_uint(op: np.ndarray, in0: np.ndarray, in1: np.ndarray,
                         outputs: np.ndarray, packed_u64: np.ndarray,
                         n_inputs: int, backend: str = "swar",
                         devices=None) -> np.ndarray:
    """Per-vector decoded outputs `(P, S)` for a population of netlists.

    `packed_u64` is `(n_inputs, W)` shared or `(P, n_inputs, W)`
    per-individual uint64 words; every backend returns the same integers
    for the same words (rows are `Netlist.eval_uint` of the row's genome).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown eval backend {backend!r}; "
                         f"valid: {', '.join(BACKENDS)}")
    if backend == "np":
        pop = NetlistPopulation(n_inputs, np.asarray(op, dtype=np.int16),
                                np.asarray(in0, dtype=np.int32),
                                np.asarray(in1, dtype=np.int32),
                                np.asarray(outputs, dtype=np.int32))
        return pop.eval_uint(packed_u64)
    op32 = np.asarray(op, dtype=np.int32)
    return _eval_device(op32, np.asarray(in0, dtype=np.int32),
                        np.asarray(in1, dtype=np.int32),
                        np.asarray(outputs, dtype=np.int32),
                        packed_u64, n_inputs, backend,
                        devices).astype(np.int64)


def population_eval_pop(pop: NetlistPopulation, packed_u64: np.ndarray,
                        backend: str = "swar",
                        devices=None) -> np.ndarray:
    """`population_eval_uint` over an existing `NetlistPopulation`."""
    return population_eval_uint(pop.op, pop.in0, pop.in1, pop.outputs,
                                packed_u64, pop.n_inputs, backend=backend,
                                devices=devices)


def program_eval_words(op: np.ndarray, in0: np.ndarray, in1: np.ndarray,
                       outputs: np.ndarray, words32: np.ndarray,
                       n_inputs: int, backend: str = "swar",
                       devices=None) -> np.ndarray:
    """Single-program serving dispatch: `(n_inputs, W)` uint32 words ->
    `(P, W*32)` int64 decoded outputs, on any backend.

    The device backends run the whole batch on one device: `devices`, a
    1-tuple, pins it there (a fleet replica's device); None leaves it on
    the default device.  `repro.serve` pins each fleet tenant's
    dispatches through this entry point, so a tenant maps to
    `np`/`swar`/`pallas` exactly like a campaign evaluator does.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown eval backend {backend!r}; "
                         f"valid: {', '.join(BACKENDS)}")
    op = np.asarray(op)
    words32 = np.ascontiguousarray(words32, dtype=np.uint32)
    if words32.ndim != 2:
        raise ValueError("program_eval_words wants a shared (n_inputs, W) "
                         "word plane")
    if backend == "np":
        # repack the uint32 lanes as the uint64 words the reference eats —
        # the inverse of pack_words32, whose contract is that lane 2k holds
        # the LOW 32 bits of word k and lane 2k+1 the high 32.  A
        # `.view(np.uint64)` only honours that on little-endian hosts, so
        # combine the lanes arithmetically instead of reinterpreting bytes.
        W32 = words32.shape[1]
        if W32 % 2:
            words32 = np.concatenate(
                [words32, np.zeros((words32.shape[0], 1), np.uint32)], axis=1)
        lo = words32[:, 0::2].astype(np.uint64)
        hi = words32[:, 1::2].astype(np.uint64)
        packed_u64 = np.ascontiguousarray(lo | (hi << np.uint64(32)))
        pop = NetlistPopulation(n_inputs, np.asarray(op, dtype=np.int16),
                                np.asarray(in0, dtype=np.int32),
                                np.asarray(in1, dtype=np.int32),
                                np.asarray(outputs, dtype=np.int32))
        return pop.eval_uint(packed_u64)[:, : W32 * 32]

    import jax

    eval_fn = _device_eval_fn(backend)
    plan = (np.asarray(op, dtype=np.int32), np.asarray(in0, dtype=np.int32),
            np.asarray(in1, dtype=np.int32),
            np.asarray(outputs, dtype=np.int32))
    if devices is not None:
        devices = tuple(devices)
        if len(devices) != 1:
            raise ValueError(f"a serving dispatch runs on one device; got "
                             f"{len(devices)}")
        with obs.span("dispatch.h2d"):
            words32 = jax.device_put(words32, devices[0])
    out = eval_fn(*plan, words32, n_inputs)
    with obs.span("dispatch.fetch"):
        return np.asarray(out).astype(np.int64)


def fleet_eval_words(plans: list, words_list: list,
                     backend: str = "pallas") -> list[np.ndarray]:
    """Whole-manifest serving dispatch: T tenants' circuits in ONE launch.

    `plans` holds one `(op, in0, in1, outputs, n_inputs)` plan tuple per
    tenant (P=1 rows or flat 1-D arrays both accepted) and `words_list`
    the matching `(n_inputs_t, W_t)` uint32 word planes.  On the
    ``pallas`` backend this pads every tenant's gate-op/ANF-mask tables
    to a common gate budget and runs the multi-program megakernel —
    grid over (tenant x word-tile), one `pallas_call` for the manifest.
    ``np``/``swar`` fall back to per-tenant `program_eval_words` loops
    (same answers, T launches), so callers can flip backends freely.
    The launch runs on the default device.

    Returns one `(W_t * 32,)` int64 decoded-label array per tenant,
    bit-identical to dispatching each tenant through
    `program_eval_words` on its own.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown eval backend {backend!r}; "
                         f"valid: {', '.join(BACKENDS)}")
    if backend == "pallas":
        from repro.kernels import pallas_circuit_sim as PS
        outs = PS.fleet_eval_words(plans, words_list)
        return [np.asarray(o, dtype=np.int64) for o in outs]
    outs = []
    for (op, in0, in1, outputs, n_in), w in zip(plans, words_list):
        out = program_eval_words(
            np.asarray(op).reshape(1, -1), np.asarray(in0).reshape(1, -1),
            np.asarray(in1).reshape(1, -1),
            np.asarray(outputs).reshape(1, -1), w, n_in, backend=backend)
        outs.append(np.asarray(out[0], dtype=np.int64))
    return outs


def population_pc_errors(pop: NetlistPopulation, packed_u64: np.ndarray,
                         true: np.ndarray, backend: str = "swar",
                         devices=None) -> tuple[np.ndarray, np.ndarray]:
    """Per-individual (mae, wcae) against true counts, any backend."""
    if backend == "np":
        return pop.pc_errors(packed_u64, true)
    approx = population_eval_pop(pop, packed_u64, backend=backend,
                                 devices=devices)
    err = np.abs(approx - np.asarray(true)[None, :])
    return err.mean(axis=1), err.max(axis=1).astype(np.float64)
