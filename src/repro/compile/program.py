"""Device backend — `CircuitProgram`: batched bit-packed circuit execution.

Executes a lowered `CircuitIR` for thousands of sensor readings per
dispatch.  Two interchangeable, bit-identical backends:

  * ``jax`` (default) — the jitted uint32-SWAR evaluator from
    `kernels.circuit_sim` (one `lax.scan` over levelized gate columns), the
    path the serving engine runs on;
  * ``np`` — the uint64 `Netlist.simulate` reference, used for
    cross-checking and as a dependency-free fallback.

Readings are packed 32/64-per-word along the batch axis, so one dispatch
costs O(n_gates * ceil(S/32)) word ops regardless of feature count or
class count.  For classifier programs (`from_classifier`) the circuit's
own argmax plane produces the class index — `predict` is end-to-end
(raw sensor floats -> ABC comparators -> gates -> label) and bit-identical
to `repro.core.tnn.predict_with_circuits`.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro import obs
from repro.core import circuits as C
from repro.compile.ir import CircuitIR, CompiledClassifier, lower_netlist


BACKENDS = ("jax", "np", "swar", "pallas")


@dataclass
class CircuitProgram:
    """An executable compiled circuit (optionally a full classifier).

    `backend` picks the executor: ``np`` is the uint64 `Netlist` reference;
    ``swar`` (alias ``jax``, the historical name) and ``pallas`` route
    through `kernels.dispatch.program_eval_words`, which runs each batch
    on `devices` (a 1-tuple; default: the default device).
    """

    ir: CircuitIR
    thresholds: np.ndarray | None = None   # (F,) ABC V_q — classifier only
    n_classes: int | None = None
    backend: str = "jax"
    devices: tuple | None = None
    _netlist: C.Netlist | None = field(default=None, repr=False)
    _jax_plan: tuple | None = field(default=None, repr=False)

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"valid: {', '.join(BACKENDS)}")
        if self.backend != "np":
            # plan arrays are P=1 population rows for kernels.circuit_sim
            self._jax_plan = (
                self.ir.op.astype(np.int32)[None],
                self.ir.in0.astype(np.int32)[None],
                self.ir.in1.astype(np.int32)[None],
                self.ir.outputs.astype(np.int32)[None],
            )
        else:
            self._netlist = self.ir.to_netlist()

    # -- construction -------------------------------------------------------
    @classmethod
    def from_netlist(cls, nl: C.Netlist, backend: str = "jax",
                     devices: tuple | None = None, **kw) -> "CircuitProgram":
        """Compile a bare netlist (DCE + levelize) into a program."""
        return cls(ir=lower_netlist(nl), backend=backend, devices=devices,
                   **kw)

    @classmethod
    def from_classifier(cls, cc: CompiledClassifier, backend: str = "jax",
                        devices: tuple | None = None,
                        **kw) -> "CircuitProgram":
        return cls(ir=cc.ir, thresholds=cc.thresholds,
                   n_classes=cc.n_classes, backend=backend, devices=devices,
                   **kw)

    # -- plan access ---------------------------------------------------------
    def plan(self) -> tuple:
        """`(op, in0, in1, outputs, n_inputs)` flat plan arrays — the tuple
        `kernels.dispatch.fleet_eval_words` eats, so a serving fleet can
        pool many programs into one multi-tenant megakernel launch."""
        return (self.ir.op.astype(np.int16), self.ir.in0.astype(np.int32),
                self.ir.in1.astype(np.int32),
                self.ir.outputs.astype(np.int32), self.ir.n_inputs)

    def pack_input_bits(self, xbin: np.ndarray) -> np.ndarray:
        """Binarized readings `(S, F)` -> packed `(F, ceil(S/32))` uint32
        words (the megakernel's word-plane layout)."""
        from repro.kernels import circuit_sim as CS
        with obs.span("dispatch.pack"):
            return np.asarray(CS.pack_bits32(np.asarray(xbin)),
                              dtype=np.uint32)

    def binarize(self, x: np.ndarray) -> np.ndarray:
        """Raw readings `(S, F)` -> 0/1 uint8 via the compiled ABC
        thresholds (strict `>`, same as `predict`)."""
        if self.thresholds is None:
            raise ValueError("program has no ABC thresholds")
        return (np.asarray(x) > self.thresholds[None, :]).astype(np.uint8)

    # -- execution ----------------------------------------------------------
    def eval_uint(self, packed_u64: np.ndarray) -> np.ndarray:
        """`(n_inputs, W)` uint64 packed vectors -> `(W*64,)` int64 decoded
        outputs (LSB-first), bit-identical to `Netlist.eval_uint`."""
        if self.backend == "np":
            return self._netlist.eval_uint(packed_u64)
        from repro.kernels import circuit_sim as CS
        return self._eval_words32(CS.pack_words32(packed_u64))

    def eval_bits(self, bits: np.ndarray) -> np.ndarray:
        """`(S, n_inputs)` 0/1 matrix -> `(S,)` int64 decoded outputs."""
        S = bits.shape[0]
        if self.backend == "np":
            with obs.span("dispatch.pack"):
                packed = C.pack_vectors(bits)
            return self._netlist.eval_uint(packed)[:S]
        from repro.kernels import circuit_sim as CS
        with obs.span("dispatch.pack"):
            words32 = CS.pack_bits32(bits)
        return self._eval_words32(words32)[:S]

    def _eval_words32(self, words32: np.ndarray) -> np.ndarray:
        from repro.kernels import dispatch as D
        op, in0, in1, outs = self._jax_plan
        exec_backend = "swar" if self.backend == "jax" else self.backend
        out = D.program_eval_words(op, in0, in1, outs, words32,
                                   self.ir.n_inputs, backend=exec_backend,
                                   devices=self.devices)
        return np.asarray(out[0], dtype=np.int64)

    # -- classifier inference ----------------------------------------------
    def predict_bits(self, xbin: np.ndarray) -> np.ndarray:
        """Binarized readings `(S, F)` -> class labels `(S,)` int32."""
        if self.n_classes is None:
            raise ValueError("not a classifier program")
        return self.eval_bits(xbin).astype(np.int32)

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Raw sensor readings `(S, F)` float -> class labels `(S,)` int32.

        Applies the compiled ABC thresholds (strict `>` comparators, same
        as `ternary.abc_binarize`) before the gate plane.
        """
        with obs.span("dispatch.binarize"):
            xbin = self.binarize(x)
        return self.predict_bits(xbin)

    def scores(self, xbin: np.ndarray) -> np.ndarray:
        """Per-class XNOR-match scores `(S, C)` from the score tap plane."""
        if "score" not in self.ir.taps:
            raise ValueError("program has no score taps")
        tap = self.ir.taps["score"]              # (C, j)
        Cc, j = tap.shape
        S = xbin.shape[0]
        nl = self.ir.to_netlist(outputs=tap.reshape(-1))
        words = nl.simulate(C.pack_vectors(xbin))        # (C*j, W)
        ints = C._decode_words(words.reshape(Cc, j, -1))  # (C, W*64)
        return ints[:, :S].T                              # (S, C)
