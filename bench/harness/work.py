"""The work a run asked of the chip, counted from shapes and real readings.

These counts feed the kernel roofline and the whole-step share of peak.
They count only what the work itself needs, whatever program does it:

* a serving launch must bring in the bit-packed plane of its real readings
  (`n_inputs` bits each; a padded batch's pad is not work) and write one
  int32 label per reading.  A serving program is fixed, so its plan need
  not move per launch and is not counted.
* a campaign launch scores one output neuron for each genome of the call:
  the genome's plan (24 bytes a real gate: two int32 operand taps and four
  int32 gate masks, as the repo's `roofline/kernel_model.py` counts them,
  plus 4 bytes an output tap), its own input plane (`nnz` bits a reading)
  and one int32 score a reading.

No compute term: the gate walk runs bitwise integer operations on the
VPU, and the chip has no published peak for them, so the roofline is the
HBM bound alone.  The model's own arithmetic (two ternary operations per
weight and reading) sets the whole-step share against the int8 peak.
"""
from __future__ import annotations

PLAN_BYTES_PER_GATE = 4 + 4 + 4 * 4
WORD_BITS = 32


def serving_launch_bytes(n_inputs: int, n_readings: int) -> int:
    """Bytes one serving launch of `n_readings` real readings must move."""
    words = -(-n_readings // WORD_BITS)
    return n_inputs * words * 4 + n_readings * 4


def serving_window_bytes(n_inputs: int, n_readings: int) -> float:
    """Bytes of `n_readings` served over any number of launches; a partial
    word counts by its bits (the counters do not say how batches split)."""
    return n_inputs * n_readings / 8 + n_readings * 4


def campaign_launch_bytes(gates: int, n_outputs: int, nnz: int,
                          n_samples: int) -> float:
    """Bytes to score one genome's output neuron over `n_samples`."""
    return (gates * PLAN_BYTES_PER_GATE + n_outputs * 4
            + nnz * n_samples / 8 + n_samples * 4)


def tnn_ops_per_reading(topology) -> int:
    """Ternary multiply-adds of one forward pass, two operations each."""
    F, H, C = topology
    return 2 * (F * H + H * C)
