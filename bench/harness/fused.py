"""Per-layer metrics of the fused megakernel launch, from the program's
counters (`repro.obs`, recorded by `ClassifierFleet._serve_fused`).

One entry a fused launch: `fleet.fused` (n 1), `fleet.fused.tenants`
(n = the tenants it carried), `fleet.fused.gates_real` and
`fleet.fused.gates_walked` (n = gate steps, one a gate and a 32-reading
word: real over the words holding readings, walked over every word the
kernel walked).  The table is the cell's window (`spans.window_table`).
A program without these counters gives no number, and the metric is left
out of the line.
"""
from __future__ import annotations

from harness.spans import window_table


def _counts(run: dict, names: tuple):
    table = window_table(run)
    if not table or not all(k in table for k in names):
        return None
    return [table[k]["n"] for k in names]


def fused_tenants(run: dict):
    """Mean tenants a fused launch carried in the window."""
    got = _counts(run, ("fleet.fused", "fleet.fused.tenants"))
    if got is None or got[0] <= 0:
        return None
    return got[1] / got[0]


def fused_pad_pct(run: dict):
    """Share of the gate steps the fused launches walked that were
    padding (no reading behind them), %."""
    got = _counts(run, ("fleet.fused.gates_real", "fleet.fused.gates_walked"))
    if got is None or got[1] <= 0:
        return None
    return (1.0 - got[0] / got[1]) * 100.0
