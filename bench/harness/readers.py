"""Per-layer metrics from what a traced run collected.

Each `bench/metrics/<name>.py` calls one of these.  `run` holds the
layer record of the driver (`layer`: the fleet's counter changes or the
campaign's objective calls, with the bytes and model operations of the
window and the window's host seconds), the reduced trace (`trace`), the
chip's published peaks (`peaks`) and the number of chips.  A reader
that finds nothing to read returns None, and the metric is left out of
the line.
"""
from __future__ import annotations


def dispatch_ms(run: dict):
    """Mean host seconds of one fleet dispatch (binarize, pack, launch,
    fetch), from the fleet's own counters over the window, in ms."""
    fleet = run["layer"].get("fleet")
    if not fleet or fleet["n_batches"] <= 0:
        return None
    return fleet["busy_s"] / fleet["n_batches"] * 1e3


def circuit_roofline(run: dict):
    """Share of the HBM roofline: the least time the window's necessary
    bytes take at the chips' peak bandwidth, over the device busy time."""
    busy = run["trace"]["busy_s"] * run["n_chips"]
    byt = run["layer"].get("bytes", 0.0)
    if busy <= 0 or byt <= 0:
        return None
    return byt / run["peaks"]["hbm_bytes_per_s"] / busy * 100.0


def device_idle_pct(run: dict):
    tr = run["trace"]
    if tr["window_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0


def objective_share_pct(run: dict):
    """Share of the window spent inside the campaign's objective calls."""
    t = run["layer"].get("objective_s")
    if not t:
        return None
    return t / run["layer"]["window_s"] * 100.0


def step_mfu(run: dict):
    """The model's own ternary operations completed in the window, over
    the chips' int8 peak for the traced window's length."""
    ops = run["layer"].get("model_ops", 0)
    w = run["trace"]["window_s"]
    if ops <= 0 or w <= 0:
        return None
    return ops / (w * run["n_chips"] * run["peaks"]["int8_ops_per_s"]) * 100.0
