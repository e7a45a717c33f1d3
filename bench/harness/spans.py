"""Per-layer metrics from the program's own spans and counters.

The program (`repro.obs`) times every layer boundary of a fleet dispatch,
a served frame and an objective call into a table of `{name: {"n":
count, "s": seconds}}`.  A traced run reads the window's part of it:
the table a cell (`harness/<kind>_cell.py`) put in `layer["spans"]`, or
else the program's table of what it recorded while the profiler was
tracing (`repro.obs.snapshot(traced=True)`, read in the benchmark's own
process), which is the traced window: the profiler runs only around it.
A program without `repro.obs` gives no table, and a table without the
spans a metric reads gives no number: the metric is left out of the
line.
"""
from __future__ import annotations

TRANSPORT = ("serve.frame.decode", "serve.frame.admit", "serve.write")
HOST_PREP = ("dispatch.gather", "dispatch.binarize", "dispatch.pack",
             "dispatch.plan")
DEVICE_ROUNDTRIP = ("dispatch.h2d", "dispatch.launch", "dispatch.fetch")


def window_table(run: dict) -> dict | None:
    spans = run["layer"].get("spans")
    if spans is not None:
        return spans
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot(traced=True)


def per_event_ms(table: dict | None, names: tuple, per: str):
    """Seconds of the spans `names`, over the count of `per`, in ms."""
    if not table or not any(k in table for k in names):
        return None
    n = table.get(per, {}).get("n", 0)
    if n <= 0:
        return None
    return sum(table[k]["s"] for k in names if k in table) / n * 1e3


def transport_ms(run: dict):
    """Decode, admission and result writing, per frame received."""
    return per_event_ms(window_table(run), TRANSPORT, "serve.frame.decode")


def queue_wait_ms(run: dict):
    """Oldest request's wait from submit to dispatch start, per batch."""
    return per_event_ms(window_table(run), ("fleet.queue_wait",),
                        "fleet.queue_wait")


def host_prep_ms(run: dict):
    """Gather, binarize, pack and plan table, per fleet dispatch."""
    return per_event_ms(window_table(run), HOST_PREP, "fleet.dispatch")


def device_roundtrip_ms(run: dict):
    """Transfers in, launch, and the wait for the result, per dispatch."""
    return per_event_ms(window_table(run), DEVICE_ROUNDTRIP, "fleet.dispatch")


def complete_ms(run: dict):
    """Labels to requests, stats and completion callbacks, per batch."""
    return per_event_ms(window_table(run), ("fleet.complete",),
                        "fleet.complete")


def objective_host_pct(run: dict):
    """Share of the objective's time outside its device round trips."""
    table = window_table(run)
    if not table or "tnn.objective" not in table:
        return None
    total = table["tnn.objective"]["s"]
    if total <= 0:
        return None
    device = table.get("tnn.objective.eval", {}).get("s", 0.0)
    return (total - device) / total * 100.0
