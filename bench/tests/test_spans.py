"""The readers of the program's spans (`harness/spans.py`).

On a synthetic layer record each of the six metrics is the arithmetic
its definition states; without a span table, as on a checkout whose
program has no `repro.obs`, every one of them is None; and a traced run
without a chip reads all five flood metrics from the program's own
table.
"""
import sys

import pytest

from harness import spans as S

TABLE = {
    "serve.frame.decode": {"n": 10, "s": 0.010},
    "serve.frame.admit": {"n": 8, "s": 0.020},
    "serve.write": {"n": 12, "s": 0.030},
    "fleet.queue_wait": {"n": 4, "s": 0.008},
    "fleet.dispatch": {"n": 4, "s": 0.100},
    "dispatch.gather": {"n": 4, "s": 0.001},
    "dispatch.binarize": {"n": 4, "s": 0.002},
    "dispatch.pack": {"n": 4, "s": 0.003},
    "dispatch.plan": {"n": 4, "s": 0.004},
    "dispatch.h2d": {"n": 8, "s": 0.010},
    "dispatch.launch": {"n": 4, "s": 0.020},
    "dispatch.fetch": {"n": 4, "s": 0.040},
    "fleet.complete": {"n": 4, "s": 0.012},
    "tnn.objective": {"n": 2, "s": 0.5},
    "tnn.objective.eval": {"n": 14, "s": 0.1},
}
WANT = {
    "transport_ms": (0.010 + 0.020 + 0.030) / 10 * 1e3,
    "queue_wait_ms": 0.008 / 4 * 1e3,
    "host_prep_ms": (0.001 + 0.002 + 0.003 + 0.004) / 4 * 1e3,
    "device_roundtrip_ms": (0.010 + 0.020 + 0.040) / 4 * 1e3,
    "complete_ms": 0.012 / 4 * 1e3,
    "objective_host_pct": (0.5 - 0.1) / 0.5 * 100.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_synthetic_layer_record(name):
    run = {"layer": {"spans": TABLE, "window_s": 1.0}}
    assert getattr(S, name)(run) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_without_spans_reads_none(monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.obs", None)    # no such module
    assert getattr(S, name)({"layer": {"window_s": 1.0}}) is None
    assert getattr(S, name)({"layer": {"spans": {}}}) is None


def test_readers_need_their_own_spans():
    campaign = {"layer": {"spans": {k: v for k, v in TABLE.items()
                                    if k.startswith("tnn.")}}}
    for name in sorted(WANT):
        got = getattr(S, name)(campaign)
        assert (got is not None) == (name == "objective_host_pct"), name


def test_traced_flood_run_reads_the_program_spans(monkeypatch, capsys):
    """With `repro.obs` the five flood metrics are in the line; a program
    without it runs the same traced cell and leaves them out."""
    import importlib.util

    from chipless import chipless, run_cell

    chipless(monkeypatch)
    line = run_cell(capsys, "table2_fleet.flood", trace=1)
    assert line["correct"]
    got = line["metrics"]
    has_spans = importlib.util.find_spec("repro.obs") is not None
    for name in ("transport_ms", "queue_wait_ms", "host_prep_ms",
                 "device_roundtrip_ms", "complete_ms"):
        if has_spans:
            assert got[f"{name}.flood"]["value"] > 0, name
            assert got[f"{name}.flood"]["unit"] == "ms"
        else:
            assert f"{name}.flood" not in got, name
