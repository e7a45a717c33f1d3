"""A run whose timed path is broken underneath reports `correct: false`.

Each test stands in for the chip (`chipless`) and drives the rest of a
real run of the cell at a short window, with one fault planted in the
program where its answers are produced:

* an answer altered where it is produced (a serving label, a campaign
  score, a campaign area);
* half of a batch left out (the second half of every served batch comes
  back as class 0).

The cells have no training step whose state could stay unchanged, and
their replicas exchange nothing between chips, so those faults do not
apply.
"""
import numpy as np
import pytest

from chipless import chipless, run_cell


def test_sound_fleet_run_is_correct(monkeypatch, capsys):
    chipless(monkeypatch)
    line = run_cell(capsys, "table2_fleet.flood")
    assert line["correct"] is True
    assert line["compared"]["mismatched_labels"]["value"] == 0


@pytest.mark.parametrize("fault", ["altered_label", "half_batch"])
def test_broken_serving_path_is_caught(monkeypatch, capsys, fault):
    from repro.serve.engine import CircuitServingEngine

    chipless(monkeypatch)
    sound = CircuitServingEngine.classify_batch

    def broken(self, x):
        labels = np.array(sound(self, x))
        if fault == "altered_label":
            labels[0] = (labels[0] + 1) % self.program.n_classes
        else:
            labels[len(labels) // 2:] = 0
        return labels

    monkeypatch.setattr(CircuitServingEngine, "classify_batch", broken)
    line = run_cell(capsys, "table2_fleet.flood")
    assert line["correct"] is False
    assert line["compared"]["mismatched_labels"]["value"] > 0


def test_broken_campaign_score_is_caught(monkeypatch, capsys, small_campaign):
    from repro.kernels import dispatch

    chipless(monkeypatch)
    sound = dispatch.population_eval_pop

    def broken(pop, packed, **kw):
        out = np.array(sound(pop, packed, **kw))
        out[:, :64] = 0             # the first 64 readings' scores, lost
        return out

    monkeypatch.setattr(dispatch, "population_eval_pop", broken)
    line = run_cell(capsys, "whitewine_campaign.sweep", seconds=0.5)
    assert line["correct"] is False
    assert line["compared"]["error_gap"]["value"] > 0


def test_broken_campaign_area_is_caught(monkeypatch, capsys, small_campaign):
    from repro.core.tnn import TNNApproxProblem

    chipless(monkeypatch)
    sound = TNNApproxProblem.objective

    def broken(self, pop):
        out = np.array(sound(self, pop))
        out[:, 1] *= 1.0 + 1e-6     # every area a millionth too large
        return out

    monkeypatch.setattr(TNNApproxProblem, "objective", broken)
    line = run_cell(capsys, "whitewine_campaign.sweep", seconds=0.5)
    assert line["correct"] is False
    assert line["compared"]["area_rel_gap"]["value"] > 0
