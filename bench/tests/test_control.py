"""The controls fail the comparison that decides `correct` (CPU size).

* Serving: the plain reference computed on bfloat16 readings and
  thresholds (the step below the float32 the configuration states), put
  in the program's place for a whole window at the cell's own load, gives
  mismatched labels on every seed; the program gives none.
* Campaign: the reference that scores only the readings filling whole
  32-bit words (it drops the last partial word, breaking the guarantee
  that all 1,714 readings are scored) and sums areas in float32 (the
  step below the float64 the configuration states) differs from the
  exact objectives on every seed, in both columns; the program does not.

On the chip the same readings come from `bench/limits.py` at the cells'
own sizes.
"""
import json

import pytest

from conftest import SMALL_POOL
from harness import device as D

SEEDS = (21, 22, 23)


def _config(name: str) -> dict:
    return json.loads((D.BENCH_DIR / "configs" / f"{name}.json").read_text())


def _traffic(name: str) -> dict:
    return json.loads((D.BENCH_DIR / "traffic" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def fleet():
    from harness.fleet_cell import FleetCell

    cell = FleetCell(_config("table2_fleet"))
    cell.setup()
    yield cell
    cell.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_control_fails_fleet(fleet, seed):
    flood = _traffic("flood")
    assert fleet.measure(seed, 1.0, flood, "bfloat16")["mismatched"] > 0
    assert fleet.measure(seed, 1.0, flood)["mismatched"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_campaign(campaign, seed):
    from harness.campaign_cell import AREA_LIMIT

    w = campaign.window(seed, 0.5, SMALL_POOL)
    bad = campaign.judge(w["calls"], seed, control=True)
    assert bad["error_gap"] > 0
    assert bad["area_rel_gap"] > AREA_LIMIT
    good = campaign.judge(w["calls"], seed)
    assert good["error_gap"] == 0
    assert good["area_rel_gap"] <= AREA_LIMIT
