"""Put the harness and the system under test on the path."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


import pytest  # noqa: E402

# Phase 2 budgets, epochs and a pool of campaign seeds small enough for a
# test; the cell's shapes (11 inputs, 11 hidden, 7 classes, 1,714 scored
# readings, pop 24) are unchanged
SMALL_PHASE = {"seed": 0, "cgp_iters": 40, "cgp_points": 2,
               "pcc_samples": 2000}
SMALL_EPOCHS = 2
SMALL_POOL = {"campaign_seeds": [1, 2]}


def small(config: dict) -> dict:
    """A copy of a campaign configuration at test size."""
    import json

    config = json.loads(json.dumps(config))
    config["phase"] = dict(SMALL_PHASE)
    config["campaign"]["epochs"] = SMALL_EPOCHS
    return config


@pytest.fixture
def small_campaign(monkeypatch):
    """`whitewine_campaign` at test size: Phase 2 budgets, epochs, pool."""
    import run

    real = run.cell_plan

    def plan(bench, workload):
        p = real(bench, workload)
        if p["config"]["kind"] == "campaign":
            p["config"] = small(p["config"])
            p["traffic"] = dict(SMALL_POOL)
        return p

    monkeypatch.setattr(run, "cell_plan", plan)


@pytest.fixture(scope="session")
def campaign():
    """A set-up `CampaignCell` of `whitewine_campaign` at test size."""
    import json

    from harness import device as D
    from harness.campaign_cell import CampaignCell

    path = D.BENCH_DIR / "configs" / "whitewine_campaign.json"
    cell = CampaignCell(small(json.loads(path.read_text())))
    cell.setup()
    yield cell
    cell.close()
