"""The campaign mix gives every seed the same work, in another order."""
import numpy as np

from conftest import SMALL_POOL


def _rows(w: dict) -> np.ndarray:
    pops = np.concatenate([pop for _, pop, _ in w["calls"]])
    return pops[np.lexsort(pops.T[::-1])]


def test_every_seed_runs_whole_passes_of_the_same_campaigns(campaign):
    # these two seeds draw the pool [1, 2] in opposite orders
    a = campaign.window(3000000021, 0.1, SMALL_POOL)
    b = campaign.window(3000000022, 0.1, SMALL_POOL)
    n = len(SMALL_POOL["campaign_seeds"])
    assert a["passes"] == b["passes"] == 1
    assert a["campaigns"] == b["campaigns"] == n
    assert a["genomes"] == b["genomes"]
    np.testing.assert_array_equal(_rows(a), _rows(b))
    assert not np.array_equal(a["calls"][0][1], b["calls"][0][1])
