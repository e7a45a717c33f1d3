"""The zoo cells' plain reference: each tenant's seeded classifier, found
from its name.

A zoo tenant is named `<dataset>_v<k>`.  Draw 0 is
`reference.seeded_weights(dataset)`, the `table2_fleet` tenant; draw
k >= 1 is the same construction (ternarized normal weights, output
columns zero-balanced, ABC thresholds the medians of the dataset's
training readings) from seed tag `golden:<dataset>:<k>`.  A tenant's
readings are its dataset's (`reference.make_dataset`), and its labels come
from the plain numpy `reference.tnn_labels` on its own weights, so the
bfloat16 control applies unchanged.

The module offers the three names the load generator takes from its
reference (`make_dataset`, `seeded_weights`, `tnn_labels`), each taking a
tenant name where `reference` takes a dataset name.  Nothing here imports
the program.
"""
from __future__ import annotations

import re

import numpy as np

from harness import reference as R

TENANT = re.compile(r"^(?P<dataset>[a-z_]+)_v(?P<k>\d+)$")


def split(tenant: str) -> tuple[str, int]:
    """`<dataset>_v<k>` -> (dataset, k)."""
    m = TENANT.match(tenant)
    if m is None or m["dataset"] not in R.SPECS:
        raise KeyError(f"not a zoo tenant: {tenant!r}")
    return m["dataset"], int(m["k"])


def variant_weights(dataset: str, k: int) -> R.Ternary:
    """Draw `k` of `dataset`'s seeded classifier."""
    if k == 0:
        return R.seeded_weights(dataset)
    F, H, C = R.SPECS[dataset][6]
    rng = R._rng(f"golden:{dataset}:{k}")
    w1 = R._ternarize(rng.normal(0.0, 0.7, size=(F, H)))
    w2 = R._balance_zero_counts(rng.normal(0.0, 0.7, size=(H, C)))
    thresholds = np.median(R.make_dataset(dataset).x_train, axis=0)
    return R.Ternary(w1=w1, w2=w2, thresholds=thresholds)


def seeded_weights(tenant: str) -> R.Ternary:
    return variant_weights(*split(tenant))


def make_dataset(tenant: str, seed: int = 0) -> R.Dataset:
    return R.make_dataset(split(tenant)[0], seed)


tnn_labels = R.tnn_labels
