"""Share of the window inside TNNApproxProblem.objective, %."""
from harness.readers import objective_share_pct


def read(run: dict):
    return objective_share_pct(run)
