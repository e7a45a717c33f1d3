"""Share of TNNApproxProblem.objective outside its device round trips, %."""
from harness.spans import objective_host_pct


def read(run: dict):
    return objective_host_pct(run)
