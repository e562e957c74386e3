"""The benchmark's cells at sizes a test run holds: the configurations'
shapes, with fewer grid points and trials."""
from bench import run as harness

#: cell -> the configuration keys that the tests shrink.
TINY = {
    "fig2-exascale-weibull07.sweep": dict(
        mu_minutes=[60.0, 300.0], rho=[2.0, 8.0], trials_per_point=16),
    "fig5-robustness-exascale55.crn-solve": dict(trials_per_point=16),
}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.Cell(name)
    cell.config = dict(cell.config, **TINY[name])
    return cell
