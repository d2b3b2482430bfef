"""Percent of the window's device time under no ``gbdt.*`` scope: the coverage
guard of the ``gbdt.*_ms_per_iter`` metrics, which explain an iteration only
while this is small (``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.unscoped_share(run)
