"""round_s.p90: the 90th percentile of the wall time of every round in
the window (Python's ``statistics.quantiles``, inclusive)."""
import statistics


def read(run):
    if len(run.round_s) < 2:
        return run.round_s[0]
    return statistics.quantiles(run.round_s, n=10, method="inclusive")[-1]
