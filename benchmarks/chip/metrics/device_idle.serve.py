"""Device: share of the traced window in which no operation ran on the
chip (mean over chips).  Moves ``tpot_ms``."""
from metrics.common import trace


def read(run):
    return None if run.trace is None else 100.0 * trace.idle_share(run.trace)
