"""Collectives: share of the traced window in which a collective ran on a
chip and no other operation did, mean over chips.  Moves
``records_per_s``."""
from metrics.common import trace


def read(run):
    if run.trace is None:
        return None
    return 100.0 * trace.collective_exposed_share(run.trace)
