"""Planner local tier: the word count's own bytes (read every id once,
write the count table once) at the chip's HBM bandwidth, over the device
time of the non-collective operations of each fold program, mean over
chips.  The same count whatever tier folds, so a kernel that replaces
segment-ops is judged on the same yardstick.  Moves ``records_per_s``."""
from metrics.common import counts, trace


def read(run):
    t = run.trace
    if t is None:
        return None
    need = counts.fold_bytes(run.config["split_ids"],
                             run.config["num_keys"]) \
        / run.peaks["hbm_bytes_per_s"]
    shares = []
    for d in t.devices:
        runs = trace.module_runs(d, "bench_fold", *t.window)
        took = trace.op_seconds_in(d, runs, collectives=False)
        if runs and took > 0:
            shares.append(100.0 * need * len(runs) / took)
    return sum(shares) / len(shares) if shares else None
