"""Benchmark suite entry point. One section per paper artifact/table.

Prints ``name,us_per_call,derived`` CSV rows. The roofline table (the per-
(arch x shape x mesh) structural numbers) is rendered separately by
``python -m benchmarks.roofline`` from the dry-run JSONs.

``--quick`` runs only the fast algorithm/aggregation/sketch sections (the
CI bench-smoke job); ``--serve`` runs only the batched serving section (the
CI serve-smoke job, interpret mode on CPU); ``--json PATH`` additionally
writes every row to a ``BENCH_*.json`` artifact so the perf trajectory
accumulates per commit; ``--compare OLD_JSON`` diffs the fresh run against
a previous artifact and exits non-zero on a >20% throughput regression in
the guarded hot rows (``segment_fold``/``mean_by_key`` — the planner's
kernel tier — and the ``serve_`` decode/fold rows).  A missing baseline is
skipped with a warning, or is an error under ``--require-baseline`` (CI on
main: the trajectory must never silently restart).
"""
import argparse
import json
import platform
import sys

from repro.launch.compile_cache import use_compile_cache

from . import (bench_aggregation, bench_kernels, bench_mapreduce,
               bench_overlap, bench_plan, bench_prefix, bench_serve,
               bench_sketches, bench_train, bench_windows)
from . import common

# rows guarded by --compare: the planner-lowered hot paths + the serve tier
# + the overlap section's step rows + the windowed-streaming event rates
# + the prefix-cache serving rows
GUARDED_PREFIXES = ("segment_fold", "mean_by_key", "plan_auto", "serve_",
                    "overlap_step", "window_events", "prefix_")
# guarded rows where BIGGER is better (hit rates, bytes saved): the compare
# gate inverts — fail when the new value drops below old / tolerance
HIGHER_IS_BETTER = ("prefix_hit_rate", "prefix_bytes_saved")
REGRESSION_TOLERANCE = 1.20   # fail on >20% slower than the previous artifact
# intra-run gate for the prefix-cache section: warm TTFT p50 must be at
# most this fraction of cold at >= 50% shared-prefix traffic — reusing
# cached KV rows that does not cut time-to-first-token is dead weight
PREFIX_TOLERANCE = 0.60
# intra-run gate: layout='auto' must stay within this factor of the BEST
# forced layout for the same case — the cost model may not mis-place a fold
AUTO_TOLERANCE = 1.50
# intra-run gate for the overlap section: the sync-vs-async argmin must not
# cost more than timing noise over always-sync (on hardware where the DCN
# crossing cannot actually hide, auto has to keep choosing sync)
OVERLAP_TOLERANCE = 1.10


def compare_rows(new_rows, old_rows, *, tolerance: float = REGRESSION_TOLERANCE):
    """Return [(name, old_us, new_us), ...] for guarded rows that regressed."""
    old = {r["name"]: float(r["us_per_call"]) for r in old_rows
           if isinstance(r, dict) and "name" in r and "us_per_call" in r}
    regressions = []
    for r in new_rows:
        name = r["name"]
        if not any(name.startswith(p) for p in GUARDED_PREFIXES):
            continue
        if name not in old or old[name] <= 0:
            continue
        new_us = float(r["us_per_call"])
        if any(name.startswith(p) for p in HIGHER_IS_BETTER):
            if new_us < old[name] / tolerance:
                regressions.append((name, old[name], new_us))
        elif new_us > old[name] * tolerance:
            regressions.append((name, old[name], new_us))
    return regressions


def check_auto_rows(rows, *, tolerance: float = AUTO_TOLERANCE):
    """Gate the planner's auto decisions against the forced layouts.

    For each ``plan_auto/<case>`` row, find the fastest
    ``plan_forced/<case>/<layout>`` row from the SAME run; auto slower than
    ``tolerance x best`` means the cost model chose a losing tier.  Returns
    [(case, auto_us, best_layout, best_us), ...] violations.
    """
    auto, forced = {}, {}
    for r in rows:
        name = str(r.get("name", ""))
        us = float(r.get("us_per_call", 0.0))
        if name.startswith("plan_auto/"):
            auto[name.split("/", 1)[1]] = us
        elif name.startswith("plan_forced/"):
            _, case, layout = name.split("/", 2)
            forced.setdefault(case, []).append((layout, us))
    violations = []
    for case, auto_us in auto.items():
        if not forced.get(case):
            continue
        best_layout, best_us = min(forced[case], key=lambda t: t[1])
        if best_us > 0 and auto_us > best_us * tolerance:
            violations.append((case, auto_us, best_layout, best_us))
    return violations


def check_overlap_rows(rows, *, tolerance: float = OVERLAP_TOLERANCE):
    """Gate the overlap section against itself (no baseline needed).

    * ``overlap_step_us/auto`` must stay within ``tolerance x`` the measured
      ``overlap_step_us/sync_dense`` — the planner's sync-vs-async argmin
      may not buy overlap the hardware does not deliver.
    * ``overlap_bytes/lossy`` must be strictly below ``overlap_bytes/dense``
      — a lossy annotation that does not shrink the DCN crossing is a bug.

    Returns a list of human-readable violation strings; empty when the
    section did not run (no 8-device mesh) or everything held.
    """
    vals = {str(r.get("name", "")): float(r.get("us_per_call", 0.0))
            for r in rows}
    violations = []
    auto = vals.get("overlap_step_us/auto")
    sync = vals.get("overlap_step_us/sync_dense")
    if auto is not None and sync is not None and sync > 0 \
            and auto > sync * tolerance:
        violations.append(
            f"overlap_step_us/auto {auto:.1f}us > {tolerance:.2f}x "
            f"sync_dense {sync:.1f}us ({auto / sync:.2f}x): the planner "
            "bought overlap that is not there")
    dense = vals.get("overlap_bytes/dense")
    lossy = vals.get("overlap_bytes/lossy")
    if dense is not None and lossy is not None and lossy >= dense:
        violations.append(
            f"overlap_bytes/lossy {lossy:.0f}B >= dense {dense:.0f}B: "
            "the lossy annotation moved no fewer bytes than the dense "
            "crossing")
    return violations


def check_prefix_rows(rows, *, tolerance: float = PREFIX_TOLERANCE):
    """Gate the prefix-cache section against itself (no baseline needed).

    * ``prefix_ttft_p50/warm`` must be <= ``tolerance x`` the measured
      ``prefix_ttft_p50/cold`` from the SAME run — the trace carries >= 50%
      shared-prefix traffic, so a prefix cache that does not cut TTFT by
      the declared factor is not pulling its weight.
    * ``prefix_hit_rate`` must be > 0 — a gate run where nothing hit the
      trie measured the wrong workload.

    Returns a list of human-readable violation strings; empty when the
    section did not run or everything held.
    """
    warm = cold = hit_rate = None
    for r in rows:
        name = str(r.get("name", ""))
        us = float(r.get("us_per_call", 0.0))
        if name.startswith("prefix_ttft_p50/warm"):
            warm = us
        elif name.startswith("prefix_ttft_p50/cold"):
            cold = us
        elif name.startswith("prefix_hit_rate"):
            hit_rate = us
    violations = []
    if warm is not None and cold is not None and cold > 0 \
            and warm > cold * tolerance:
        violations.append(
            f"prefix_ttft_p50/warm {warm:.1f}us > {tolerance:.2f}x cold "
            f"{cold:.1f}us ({warm / cold:.2f}x): prefix reuse did not cut "
            "TTFT under shared-prefix traffic")
    if hit_rate is not None and hit_rate <= 0:
        violations.append(
            "prefix_hit_rate is 0%: the shared-prefix trace never hit the "
            "trie")
    return violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fast sections only (CI bench-smoke)")
    ap.add_argument("--serve", action="store_true",
                    help="batched serving section only (CI serve-smoke)")
    ap.add_argument("--prefix", action="store_true",
                    help="prefix KV-cache section only (CI serve-smoke; "
                         "warm-vs-cold TTFT gate)")
    ap.add_argument("--overlap", action="store_true",
                    help="async-overlap section only (CI runs it under "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to a BENCH_*.json artifact")
    ap.add_argument("--compare", default=None, metavar="OLD_JSON",
                    help="diff against a previous BENCH_*.json; exit 1 on "
                         ">20%% regression in segment_fold/mean_by_key/"
                         "serve_ rows")
    ap.add_argument("--require-baseline", action="store_true",
                    help="with --compare: a missing/unreadable baseline is "
                         "an error, not a silent skip")
    args = ap.parse_args(argv)
    use_compile_cache()

    print("name,us_per_call,derived")
    if args.prefix:
        print("# -- radix prefix KV cache: warm vs cold TTFT --------------------")
        bench_prefix.main()
    elif args.serve:
        print("# -- batched serving path (planner-lowered keyed folds, CPU) -----")
        bench_serve.main()
    elif args.overlap:
        print("# -- async overlap: double-buffered DCN crossing vs sync ---------")
        bench_overlap.main()
    else:
        print("# -- Algorithms 1/3/4: mean-by-key & word count ------------------")
        bench_mapreduce.main()
        print("# -- aggregation layer: folds, planner tiers, grad accum, metrics --")
        bench_aggregation.main()
        print("# -- cost-model planner: auto vs forced layouts ------------------")
        bench_plan.main()
        print("# -- sketch monoids (paper section 3) ----------------------------")
        bench_sketches.main()
        print("# -- windowed streaming: two-stacks + keyed window folds ---------")
        bench_windows.main()
        if not args.quick:
            print("# -- Pallas kernels vs XLA refs (interpret mode on CPU) ----------")
            bench_kernels.main()
            print("# -- end-to-end train step (smoke configs, CPU) ------------------")
            bench_train.main()
            print("# -- batched serving path (planner-lowered keyed folds, CPU) -----")
            bench_serve.main()

    if args.json:
        import jax
        payload = {
            "quick": args.quick,
            "python": platform.python_version(),
            "jax": jax.__version__,
            "platform": platform.platform(),
            "rows": common.ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.json} ({len(common.ROWS)} rows)")

    if args.compare:
        # intra-run auto-vs-forced gate (no baseline needed): the planner's
        # layout='auto' rows must be within AUTO_TOLERANCE of the best
        # forced layout measured in THIS run
        overlap_violations = check_overlap_rows(common.ROWS)
        if overlap_violations:
            print("# OVERLAP GATE FAILED:")
            for v in overlap_violations:
                print(f"#   {v}")
            return 1
        prefix_violations = check_prefix_rows(common.ROWS)
        if prefix_violations:
            print("# PREFIX CACHE GATE FAILED:")
            for v in prefix_violations:
                print(f"#   {v}")
            return 1
        auto_violations = check_auto_rows(common.ROWS)
        if auto_violations:
            print(f"# PLANNER AUTO REGRESSION (> {AUTO_TOLERANCE:.2f}x best "
                  "forced layout):")
            for case, auto_us, best_layout, best_us in auto_violations:
                print(f"#   plan_auto/{case}: {auto_us:.1f}us vs best forced "
                      f"'{best_layout}' {best_us:.1f}us "
                      f"({auto_us / best_us:.2f}x)")
            return 1
        try:
            with open(args.compare) as f:
                old = json.load(f)
        except (OSError, ValueError):
            if args.require_baseline:
                print(f"# MISSING BASELINE: no usable previous artifact at "
                      f"{args.compare} and --require-baseline is set")
                return 1
            print(f"# no usable previous artifact at {args.compare}; "
                  "skipping diff")
            return 0
        old_rows = old.get("rows", []) if isinstance(old, dict) else []
        regressions = compare_rows(common.ROWS, old_rows)
        if regressions:
            print("# PERF REGRESSION (>20% vs previous artifact):")
            for name, old_us, new_us in regressions:
                print(f"#   {name}: {old_us:.1f}us -> {new_us:.1f}us "
                      f"({new_us / old_us:.2f}x)")
            return 1
        print(f"# perf diff vs {args.compare}: "
              f"guarded rows within {REGRESSION_TOLERANCE:.2f}x tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
