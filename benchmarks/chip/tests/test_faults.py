"""A run with the timed path broken underneath must come out not correct,
and so must the precision control.  The look for a chip is skipped; the
rest of a run is driven as ``run.py`` drives it, at a size the CPU holds.

The four-device fold runs in a child process with four virtual CPU devices.
"""
import functools
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from conftest import CHIP
import control
from chipbench import fold, serve, spec

FIXTURES = CHIP / "tests" / "fixtures"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
LIMITS = json.loads((FIXTURES / "limits-smoke.json").read_text())


def cell(config, traffic, chips):
    return spec.Cell(
        workload={"name": "smoke", "chips": chips},
        config=json.loads((FIXTURES / config).read_text()),
        traffic=traffic, limits=LIMITS,
        end_to_end=[{"name": "setup_s", "unit": "s"}], per_layer=[])


def serve_correct(monkeypatch, fault, seed=13):
    import run

    c = cell("qwen3-smoke.json",
             json.loads((FIXTURES / "chat-smoke.json").read_text()), 1)
    monkeypatch.setattr(serve, "ServeCell",
                        functools.partial(serve.ServeCell, fault=fault))
    out = serve.run(c, seed, 1.0, False, jax.devices()[:1], PEAKS,
                    time.perf_counter(), lambda _: None)
    return run.result_line(c, out, False, jax.devices()[:1])


def test_sound_serve_run_is_correct(monkeypatch):
    line = serve_correct(monkeypatch, None)
    assert line["correct"] and list(line)[-1] == "checks"
    assert set(line["checks"]) == {"token_gap", "logprob_rel"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_serve_path_is_not_correct(monkeypatch, fault):
    assert not serve_correct(monkeypatch, fault)["correct"]


def test_serve_precision_control_fails_the_limits():
    from chipbench.record import WindowTracer

    c = cell("qwen3-smoke.json",
             json.loads((FIXTURES / "chat-smoke.json").read_text()), 1)
    sc = serve.ServeCell(c.config, c.traffic, 11, jax.devices()[:1])
    sc.warm_up()
    served, _, _ = sc.run_window(sc.requests(1.0), 1.0,
                                 WindowTracer(False, 1.0))
    sample = sc.sample(served, 4)
    sc.release()
    got = sc.compare(sample, control=True)
    assert control.verdicts(got, LIMITS) == {"correct": True,
                                             "control_correct": False}


# -- the four-device fold ----------------------------------------------------

SMALL = {"name": "wc", "kind": "wordcount", "num_keys": 4096,
         "split_ids": 8192, "splits_per_chip": 4, "zipf_exponent": 1.1,
         "strategy": "combiner"}
# so skewed that the hottest id is counted past 2**24 in one job, where
# float32 stops holding every integer
SKEWED = dict(SMALL, split_ids=1 << 22, zipf_exponent=3.0)


def fold_child(which: str) -> dict:
    import run

    devs = jax.devices()[:4]
    if which == "control":
        fc = fold.FoldCell(SKEWED, 5, devs, control=True)
        acc = fc.one_job()
        return fc.compare([acc], (acc, len(fc.splits)))
    fault = None if which == "sound" else which
    c = cell("wordcount-smoke.json", {"kind": "fold"}, 4)
    c.config = SMALL

    class Faulty(fold.FoldCell):
        def __init__(self, *a, **k):
            super().__init__(*a, fault=fault, **k)

    fold.FoldCell = Faulty
    out = fold.run(c, 2**32 + 3, 0.5, False, devs, PEAKS,
                   time.perf_counter(), lambda _: None)
    return run.result_line(c, out, False, devs)


def run_child(which: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__, which], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_fold_run_is_correct():
    line = run_child("sound")
    assert line["correct"] and line["checks"]["count_diff"]["value"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "exchange_left_out", "answer_altered"])
def test_broken_fold_path_is_not_correct(fault):
    line = run_child(fault)
    assert not line["correct"]
    assert line["checks"]["count_diff"]["value"] > 0


def test_fold_precision_control_is_not_exact():
    assert run_child("control")["count_diff"] > 0


if __name__ == "__main__":
    print(json.dumps(fold_child(sys.argv[1])))
