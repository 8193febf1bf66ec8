"""A serve configuration brings its model as files: the configuration names
its reference, the reference's name finds its program adapter, and the
harness knows no model of its own."""
import functools
import hashlib
import json
import time

import jax
import numpy as np
import pytest

from conftest import CHIP
from chipbench import serve, spec

FIXTURES = CHIP / "tests" / "fixtures"
# sha256 of the smoke Qwen3 weights drawn from seed 13, and of their
# program parameter tree, as the harness made them before the reference
# was named by the configuration file: moving the model out of the
# harness changed no bit of either
WEIGHTS_SHA256 = \
    "cc00efac53b5606ba31ad548c00a31aa1db2f79a5633841ffe35d82080e89cc6"
PARAMS_SHA256 = \
    "95705ed5fdcffe76c3611b8021be7daddd639a343e2b28dfe78d508110e8e086"


def fixture(name: str) -> dict:
    return json.loads((FIXTURES / name).read_text())


def tree_sha256(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}\n"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_smoke_weights_and_parameters_keep_their_bits():
    config = fixture("qwen3-smoke.json")
    ref = spec.reference(config)
    adapter = spec.program_adapter(config)
    weights = ref.make_weights(13, ref.Sizes.from_config(config))
    assert tree_sha256(weights) == WEIGHTS_SHA256
    params = serve.program_params(adapter, weights,
                                  adapter.program_config(config))
    assert tree_sha256(params) == PARAMS_SHA256


def test_reference_and_adapter_are_found_by_name():
    config = fixture("qwen3-smoke.json")
    ref, adapter = spec.reference(config), spec.program_adapter(config)
    assert ref.__file__ == str(CHIP / "reference" / "qwen3.py")
    assert adapter.__file__ == str(CHIP / "programs" / "qwen3.py")
    assert spec.reference(config) is ref     # loaded once per process


def test_a_served_configuration_runs_through_its_named_files():
    import run

    config = fixture("alias-smoke.json")
    ref, adapter = spec.reference(config), spec.program_adapter(config)
    ref.CALLS.clear()
    adapter.CALLS.clear()
    c = spec.Cell(workload={"name": "alias", "chips": 1}, config=config,
                  traffic=fixture("chat-smoke.json"),
                  limits=fixture("limits-smoke.json"),
                  end_to_end=[{"name": "setup_s", "unit": "s"}],
                  per_layer=[{"name": "decode_mfu", "unit": "%"}])
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    out = serve.run(c, 2**32 + 17, 1.0, False, jax.devices()[:1], peaks,
                    time.perf_counter(), lambda _: None)
    line = run.result_line(c, out, False, jax.devices()[:1])
    assert line["correct"], line["checks"]
    assert adapter.CALLS == ["program_config", "program_params"]
    assert set(ref.CALLS) == {"make_weights", "served_logits",
                              "decode_flops"}
    assert ref.CALLS.count("make_weights") == 2     # program, then check
    # every decode step's FLOPs are the named reference's count
    steps = out["run"].untraced("decode_step")
    assert steps and ref.CALLS.count("decode_flops") == len(steps)
    assert spec.read_metrics(c.per_layer, out["run"])["decode_mfu"][
        "value"] > 0


def test_a_missing_reference_fails_at_load_with_its_path():
    bench = {"configs": [{"name": "m", "reduced": [],
                          "file": "benchmarks/chip/tests/fixtures/"
                                  "missing-reference.json"}],
             "workloads": [{"name": "m.chat", "config": "m",
                            "traffic": "chat-prefix", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    with pytest.raises(FileNotFoundError,
                       match=str(CHIP / "reference" / "no_such_model.py")):
        spec.load_cell("m.chat", bench)


def test_a_reference_outside_the_benchmark_is_refused():
    config = dict(fixture("qwen3-smoke.json"),
                  reference="../../src/repro/models/common.py")
    with pytest.raises(ValueError, match="outside"):
        spec.reference(config)


def test_reduced_must_agree_with_benchmark_json():
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    entry = dict(bench["configs"][0], reduced=["num_hidden_layers"])
    bench = dict(bench, configs=[entry])
    with pytest.raises(ValueError, match="reduced"):
        spec.load_cell(bench["workloads"][0]["name"], bench)


def test_reduced_keys_are_set_on_the_registered_architecture():
    program = spec.program_adapter(fixture("qwen3-smoke.json"))
    cut = dict(fixture("qwen3-smoke.json"), num_hidden_layers=2)
    with pytest.raises(ValueError, match="num_hidden_layers"):
        program.program_config(cut)
    cfg = program.program_config(dict(cut, reduced=["num_hidden_layers"]))
    assert cfg.num_layers == 2
    with pytest.raises(ValueError, match="no setting"):
        program.program_config(dict(cut, reduced=["rope_scaling"]))
    # a width the file does not list as reduced still has to agree
    wide = dict(cut, reduced=["num_hidden_layers"], hidden_size=128)
    with pytest.raises(ValueError, match="hidden_size"):
        program.program_config(wide)
