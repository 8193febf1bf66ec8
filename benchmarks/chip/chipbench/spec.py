"""The benchmark's definition: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by its name:

  configs/<config>.json      the configuration as it is run
  traffic/<traffic>.json     the mix: a ``kind`` and its parameters
  limits/<workload>.json     the limits of the cell's correctness check
  metrics/<metric>.py        a reader with ``read(run) -> float | None``

A configuration's ``reference`` key names, relative to this directory, its
plain reference, ``reference/<model>.py``; a serve configuration's program
adapter is ``programs/<model>.py``, beside it.  A configuration cut to fit
its chips lists the cut keys under ``reduced``, as ``BENCHMARK.json`` does.

A serve reference imports nothing of the program and provides

  Sizes.from_config(config)  the model's shape, with a ``vocab_size``
  make_weights(seed, sizes, device=None)
                             every weight, in the type it is served in,
                             drawn in one jitted call from the seed
  logits_at(weights, tokens, read, sizes, fp8=False)
                             jitted float32 logits at positions ``read``
  served_logits(weights, prompt, served, sizes, length, width, fp8=False)
                             the logits before each served token, as numpy
  decode_flops(config, keys_per_token)
                             one decode step's model FLOPs

and its adapter provides

  program_config(config)     the program's ``ModelConfig``; raises where the
                             program differs from the file
  program_params(weights, cfg)
                             the weights as the program's parameter tree

So a serve configuration is added as new files only: ``configs/<name>.json``,
``reference/<model>.py`` and ``programs/<model>.py`` (where the model is
new), ``limits/<workload>.json``, any new ``traffic/<traffic>.json``, and
its entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
from typing import Dict, List, Optional

CHIP_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]
BENCHMARK_JSON = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"{what} {name!r} is not a valid name (letters, "
                         f"digits, '_', '.', '-'; at most 64; no leading "
                         f"'.' or '-')")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ValueError(f"unit {unit!r} is not valid (1 to 16 of letters, "
                         f"digits, '_', '/', '%', '.', '-')")
    return unit


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def validate(bench: dict) -> None:
    """The name and unit rules of every entry (raises ValueError)."""
    seen = set()
    for c in bench["configs"]:
        check_name(c["name"], "config")
        for key in c["reduced"]:
            check_name(key, "reduced key")
    for w in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            check_name(w[key], f"workload {key}")
        if w["chips"] not in (1, 4):
            raise ValueError(f"workload {w['name']}: chips must be 1 or 4")
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name(m["name"], "metric")
        check_unit(m["unit"])
        if m["better"] not in ("lower", "higher"):
            raise ValueError(f"metric {m['name']}: better must be lower or "
                             f"higher")
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in bench[kind]]
        if len(names) != len(set(names)):
            raise ValueError(f"duplicate names among {kind}")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in seen:
            raise ValueError(f"duplicate metric {m['name']}")
        seen.add(m["name"])


@dataclasses.dataclass
class Cell:
    """One workload with everything its files say."""

    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    listed = metric.get("workloads")
    if listed is not None:
        return cell in listed
    if "moves" in metric:       # per-layer: reported wherever its metric is
        return metric["moves"] in e2e_names
    return True


def load_cell(workload: str, bench: Optional[dict] = None,
              chip_dir: pathlib.Path = CHIP_DIR) -> Cell:
    """The cell ``workload``: its configuration file as ``BENCHMARK.json``
    lists it (relative to the checkout that holds ``chip_dir``), its traffic
    mix and its limits."""
    bench = load_json(BENCHMARK_JSON) if bench is None else bench
    validate(bench)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(chip_dir.parents[1] / entry["file"])
    if sorted(config.get("reduced", [])) != sorted(entry["reduced"]):
        raise ValueError(f"{entry['file']}: reduced "
                         f"{config.get('reduced', [])} differs from "
                         f"BENCHMARK.json's {entry['reduced']}")
    if "reference" in config:
        reference(config, chip_dir)
    traffic = load_json(chip_dir / "traffic" / f"{w['traffic']}.json")
    limits_path = chip_dir / "limits" / f"{workload}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(workload=w, config=config, traffic=traffic, limits=limits,
                end_to_end=e2e, per_layer=per_layer)


def _module(path: pathlib.Path, what: str, chip_dir: pathlib.Path):
    """The module at ``path``, loaded once per process."""
    path = path.resolve()
    if chip_dir.resolve() not in path.parents:
        raise ValueError(f"{what} {path} is outside {chip_dir}")
    if not path.is_file():
        raise FileNotFoundError(f"no {what} at {path}")
    rel = path.relative_to(chip_dir.resolve()).with_suffix("")
    mod_name = "chip_" + re.sub(r"[^A-Za-z0-9_]", "_", str(rel))
    known = sys.modules.get(mod_name)
    if known is not None and known.__file__ != str(path):
        raise ValueError(f"{what} {path} and {known.__file__} share the "
                         f"module name {mod_name}")
    if known is None:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod        # dataclasses look their module up
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return sys.modules[mod_name]


def metric_reader(name: str, chip_dir: pathlib.Path = CHIP_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = chip_dir / "metrics" / f"{check_name(name, 'metric')}.py"
    return _module(path, f"reader for metric {name!r}", chip_dir).read


def reference(config: dict, chip_dir: pathlib.Path = CHIP_DIR):
    """The module that the configuration's ``reference`` key names."""
    return _module(chip_dir / config["reference"],
                   f"reference for {config['name']!r}", chip_dir)


def program_adapter(config: dict, chip_dir: pathlib.Path = CHIP_DIR):
    """The program adapter of the configuration's reference:
    ``programs/<model>.py`` beside ``reference/<model>.py``."""
    ref = chip_dir / config["reference"]
    return _module(ref.parents[1] / "programs" / ref.name,
                   f"program adapter for {config['name']!r}", chip_dir)


def read_metrics(metrics: List[dict], run) -> Dict[str, dict]:
    """Every metric whose reader finds something to read, with its unit."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
