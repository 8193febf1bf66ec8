"""The Qwen3 program adapter under another name, recording each call the
harness makes into it."""
from programs import qwen3

CALLS = []


def program_config(config):
    CALLS.append("program_config")
    return qwen3.program_config(config)


def program_params(weights, cfg):
    CALLS.append("program_params")
    return qwen3.program_params(weights, cfg)
