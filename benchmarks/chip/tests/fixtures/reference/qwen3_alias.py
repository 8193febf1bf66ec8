"""The Qwen3 reference under another name, recording each call the harness
makes into it: a configuration that names this file must be served and
checked through it."""
from reference import qwen3

CALLS = []

Sizes = qwen3.Sizes
logits_at = qwen3.logits_at


def _recorded(fn):
    def call(*args, **kwargs):
        CALLS.append(fn.__name__)
        return fn(*args, **kwargs)
    return call


make_weights = _recorded(qwen3.make_weights)
served_logits = _recorded(qwen3.served_logits)
decode_flops = _recorded(qwen3.decode_flops)
