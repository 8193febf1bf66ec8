"""The one generator of serve traffic: a mix file's parameters -> requests.

A mix is data (``traffic/<name>.json``).  Its ``shape_seed`` fixes the
arrival times and each request's shape (which shared prefix, how many user
and output tokens); the run's ``--seed`` draws only the token ids.  So every
seed offers the same work on the same schedule, and runs of different
seeds differ as little as two runs of one seed: with a few dozen requests
in a window, a seed that reordered the shapes moved the TTFT tail by a
quarter.

Parameters:

  arrivals        {"process": "poisson", "rate_per_s": r}
  shared_prefixes {"count": n, "tokens": t, "popularity_exponent": a}
                   -- n system prompts of t tokens, the k-th chosen with
                   weight 1 / k**a; count 0 gives every prompt unique
  prompt_tokens   the user turn after the shared prefix, and
  output_tokens   each {"median": m, "sigma": s, "min": lo, "max": hi}:
                   log-normal, rounded and clipped to [lo, hi]
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    offset_s: float               # due time after the window opens
    prompt: tuple
    max_new: int
    prefix: int                   # shared prefix index, -1 for none


def _lognormal(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_offsets(rng: np.random.Generator, spec: dict,
                    seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson process at ``rate_per_s``."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = float(spec["rate_per_s"])
    out, t = [], 0.0
    while True:
        # gamma(1) is the exponential gap; this draw fixes the schedules measured
        t += float(rng.gamma(1.0, 1.0 / rate))
        if t >= seconds:
            return np.asarray(out)
        out.append(t)


def shared_prefixes(mix: dict, seed: int, vocab: int) -> List[tuple]:
    """The token ids of the mix's shared prefixes (system prompts)."""
    rng = np.random.default_rng([seed, 1])
    shared = mix["shared_prefixes"]
    return [tuple(rng.integers(1, vocab, int(shared["tokens"])).tolist())
            for _ in range(int(shared["count"]))]


def serve_requests(mix: dict, seed: int, seconds: float,
                   vocab: int) -> List[ServeRequest]:
    """The requests due in a window of ``seconds``, in due order."""
    shape_rng = np.random.default_rng(int(mix["shape_seed"]))
    offsets = arrival_offsets(shape_rng, mix["arrivals"], seconds)
    n = len(offsets)
    shared = mix["shared_prefixes"]
    n_prefix = int(shared["count"])
    if n_prefix:
        w = 1.0 / np.arange(1, n_prefix + 1) ** shared["popularity_exponent"]
        prefix = shape_rng.choice(n_prefix, size=n, p=w / w.sum())
    else:
        prefix = np.full((n,), -1)
    user = _lognormal(shape_rng, mix["prompt_tokens"], n)
    out = _lognormal(shape_rng, mix["output_tokens"], n)

    rng = np.random.default_rng(seed)
    texts = shared_prefixes(mix, seed, vocab)
    reqs = []
    for i in range(n):
        head = texts[prefix[i]] if prefix[i] >= 0 else ()
        body = tuple(rng.integers(1, vocab, int(user[i])).tolist())
        reqs.append(ServeRequest(offset_s=float(offsets[i]),
                                 prompt=head + body, max_new=int(out[i]),
                                 prefix=int(prefix[i])))
    return reqs
