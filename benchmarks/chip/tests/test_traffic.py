"""The traffic generator and the serve cell's end-to-end arithmetic."""
import json
import math

import pytest

from conftest import CHIP
from chipbench import serve, traffic


def mix(name="chat-prefix"):
    return json.loads((CHIP / "traffic" / f"{name}.json").read_text())


def test_same_seed_same_requests():
    a = traffic.serve_requests(mix(), 2**31 + 12345, 30.0, 151936)
    b = traffic.serve_requests(mix(), 2**31 + 12345, 30.0, 151936)
    assert a == b and len(a) > 10


def test_seeds_share_the_schedule_and_draw_other_tokens():
    a = traffic.serve_requests(mix(), 1, 30.0, 151936)
    b = traffic.serve_requests(mix(), 2, 30.0, 151936)
    assert [(r.offset_s, r.prefix, len(r.prompt), r.max_new) for r in a] == \
        [(r.offset_s, r.prefix, len(r.prompt), r.max_new) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))


def test_chat_prefix_lengths_and_sharing():
    m = mix()
    reqs = traffic.serve_requests(m, 5, 60.0, 151936)
    assert all(r.offset_s < 60.0 for r in reqs)
    shared = m["shared_prefixes"]["tokens"]
    heads = {r.prompt[:shared] for r in reqs}
    assert 1 < len(heads) <= m["shared_prefixes"]["count"]
    for r in reqs:
        user = len(r.prompt) - shared
        assert m["prompt_tokens"]["min"] <= user <= m["prompt_tokens"]["max"]
        assert m["output_tokens"]["min"] <= r.max_new <= m["output_tokens"]["max"]
        assert len(r.prompt) <= m["server"]["prefill_buckets"][-1]
        assert r.max_new <= m["server"]["max_new_tokens"]


def test_unique_mix_shares_nothing():
    reqs = traffic.serve_requests(mix("chat-unique"), 5, 60.0, 151936)
    assert all(r.prefix == -1 for r in reqs)
    assert max(len(r.prompt) for r in reqs) <= 448


def test_poisson_arrivals_keep_the_rate():
    import numpy as np
    spec = {"process": "poisson", "rate_per_s": 50.0}
    t = traffic.arrival_offsets(np.random.default_rng(0), spec, 200.0)
    assert len(t) / 200.0 == pytest.approx(50.0, rel=0.1)
    assert all(0 <= a < b < 200.0 for a, b in zip(t, t[1:]))


def test_ttft_and_tpot_are_timed_from_due_times():
    req = traffic.ServeRequest(0.0, (1, 2), 3, -1)
    a = serve.Served(req=req, due=10.0, tokens=[1, 2, 3],
                     times=[10.5, 10.6, 10.8], done=True)
    b = serve.Served(req=req, due=11.0, tokens=[1, 2, 3],
                     times=[11.2, 11.3, 11.4], done=True)
    lost = serve.Served(req=req, due=12.0, failed=True)
    out = serve.ServeCell.end_to_end([a, b, lost])
    # TTFTs 500 ms, 200 ms and a failure, which misses every tail
    assert out["ttft_p50_ms"] == pytest.approx(500.0)
    assert math.isinf(out["ttft_p95_ms"])
    # pooled: (0.3 + 0.2) s over 4 decoded tokens
    assert out["tpot_ms"] == pytest.approx(125.0)
