"""The precision witness on the DeepSeek-V2 smoke configuration, on the
CPU: in float32 the program's forward picks the reference's best token
everywhere."""
import json

import jax

from conftest import CHIP
from chipbench import spec
import precision_witness as pw

FIXTURES = CHIP / "tests" / "fixtures"


def test_the_float32_forward_picks_the_references_tokens():
    fixture = lambda n: json.loads((FIXTURES / n).read_text())  # noqa: E731
    cell = spec.Cell(workload={"name": "ds", "chips": 1},
                     config=fixture("deepseek-v2-smoke.json"),
                     traffic=fixture("chat-smoke.json"), limits={},
                     end_to_end=[], per_layer=[])
    out = pw.seed_readings(cell, 2**32 + 23, 1.0, None, jax.devices()[:1])
    assert out["sampled_requests"] > 0 and out["failed"] == 0
    # float32 at highest precision: the same sums in other orders
    assert out["program_f32"] == 0.0
    assert out["program_f32_logit_max"] < 1e-3
    # bfloat16 departs from the reference, the served reading with it
    assert out["program_logit_max"] > out["program_f32_logit_max"]
    assert 0.0 < out["served_picks_agree"] <= 1.0
