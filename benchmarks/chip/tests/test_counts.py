"""FLOP and byte counts against sums worked out by hand."""
import json

from conftest import CHIP
from chipbench import spec
from metrics.common import counts


def qwen3():
    return json.loads((CHIP / "configs" / "qwen3-0.6b.json").read_text())


def test_matmul_params_of_qwen3_0p6b():
    # per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024,
    # gate/up/down 3 x 1024x3072; then the tied head 151936x1024
    per_layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072
    assert per_layer == 15_728_640
    c = qwen3()
    assert spec.reference(c).matmul_params(c) == 28 * 15_728_640 + 155_582_464


def test_decode_flops_hand_sum():
    c = qwen3()
    ref = spec.reference(c)
    # two tokens attending over 100 and 300 keys: 2 x weights each, plus
    # 4 x 28 layers x 16 heads x 128 x keys
    w = 2 * (28 * 15_728_640 + 155_582_464)
    want = 2 * w + 4 * 28 * 16 * 128 * (100 + 300)
    assert ref.decode_flops(c, [100, 300]) == want
    assert ref.decode_flops(c, []) == 0


def test_shared_decode_arithmetic_hand_sum():
    # 2 x 1000 weights a token, 8 FLOPs a key, over 0, 5 and 10 keys
    assert counts.decode_flops(1000, 8, [0, 5, 10]) == 3 * 2000 + 8 * 15
    assert counts.decode_flops(1000, 8, []) == 0


def test_fold_bytes_hand_sum():
    # 2**24 int32 ids read once, a 151936-entry int32 table written once
    assert counts.fold_bytes(1 << 24, 151936) == 67_108_864 + 607_744
