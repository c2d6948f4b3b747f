"""Operations and bytes at published widths against hand counts, and the
peaks table."""

import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

from roofline import bound_s, peaks  # noqa: E402
from roofline import ops as R  # noqa: E402

QWEN = json.loads((BENCH / "configs" / "qwen2.5-14b-kanffn.json").read_text())
KAN2 = json.loads((BENCH / "configs" / "kan2-knot.json").read_text())


def test_kan_ffn_halves_at_published_widths():
    (o1, b1), (o2, b2) = R.kan_ffn_halves(QWEN, 32)
    # 5120 -> 1280, K+1 = 4 band terms + the ReLU branch
    assert o1 == 2 * 32 * 5120 * 1280 * 5 == 2_097_152_000
    # codes 5120*11*1280 + 5120*1280, 2 f32 scale rows, a 32x4 f32 LUT,
    # bf16 activations in and out
    assert b1 == 72_089_600 + 6_553_600 + 10_240 + 512 + 327_680 + 81_920
    assert o2 == o1
    assert b2 == 72_089_600 + 6_553_600 + 2 * 5120 * 4 + 512 + 81_920 \
        + 327_680


def test_kan2_network():
    (o1, b1), (o2, b2) = R.kan_network(KAN2, 65536)
    assert R.lut_rows(68, 8) == 2              # 68 * 2**1 <= 256
    assert o1 == 2 * 65536 * 17 * 1 * 5
    assert b1 == 17 * 71 * 1 + 17 + 8 + 2 * 4 * 4 + 65536 * 17 * 4 + 65536 * 4
    assert o2 == 2 * 65536 * 1 * 14 * 5
    assert b2 == 71 * 14 + 14 + 2 * 14 * 4 + 32 + 65536 * 4 + 65536 * 14 * 4


def test_attention():
    o, b = R.attention_decode(QWEN, [100, 200])
    assert o == 4 * 40 * 128 * 300
    assert b == 2 * 300 * 8 * 128 * 2 + 2 * 2 * 40 * 128 * 2
    o, b = R.attention_prefill(QWEN, 256, 4)
    assert o == 4 * 40 * 128 * (257 + 258 + 259 + 260)
    assert b == 2 * 260 * 8 * 128 * 2 + 4 * 2 * 40 * 128 * 2


def test_step_ops():
    per_token = (2 * 5120 * (40 + 16) * 128 + 2 * 40 * 128 * 5120
                 + 2 * 2_097_152_000 // 32)
    assert R.lm_token_ops(QWEN) == per_token
    assert R.lm_step_ops(QWEN, "prefill", (0, 8, True)) == (
        4 * (8 * per_token + 4 * 40 * 128 * 36) + 2 * 5120 * 152064)


def test_peaks_and_bound():
    pk = peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert bound_s(2e12, 1e6, 1e12, 1e9) == 2.0
    assert bound_s(1e6, 2e9, 1e12, 1e9) == 2.0
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
