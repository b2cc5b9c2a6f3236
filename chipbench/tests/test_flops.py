"""Model FLOPs against hand counts for GPT-A at 2 layers."""
import json
import os

import pytest

from chipbench import flops
from chipbench.peaks import PEAKS, peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def gpt_a_2l():
    with open(os.path.join(HERE, "..", "configs", "gpt-a-2l.json")) as f:
        return json.load(f)


def test_matmul_params(gpt_a_2l):
    # per layer 4 * 4096^2 (attention) + 2 * 4096 * 16384 (FFN); head 4096 * 50304
    assert flops.layer_matmul_params(gpt_a_2l) == 4 * 4096**2 + 2 * 4096 * 16384 == 201_326_592
    assert flops.matmul_params(gpt_a_2l) == 2 * 201_326_592 + 206_045_184 == 608_698_368


def test_train_flops_per_token(gpt_a_2l):
    # 6N + 12 L d T at T = 1536: 3.652e9 + 1.510e8 = 3.803e9
    per_token = flops.train_flops_per_token(gpt_a_2l, 1536)
    assert per_token == 6 * 608_698_368 + 12 * 2 * 4096 * 1536
    assert per_token == pytest.approx(3.80e9, rel=1e-3)
    assert flops.train_step_flops(gpt_a_2l, 1, 1536) == pytest.approx(5.84e12, rel=1e-3)


def test_peaks_table():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert all("source" in p for p in PEAKS.values())
    with pytest.raises(KeyError):
        peaks_for("cpu")
