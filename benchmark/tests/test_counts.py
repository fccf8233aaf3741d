"""FLOP and byte counts against numbers worked by hand for Mistral-7B's
published widths, and the shares they give never pass the peak."""
import json
import os

import pytest

from benchmark.harness import counts, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def load(name):
    with open(os.path.join(CONFIGS, name)) as fh:
        return json.load(fh)


def test_layer_and_total_parameters_by_hand():
    config = load("mistral-7b-train.json")
    # q and o: 4096*4096 each; k and v: 4096*1024 each; SwiGLU: 3*4096*14336
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert by_hand == 218_103_808
    assert counts.layer_matmul_params(config) == by_hand
    head = 4096 * 32000
    assert counts.matmul_params(config) == 2 * by_hand + head == 567_279_616
    full = dict(config, num_hidden_layers=32)
    # the published model: 7.24 B parameters
    assert counts.total_params(full) == 32 * by_hand + 2 * head + 65 * 4096
    assert abs(counts.total_params(full) / 1e9 - 7.24) < 0.01


def test_attention_span_by_hand():
    assert counts.mean_attention_span(8, 0) == 4.5          # (s+1)/2
    assert counts.mean_attention_span(8192, 4096) == 4096 - 4096 * 4095 / 16384
    assert counts.mean_attention_span(4096, 4096) == 2048.5  # never binds


def test_train_flops_by_hand():
    config = load("mistral-7b-train.json")
    per_token = counts.train_flops_per_token(config, 8192, 4096)
    matmul = 6 * 567_279_616
    attention = 12 * 2 * 4096 * (4096 - 4096 * 4095 / 16384)
    assert per_token == pytest.approx(matmul + attention)
    assert per_token / 1e9 == pytest.approx(3.71, abs=0.01)
    # a step of 16,384 tokens at the chip's peak takes 0.31 s: no
    # measured rate above 53 k tokens/s can be real
    assert 16384 * per_token / peaks.peak("TPU v5 lite", "bf16_flops") == \
        pytest.approx(0.3083, abs=0.001)


def test_decode_bytes_by_hand():
    config = load("mistral-7b-serve.json")
    weights = (4 * 218_103_808 + 4096 * 32000) * 2
    assert counts.kv_bytes_per_token(config) == 4 * 2 * 1024 * 2 == 16384
    assert counts.decode_step_bytes(config, 0) == weights == 2_006_974_464
    live = 16 * 224
    assert counts.decode_step_bytes(config, live) == weights + live * 16384
    # at 819 GB/s the least a step can take is 2.5 ms
    least_ms = counts.decode_step_bytes(config, live) / peaks.peak(
        "TPU v5 lite", "hbm_bytes_per_s") * 1e3
    assert least_ms == pytest.approx(2.52, abs=0.01)


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        peaks.peak("TPU v9", "bf16_flops")
    with pytest.raises(ValueError):
        peaks.peak("cpu", "hbm_bytes_per_s")
