"""MFU denominator accounting: the attention span must be the mean
number of keys a query ACTUALLY attends to — billing the skipped
causal half would flatter MFU ~2x on exactly the configs where the
flash kernels skip it."""
import types

from containerpilot_tpu.workload.flops import (
    peak_flops,
    train_flops_per_token,
)


def _cfg(window=0):
    return types.SimpleNamespace(
        n_layers=4, d_model=256, d_ff=1024, window=window,
    )


def test_full_causal_attention_span_is_halved():
    cfg = _cfg()
    seq, n_params = 2048, 10_000_000
    got = train_flops_per_token(cfg, n_params, seq)
    # exact mean span over positions: (seq + 1) / 2
    expected = (
        6.0 * n_params
        + 12.0 * cfg.n_layers * cfg.d_model * (seq + 1) / 2.0
    )
    assert abs(got - expected) < 1.0


def test_windowed_attention_span_tracks_window():
    cfg = _cfg(window=256)
    seq, n_params = 4096, 10_000_000
    got = train_flops_per_token(cfg, n_params, seq)
    w = 256.0
    span = w - w * (w - 1.0) / (2.0 * seq)
    expected = 6.0 * n_params + 12.0 * cfg.n_layers * cfg.d_model * span
    assert abs(got - expected) < 1.0
    # windowed span ~= window, far below the full-causal span
    full = train_flops_per_token(_cfg(), n_params, seq)
    assert got < full


def test_window_wider_than_seq_equals_full_causal():
    assert train_flops_per_token(
        _cfg(window=8192), 1_000_000, 1024
    ) == train_flops_per_token(_cfg(), 1_000_000, 1024)


def test_frozen_params_bill_4_flops():
    cfg = _cfg()
    n = 1_000_000
    all_trained = train_flops_per_token(cfg, n, 128)
    all_frozen = train_flops_per_token(cfg, n, 128, n_frozen=n)
    assert abs((all_trained - all_frozen) - 2.0 * n) < 1.0


def test_peak_flops_known_generations():
    assert peak_flops("TPU v5 lite") == 197e12
    assert peak_flops("TPU v4") == 275e12


def test_peak_flops_unknown_device_is_an_error():
    """An MFU against an assumed peak is a made-up number: a device
    kind the table does not know raises, it never defaults."""
    import pytest

    with pytest.raises(ValueError, match="weird-device"):
        peak_flops("weird-device")
    with pytest.raises(ValueError):
        peak_flops("cpu")
