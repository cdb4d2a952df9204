"""The FLOP function behind ``train_mfu``, against a hand count for the
4-layer Qwen2.5-3B configuration."""

import pytest

from bench import flops
from bench.harness import find_config, load_peaks


def test_four_layer_qwen_by_hand():
    cfg = find_config("qwen2.5-3b-4L-adam")
    d, ff, v, hd = 2048, 11008, 151936, 128
    per_layer = (d * 16 * hd          # q
                 + 2 * d * 2 * hd     # k, v
                 + 16 * hd * d        # o
                 + 3 * d * ff)        # up, gate, down
    assert per_layer == 77_070_336
    assert flops.matmul_params(cfg) == 4 * per_layer + d * v
    fwd = 2 * (4 * per_layer + d * v) + 4 * 4 * 512 * 16 * hd
    assert flops.train_flops_per_token(cfg, 512) == 3 * fwd
    step = flops.train_step_flops(cfg, 4, 512)
    assert step == 3 * fwd * 2048
    assert step == 7_714_835_005_440


def test_peaks_by_device_kind():
    assert load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        load_peaks("cpu")
