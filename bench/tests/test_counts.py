"""The yardstick's counts against hand-computed values."""
import json

import pytest

from bench.lib import counts, harness

VGG16_PLAN = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
              512, 512, 512, "M", 512, 512, 512, "M"]


def test_vgg16_forward_flops():
    # 13 convs at 32x32 input, then dense 512->512->10 (multiply-add = 2)
    assert counts.vgg_fwd_flops(VGG16_PLAN, hw=32, in_ch=3, fc_width=512,
                                n_classes=10) == 626_927_616


def test_vgg16_config_file_counts_the_same():
    cfg = json.loads((harness.BENCH_DIR / "configs"
                      / "vgg16-cifar10.json").read_text())
    model = harness.load_module(harness.BENCH_DIR / "models" / "vgg16.py",
                                "vgg16")
    assert model.fwd_flops_per_sample(cfg) == 626_927_616
    assert model.cut_shape(cfg, {"per_client": 64}) == (64, 32, 32, 64)


def test_wire_quant_bytes_of_the_cut_activation():
    # (64, 32, 32, 64) f32: 16,777,216 B read; 4,194,304 int8 values and
    # 65,536 row scales of 4 B written
    assert counts.wire_quant_bytes((64, 32, 32, 64), 4) == 21_233_664
    assert counts.wire_dequant_bytes((64, 32, 32, 64), 4) == 21_233_664
    assert counts.wire_quant_bytes((64,), 4) == 256 + 64 + 4
    assert counts.wire_quant_bytes((), 4) == 4 + 1 + 4


def test_peak_table():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_least_time_takes_the_larger_bound():
    p = counts.peaks("TPU v5 lite")
    assert counts.least_time_s(197e12, 0, p) == pytest.approx(1.0)
    assert counts.least_time_s(0, 819e9, p) == pytest.approx(1.0)
    assert counts.least_time_s(197e12, 2 * 819e9, p) == pytest.approx(2.0)
