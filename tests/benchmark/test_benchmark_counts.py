"""The operation counts the MXU share is built from, against hand counts."""

import json
import os

import pytest

from bench_paths import ROOT

from benchmark import ops_count


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,n", [("alexnet", 62378344),
                                    ("vgg16", 138357544)])
def test_parameter_counts_are_the_published_ones(name, n):
    cfg = config(name)
    assert ops_count.n_params(cfg) == n == cfg["n_params"]


def test_alexnet_forward_macs_against_a_hand_count():
    by_hand = (55 * 55 * 11 * 11 * 3 * 96        # conv1, stride 4
               + 27 * 27 * 5 * 5 * 96 * 256      # conv2, one tower
               + 13 * 13 * 3 * 3 * 256 * 384
               + 13 * 13 * 3 * 3 * 384 * 384
               + 13 * 13 * 3 * 3 * 384 * 256
               + 6 * 6 * 256 * 4096 + 4096 * 4096 + 4096 * 1000)
    assert by_hand == 1135256096
    assert ops_count.forward_macs(config("alexnet")) == by_hand


def test_vgg16_forward_macs_against_a_hand_count():
    convs = [(224, 3, 64), (224, 64, 64), (112, 64, 128), (112, 128, 128),
             (56, 128, 256), (56, 256, 256), (56, 256, 256),
             (28, 256, 512), (28, 512, 512), (28, 512, 512),
             (14, 512, 512), (14, 512, 512), (14, 512, 512)]
    by_hand = sum(hw * hw * 9 * cin * co for hw, cin, co in convs) \
        + 7 * 7 * 512 * 4096 + 4096 * 4096 + 4096 * 1000
    assert ops_count.forward_macs(config("vgg16")) == by_hand == 15470264320


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_the_first_layers_input_gradient_is_not_charged(name):
    cfg = config(name)
    first = next(r for r in ops_count.layer_table(cfg) if r["macs"])
    fwd = ops_count.forward_macs(cfg)
    assert ops_count.train_flops_per_sample(cfg) \
        == 2 * (3 * fwd - first["macs"])
    assert ops_count.train_flops_per_sample(cfg) < 6 * fwd


def test_shapes_follow_the_layer_list():
    table = ops_count.layer_table(config("alexnet"))
    assert [r["out"] for r in table if r["type"] == "max_pooling"] \
        == [(27, 27, 96), (13, 13, 256), (6, 6, 256)]
    assert table[-1]["out"] == (1000,)


def test_a_pooling_window_that_does_not_divide_is_an_error():
    cfg = config("alexnet")
    cfg["input_shape"] = [231, 231, 3]
    with pytest.raises(ValueError, match="not a multiple"):
        ops_count.layer_table(cfg)


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert ops_count.peak_for(peaks, "TPU v5 lite")["bf16_flops_per_s"] \
        == 197e12
    assert "source" in "".join(peaks) and "Google Cloud" in peaks["_source"]
    with pytest.raises(KeyError, match="not in peaks.json"):
        ops_count.peak_for(peaks, "TPU v9 imaginary")


def test_mxu_share_is_operations_over_time_times_peak():
    assert ops_count.mxu_share_percent(197e12 * 0.05, 0.1, 197e12) \
        == pytest.approx(50.0)
