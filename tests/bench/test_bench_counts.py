"""Operation and byte counts of the benchmark against hand-worked numbers."""

import pytest

from bench_tiny import load_json

from bench import counts, device, spec

MLP = load_json("bench/configs/housing-mlp-10m.json")
QWEN = load_json("bench/configs/qwen2-0.5b-l4.json")


def test_mlp_parameter_count():
    # 13*320+320 + 99*(320*320+320) + 320+1
    assert counts.mlp_params(MLP) == 10_174_081 == MLP["params"]


def test_qwen_parameter_count():
    # 151,936*896 + 4 * (2*896*896 + 2*896*128 + 18*64 + 3*896*4864 + 2*896) + 896
    assert counts.lm_layer_params(QWEN) == 14_912_384
    assert counts.lm_params(QWEN) == 195_785_088 == QWEN["params"]


def test_qwen_parameter_count_matches_the_program():
    import jax

    fam = spec.family("dense_lm")
    tree = fam.abstract_params(fam.program_model(QWEN))
    assert sum(a.size for a in jax.tree_util.tree_leaves(tree)) == 195_785_088


def test_mlp_learner_flops():
    t = load_json("bench/traffic/sync-raw-n50-s1.json")
    got = spec.family("mlp").learner_flops(MLP, t)
    matmul = 13 * 320 + 99 * 320 * 320 + 320  # 10,142,080 weights in matrices
    assert got == {"train": 6 * matmul * 100, "eval": 2 * matmul * 100}
    assert got["train"] == 6_085_248_000


def test_qwen_learner_flops():
    t = load_json("bench/traffic/silo-raw-n8-s8-b2x512.json")
    got = spec.family("dense_lm").learner_flops(QWEN, t)
    matmul = 4 * (2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864) + 151_936 * 896
    attention = 4 * 2 * 896 * 512  # QK^T and PV over half the context, 4 layers
    per_token = 2 * matmul + attention
    assert matmul == 195_772_416
    assert got["train"] == 3 * per_token * 2 * 512 * 8 == 9_712_800_104_448
    assert got["eval"] == per_token * 2 * 512


def test_reduce_and_quantize_bytes():
    width = 10_174_464
    f32 = counts.reduce_cost(100, width, "f32")
    assert f32 == {"bytes": 100 * width * 4 + width * 4, "flops": 2 * 100 * width}
    q8 = counts.reduce_cost(100, width, "int8")
    assert q8["bytes"] == 100 * width + 100 * (width // 256) * 4 + width * 4
    assert counts.quantize_cost(width)["bytes"] == width * 5 + (width // 256) * 4


def test_roofline_takes_the_larger_bound():
    peaks = device.peaks("TPU v5 lite")
    cost = {"bytes": 819e9, "flops": 1.0}
    assert counts.roofline_s(cost, peaks) == pytest.approx(1.0)
    cost = {"bytes": 1.0, "flops": 197e12}
    assert counts.roofline_s(cost, peaks) == pytest.approx(1.0)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        device.peaks("cpu")
