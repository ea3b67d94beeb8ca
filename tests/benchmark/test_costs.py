"""Operations and bytes from shapes, and the table of peaks."""

import pytest

from benchmark import costs, spec


def test_train_flops_per_token_gpt2_medium():
    config = spec.load_json("configs", "gpt2-medium.json")
    family = spec.load_part("families", config["family"])
    params = family.matmul_params(config)
    assert params == 50257 * 1024 + 24 * 12 * 1024 * 1024
    assert costs.train_flops_per_token(params, 24, 1024, 1024) == \
        6.0 * params + 6.0 * 24 * 1024 * 1024


@pytest.mark.parametrize("kind,products", [("fwd", 2), ("dq", 3),
                                           ("dkv", 4)])
def test_flash_pass(kind, products):
    cost = costs.flash_pass(kind, 12, 16, 1024, 64)
    assert cost["flops"] == products * 12 * 16 * 1024 * 1024 * 64
    assert cost["bytes"] > 4 * 12 * 16 * 1024 * 64 * 2


def test_roofline_is_the_larger_of_the_two():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_seconds({"flops": 1000, "bytes": 10}, peaks) == 10
    assert costs.least_seconds({"flops": 10, "bytes": 1000}, peaks) == 100


def test_decode_bytes_mistral_8_layers():
    config = spec.load_json("configs", "mistral-7b-v0.3-8l.json")
    family = spec.load_part("families", config["family"])
    layer = 4096 * 4096 * 2 + 4096 * 2 * 1024 + 3 * 4096 * 14336
    assert family.decode_weight_params(config) == 8 * layer + 4096 * 32768
    assert family.kv_bytes_per_token(config) == 8 * 2 * 8 * 128 * 2
    assert costs.decode_step_bytes(10, 4, 2.5) == 30


def test_an_unknown_device_kind_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        spec.peaks_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        spec.peaks_for("cpu")
