"""Operation and byte counts, checked by hand against the published
sizes, and the peaks table."""
import json

import pytest

import flops
import spec
from model_conf import dims


def _dims(name):
    return dims(json.loads((spec.BENCH / "configs" / f"{name}.json")
                           .read_text()))


def test_stablelm_train_flops_per_token():
    dm = _dims("stablelm-1.6b")
    # per layer: q,k,v,o 4 x 2048^2 and the gated MLP 3 x 2048 x 5632
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert dm.layer_matrix_params() == layer
    dense = 24 * layer + 2048 * 100352
    # six products (two forward, four backward) of 2 ops per multiply-add
    # over the 4096 x 4097 / 2 causal pairs, 32 heads of 64, 24 layers
    attn = 6 * 2 * 64 * (4096 * 4097 // 2) * 32 * 24
    per_step = flops.train_flops_per_step(dm, 1, 4096)
    assert per_step == 6 * dense * 4096 + attn
    # about 9.84 GFLOP a token, an eighth of it causal attention
    assert per_step / 4096 == pytest.approx(9.84e9, rel=2e-3)
    assert flops.attention_flops_train(dm, 1, 4096) / per_step == \
        pytest.approx(0.123, abs=0.002)


def test_param_counts_match_the_published_models():
    assert _dims("stablelm-1.6b").param_count() == 1_644_367_872
    assert _dims("granite-3-2b").param_count() == 2_533_531_648


def test_granite_decode_bytes_at_16_slots_of_2048():
    dm = _dims("granite-3-2b")
    weights = dm.param_count() * 2
    kv = 2 * 8 * 64 * 40 * 16 * 2048 * 2      # k and v, every slot full
    assert flops.decode_bytes(dm, 16 * 2048) == weights + kv
    assert weights / 1e9 == pytest.approx(5.07, abs=0.01)
    assert kv / 1e9 == pytest.approx(2.68, abs=0.01)
    t, bound = flops.least_time(flops.decode_flops(dm, 16, 16 * 2048),
                                flops.decode_bytes(dm, 16 * 2048),
                                flops.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(7.75e9 / 819e9, rel=1e-3)


def test_attention_training_is_compute_bound_at_4k():
    dm = _dims("stablelm-1.6b")
    _, bound = flops.least_time(flops.attention_flops_train(dm, 1, 4096),
                                flops.attention_bytes_train(dm, 1, 4096),
                                flops.peaks("TPU v5 lite"))
    assert bound == "compute"


def test_peaks_are_keyed_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v4")
    with pytest.raises(KeyError):
        flops.peaks("cpu")
