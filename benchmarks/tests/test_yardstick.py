import json
import os

import pytest

from benchmarks import harness, yardstick
from benchmarks.families import encoder

HERE = os.path.dirname(os.path.abspath(__file__))


def sizes(name):
    return harness.load_json("configs", name + ".json")


def test_bert_base_required_flop_by_hand():
    # 12 layers x 2 x (4 x 768^2 + 2 x 768 x 3072) = 169.9 MFLOP, attention
    # 12 x 4 x 128 x 768 = 4.7 MFLOP, the pooler and classifier once a
    # sequence; three times that for a trained token: 524 MFLOP
    s = sizes("bert-base")
    fwd = 12 * (2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 128 * 768)
    fwd += (2 * 768 * 768 + 2 * 768 * 2) / 128
    assert encoder.forward_flops_per_token(s, 128) == pytest.approx(fwd)
    assert encoder.train_flops_per_token(s, 128) / 1e6 == pytest.approx(524, abs=0.5)


def test_albert_counts_its_shared_layer_twelve_times():
    a, b = sizes("albert-base"), sizes("bert-base")
    # one parameter set, applied 12 times: the same FLOP as bert-base plus
    # the factorized embedding's projection (2 x 128 x 768 a token)
    extra = 2 * 128 * 768
    assert encoder.forward_flops_per_token(a, 128) == pytest.approx(
        encoder.forward_flops_per_token(b, 128) + extra)
    one_layer = dict(a, num_hidden_layers=1)
    assert (encoder.forward_flops_per_token(a, 128)
            > 11 * encoder.forward_flops_per_token(one_layer, 128))
    # nothing like 6 x parameters x tokens: 11.7 M parameters would give 70 MFLOP
    assert encoder.train_flops_per_token(a, 128) > 500e6


def test_embedding_lookups_count_nothing():
    s = sizes("bert-base")
    assert (encoder.forward_flops_per_token(dict(s, vocab_size=10 * s["vocab_size"]), 128)
            == encoder.forward_flops_per_token(s, 128))


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        yardstick.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        yardstick.mfu_pct(1e5, sizes("bert-base"), 128, "cpu")
    assert yardstick.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_mfu_of_the_bring_up_line():
    # 121.8 k tokens/s x 524 MFLOP = 63.8 TFLOP/s = 32.4% of 197
    assert yardstick.mfu_pct(121.8e3, sizes("bert-base"), 128, "TPU v5 lite") == pytest.approx(32.4, abs=0.1)


def test_roofline_says_which_peak_bounds():
    # 197 TFLOP in 2 s with little traffic: compute-bound, half the peak
    share, bound = yardstick.roofline(197e12, 1e9, 2.0, "TPU v5 lite")
    assert bound == "compute" and share == pytest.approx(50.0)
    # 819 GB in 4 s with few operations: memory-bound, a quarter of the peak
    share, bound = yardstick.roofline(1e9, 819e9, 4.0, "TPU v5 lite")
    assert bound == "memory" and share == pytest.approx(25.0)
    # nothing measured: nothing reported, never 0
    assert yardstick.roofline(1e9, 1e9, 0.0, "TPU v5 lite") is None


def test_the_count_moved_house_and_not_value():
    """The encoder family's count is the old yardstick's to the last digit
    (PERF.md section 4: 523.8 and 524.4 MFLOP a trained token), and so is
    the share of the peak at PR 26's rates."""
    assert encoder.forward_flops_per_token(sizes("bert-base"), 128) == 174597144.0
    assert encoder.train_flops_per_token(sizes("bert-base"), 128) == 523791432.0
    assert encoder.forward_flops_per_token(sizes("albert-base"), 128) == 174793752.0
    assert encoder.train_flops_per_token(sizes("albert-base"), 128) == 524381256.0
    assert yardstick.mfu_pct(145899.0, sizes("bert-base"), 128, "TPU v5 lite") == 38.79220616110051
    assert yardstick.mfu_pct(145899.0, sizes("albert-base"), 128, "TPU v5 lite") == 38.83588876606294


def test_a_frozen_matrix_has_no_weight_gradient_product():
    """The rule on two matrices by hand: y = (x W1) W2 at 8 -> 16 -> 4, W1
    frozen and W2 trained. Forward 2 x 8 x 16 + 2 x 16 x 4 = 384 FLOP a
    token. W2: forward, activation gradient, weight gradient = 3 x 128;
    W1: forward and activation gradient only = 2 x 256; with a rank-2
    adapter on W1 (a: 8 x 2, b: 2 x 16, both trained) 3 x (32 + 64) more."""
    w1, w2 = 2 * 8 * 16, 2 * 16 * 4
    assert yardstick.train_flops(w1, trained=False) + yardstick.train_flops(w2) == 2 * 256 + 3 * 128
    assert yardstick.train_flops(w1 + w2) == 3 * 384            # full fine-tuning: 1152
    adapters = yardstick.train_flops(2 * 8 * 2 + 2 * 2 * 16)
    assert adapters == 288
    lora = yardstick.train_flops(w1, trained=False) + adapters + yardstick.train_flops(w2)
    assert lora == 512 + 288 + 384 < yardstick.train_flops(w1 + w2) + adapters
