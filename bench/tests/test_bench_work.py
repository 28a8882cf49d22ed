"""Operation and byte counts against hand counts at small shapes."""
import json

import pytest

from bench_fixtures import ROOT

from bench import work

CNN = dict(input_shape=[8, 8, 3], kernel=5, channels=4, fc_width=16,
           num_classes=10)
DEC = dict(hidden_size=8, intermediate_size=32, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, vocab_size=100,
           sliding_window=4)


def test_cnn_counts():
    f = work.cnn_layer_flops(CNN)
    assert f["conv1"] == 2 * 8 * 8 * 4 * 25 * 3
    assert f["conv2"] == 2 * 4 * 4 * 4 * 25 * 4
    assert f["fc1"] == 2 * (2 * 2 * 4) * 16 and f["fc2"] == 2 * 16 * 10
    assert work.cnn_train_flops_per_sample(CNN) == \
        3 * sum(f.values()) - f["conv1"]
    assert work.cnn_param_count(CNN) == \
        75 * 4 + 4 + 100 * 4 + 4 + 16 * 16 + 16 + 160 + 10


def test_paper_cnn_size():
    cfg = json.loads((ROOT / "bench/configs/fedveca-cnn-cifar10.json")
                     .read_text())
    assert work.cnn_param_count(cfg) == 555_178


def test_vecavg_counts():
    assert work.vecavg_bytes(5, 100) == 4 * (500 + 5 + 100 + 5)
    assert work.vecavg_flops(5, 100) == 2000


def test_decoder_counts():
    hd = 2
    per_layer = 8 * (8 + 2 * 4) + 8 * 8 + 2 * 8 * 32
    assert work.decoder_matmul_params(DEC) == 2 * per_layer + 8 * 100
    # the window caps the keys attended
    assert [work.attended(DEC, p) for p in (0, 2, 3, 9)] == [1, 3, 4, 4]
    assert work.decoder_token_flops(DEC, 9) == \
        2 * (2 * per_layer + 800) + 2 * 4 * 4 * hd * 4
    assert work.prefill_flops(DEC, 3) == sum(
        work.decoder_token_flops(DEC, p) for p in range(3)) - 2 * 2 * 8 * 100
    # 4 rows attended at pos 9: 3 read, new row in and out, q, f32 out
    assert work.paged_attn_bytes(DEC, 9) == \
        2 * 3 * 2 * hd * 2 + 4 * 2 * hd * 2 + 4 * hd * 2 + 4 * hd * 4


def test_starcoder2_size():
    cfg = json.loads((ROOT / "bench/configs/starcoder2-3b.json").read_text())
    # the served parameter count without biases, norms and the embedding
    # (the program holds 3,030,279,168 in all)
    assert work.decoder_matmul_params(cfg) == \
        30 * (3072 * (3072 + 512) + 3072 * 3072 + 2 * 3072 * 12288) \
        + 3072 * 49152


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    assert work.roofline_s(197e12, 0, pk) == pytest.approx(1.0)
    assert work.roofline_s(0, 819e9, pk) == pytest.approx(1.0)
