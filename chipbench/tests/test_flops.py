"""The FLOP count against hand counts, and the peak table."""

import numpy as np
import pytest

from chipbench import flops

BASE = {"hidden_size": 768, "intermediate_size": 3072,
        "num_hidden_layers": 12, "vocab_size": 30522}


def test_one_token_one_layer_by_hand():
    cfg = {"hidden_size": 2, "intermediate_size": 8, "num_hidden_layers": 1,
           "vocab_size": 10}
    # QKV + output: 4 matmuls of 2x2 = 4 * 2*2*2 = 32; FFN 2x8 and 8x2 =
    # 2 * 2*2*8 = 64; attention QK^T and AV at l=1: 2 * 2*1*2 = 8; no
    # masked position; pooler 2*2*2 = 8 and classifier 2*2*2 = 8.
    fwd = 32 + 64 + 8 + 0 + 16
    assert flops.sample_train_flops(cfg, 1, 0) == 3 * fwd
    # One masked position adds the transform (2*2*2) and decoder (2*2*10).
    assert flops.sample_train_flops(cfg, 1, 1) == 3 * (fwd + 8 + 40)


def test_bert_base_per_token():
    # ~0.52 GFLOP per token at l=128 without the head: 6 * 110M-ish.
    per_token = flops.sample_train_flops(BASE, 128, 0) / 128
    assert 0.50e9 < per_token < 0.56e9


def test_batch_counts_real_tokens_only():
    mask = np.zeros((2, 16), np.int32)
    mask[0, :10] = 1
    mask[1, :4] = 1
    labels = np.full((2, 16), -1, np.int32)
    labels[0, 3] = 7
    batch = {"attention_mask": mask, "labels": labels}
    expect = (flops.sample_train_flops(BASE, 10, 1)
              + flops.sample_train_flops(BASE, 4, 0))
    assert flops.batch_train_flops(BASE, batch) == pytest.approx(expect)


def test_unknown_device_is_an_error():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError):
        flops.peak_flops("cpu")
