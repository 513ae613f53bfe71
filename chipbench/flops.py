"""Useful model FLOPs of a BERT pretraining step, and the chips' peaks.

Copied in spirit from ``benchmarks/model_bench.py`` (its peak table and
its per-token count), with two changes: FLOPs are counted per sample at
its real length and masked count, so padding never counts as work, and
the whole count is here, with the benchmark.

Per sample with real length ``l`` and ``m`` masked positions, forward:

    layers * (8 h^2 + 4 h f) * l       QKV, attention output, FFN
  + layers * 4 h l^2                   QK^T and AV
  + (2 h V + 2 h^2) * m                MLM transform and decoder
  + 2 h^2 + 4 h                        pooler and NSP classifier

and a training step is three times the forward (backward = 2x forward).
Recomputation is not counted. Elementwise work (softmax, LayerNorm,
GELU, the optimizer) is not counted either.
"""

import numpy as np

# Peak dense bf16 FLOP/s of one chip, by jax ``device_kind``.
# Source: Google Cloud TPU documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s) and the v4/v5p/v6e pages.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,
}


def peak_flops(device_kind):
    """Peak bf16 FLOP/s of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError("device_kind {!r} has no peak in PEAK_BF16_FLOPS "
                         "({})".format(device_kind,
                                       sorted(PEAK_BF16_FLOPS))) from None


def sample_train_flops(cfg, length, masked):
    """Training FLOPs of one sample (scalars or numpy arrays)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    length = np.asarray(length, dtype=np.float64)
    masked = np.asarray(masked, dtype=np.float64)
    fwd = (n * (8 * h * h + 4 * h * f) * length
           + n * 4 * h * length ** 2
           + (2 * h * v + 2 * h * h) * masked
           + 2 * h * h + 4 * h)
    return 3.0 * fwd


def batch_train_flops(cfg, batch):
    """Useful training FLOPs of a host batch (numpy dict)."""
    lengths = batch["attention_mask"].sum(axis=1)
    masked = (batch["labels"] != -1).sum(axis=1)
    return float(np.sum(sample_train_flops(cfg, lengths, masked)))
