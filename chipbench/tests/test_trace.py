"""The trace reduction, on a hand-made trace and on three steps of a
chip trace (``data/trace_bert_base.json.gz``, cut from a ``--trace 1``
run of bert_base.p1_binned128 on a TPU v5 lite)."""

import gzip
import json
import os

import numpy as np

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_hand_made_trace():
    ops = {"/device:TPU:0": [(10, 30, "a"), (20, 40, "b"), (60, 70, "a"),
                             (95, 130, "c")]}
    spans = [(0, 50, "bench.dispatch"), (50, 100, "bench.readback")]
    r = trace.reduce(ops, spans)
    # Window 0-100; busy 10-40, 60-70, 95-100 = 45 ns.
    assert r["window_s"] == 100e-9
    assert abs(r["busy_s"] - 45e-9) < 1e-15
    assert r["device_ops"][0] == ["a", 30e-9]
    gaps = dict((round(t * 1e9), n) for n, t in r["idle_gaps"])
    # Gaps 0-10 (dispatch), 40-60 (half each: dispatch wins the tie by
    # order), 70-95 (readback).
    assert gaps[25] == "bench.readback" and gaps[10] == "bench.dispatch"


def test_no_device_op_reads_nothing():
    assert trace.reduce({"/device:TPU:0": []}, [(0, 5, "bench.x")]) is None
    assert trace.reduce({}, []) is None


def test_chip_trace_against_a_bitmap():
    with gzip.open(os.path.join(DATA, "trace_bert_base.json.gz"), "rt") as f:
        fix = json.load(f)
    ops = {k: [tuple(x) for x in v] for k, v in fix["ops"].items()}
    spans = [tuple(x) for x in fix["spans"]]
    r = trace.reduce(ops, spans)
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    # Independent count: a 100 ns bitmap of the window.
    bins = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for s, e, _ in next(iter(ops.values())):
        s, e = max(s, w0), min(e, w1)
        if e > s:
            bins[int((s - w0) // 100):int(np.ceil((e - w0) / 100))] = True
    bitmap_busy = bins.sum() * 100e-9
    assert r["window_s"] == (w1 - w0) / 1e9
    assert 0 < r["busy_s"] <= r["window_s"]
    assert abs(r["busy_s"] - bitmap_busy) < 0.01 * r["window_s"]
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(n.startswith("bench.") for n, _ in r["idle_gaps"])
