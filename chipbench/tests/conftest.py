"""Fixtures of the benchmark's own tests: a tiny cell in a benchmark
root of its own, on the CPU.

Run from the checkout root:  python -m pytest chipbench/tests -q
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

# Set as the cells' limits are, by calibrate.set_limits from calibrate.py's
# readings of the tiny cell on the CPU (8 program seeds, fp8 control and
# half batch on 4). Lower / upper: loss 2.43e-5 / 1.1e-4, grad norm
# 9.8e-4 / 5.48e-3, median first-gradient leaf 9.81e-4 / 3.61e-3 (fp8
# control); worst first-gradient leaf 0.0136 / 0.376, worst change leaf
# 3.67e-3 / 0.232, median change leaf 6.09e-4 / 0.05 (half batch).
TINY_LIMITS = {"loss_rel_gap": 6.65e-05, "grad_norm_rel_gap": 0.00309,
               "grad1_leaf_gap": 0.124, "grad1_median_leaf_gap": 0.00234,
               "change_leaf_gap": 0.0582, "change_median_leaf_gap": 0.0115,
               "loader_bad_rows": 0, "loader_rows_not_in_shards": 0}


def make_root(path, workers="thread"):
    """A benchmark root holding one tiny cell, ``tiny.binned``: BERT at
    hidden 64 (vocab 30522, the frozen vocab's size), phase-1 traffic on a
    half-MiB corpus, batch 8."""
    os.makedirs(os.path.join(path, "chipbench", "configs"))
    os.makedirs(os.path.join(path, "chipbench", "traffic"))
    os.makedirs(os.path.join(path, "chipbench", "cells"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(path, "chipbench", "metrics"))
    with open(os.path.join(BENCH, "configs", "bert_base.json")) as f:
        config = json.load(f)
    config.update(name="tiny", hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, intermediate_size=128)
    with open(os.path.join(BENCH, "traffic", "p1_binned128.json")) as f:
        traffic = json.load(f)
    traffic["corpus_mb"] = 0.5
    traffic["preprocess"].update(duplicate_factor=1, num_shards=2)
    traffic["loader"].update(batch_size=8, num_workers=1,
                             worker_mode=workers)
    traffic.update(reference_block_rows=4, trace_steps=3)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [dict(bench["configs"][0], name="tiny",
                             file="chipbench/configs/tiny.json")]
    bench["workloads"] = [{"name": "tiny.binned", "config": "tiny",
                           "traffic": "tiny_binned", "chips": 1,
                           "why": "tiny test cell"}]
    files = {"BENCHMARK.json": bench,
             "chipbench/configs/tiny.json": config,
             "chipbench/traffic/tiny_binned.json": traffic,
             "chipbench/cells/tiny.binned.json": {"limits": TINY_LIMITS}}
    for rel, obj in files.items():
        with open(os.path.join(path, rel), "w") as f:
            json.dump(obj, f, indent=1)
    return path


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path / "bench"))


@pytest.fixture(autouse=True)
def _cache_in_tmp(tmp_path_factory, monkeypatch):
    """Keep CPU compiles out of the checkout's chip cache."""
    from chipbench import run
    monkeypatch.setattr(run, "CACHE_DIR",
                        str(tmp_path_factory.getbasetemp() / "jax_cache"))
