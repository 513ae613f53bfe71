"""The harness end to end on the CPU at a tiny size: a sound run is
correct and prints the contract's keys; each fault the cell can have,
planted under the timed path, makes ``correct`` false; without a chip
the command prints no result."""

import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import run
from conftest import ROOT

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_sound_run_is_correct_with_contract_keys(tiny_root):
    result, compared = run.run("tiny.binned", 2 ** 32 + 11, 3, 0,
                               root=tiny_root, require_tpu=False)
    assert result["correct"], compared
    assert list(result) == RESULT_KEYS + ["compared"]
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p95",
                                      "setup_s"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["attempted"] > 2 and result["failed"] == 0
    for value in result["compared"].values():
        assert set(value) == {"value", "limit"}


def test_traced_run_reports_per_layer_metrics(tiny_root):
    result, _ = run.run("tiny.binned", 12, 2, 1, root=tiny_root,
                        require_tpu=False)
    assert result["correct"]
    assert list(result)[-1] == "compared"
    # On the CPU there is no device plane and no peak: the trace and MFU
    # readers stay silent; the host-side ones read.
    assert set(result["metrics"]) == {"preprocess.mb_per_s",
                                      "loader.input_share",
                                      "loader.padding_efficiency"}


def _unchanged(step, mesh, config):
    from lddl_tpu.models import make_sharded_train_step
    keep = make_sharded_train_step(mesh, config, donate=False)

    def step_fn(state, batch, seed):
        return state, keep(state, batch, seed)[1]
    return step_fn


def _half_batch(step, mesh, config):
    def step_fn(state, batch, seed):
        n = batch["input_ids"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()}, seed)
    return step_fn


def _alter_token(batch):
    batch = dict(batch)
    ids = batch["input_ids"].copy()
    ids[0, 1] = ids[0, 1] + 1
    batch["input_ids"] = ids
    return batch


@pytest.mark.parametrize("fault,hooks", [
    ("state_unchanged", {"step_hook": _unchanged}),
    ("half_batch", {"step_hook": _half_batch}),
    ("token_altered", {"collate_hook": _alter_token}),
])
def test_fault_makes_run_incorrect(tiny_root, fault, hooks):
    result, compared = run.run("tiny.binned", 13, 2, 0, root=tiny_root,
                               require_tpu=False, **hooks)
    assert not result["correct"], (fault, compared)
    failing = [k for k, (v, lim) in compared.items() if v > lim]
    expect = {"state_unchanged": "change_leaf_gap",
              "half_batch": "grad_norm_rel_gap",
              "token_altered": "loader_rows_not_in_shards"}[fault]
    assert expect in failing, compared


def test_refuses_without_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "bert_large.p2_512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=ROOT,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr


def test_layout_check_flags_broken_rows():
    from chipbench.check import layout_faults
    ids = np.array([[2, 10, 11, 3, 12, 3, 0, 0]], np.int32)
    batch = {"input_ids": ids,
             "token_type_ids": np.array([[0, 0, 0, 0, 1, 1, 0, 0]], np.int32),
             "attention_mask": np.array([[1, 1, 1, 1, 1, 1, 0, 0]], np.int32),
             "labels": np.full((1, 8), -1, np.int32)}
    assert not layout_faults(batch, 2, 3)[0].any()
    broken = dict(batch, input_ids=ids.copy())
    broken["input_ids"][0, 3] = 9     # the first [SEP] overwritten
    assert layout_faults(broken, 2, 3)[0].all()
    labelled = dict(batch, labels=batch["labels"].copy())
    labelled["labels"][0, 0] = 5      # a label on [CLS]
    assert layout_faults(labelled, 2, 3)[0].all()
