"""A cell of the benchmark, found by name.

``BENCHMARK.json`` names each cell (workload) as a configuration and a
traffic mix. Everything else is found from those names, so that a new
cell, configuration, traffic mix or per-layer metric needs only new
files:

- ``<file>`` of the configuration entry: the model's sizes as run;
- ``chipbench/traffic/<traffic>.json``: corpus, preprocess flags, loader
  settings, per-chip batch, optimizer;
- ``chipbench/cells/<workload>.json``: the limits of the correctness
  comparison and the readings they were set from;
- ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.
"""

import importlib.util
import json
import os

BENCH_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark(root=BENCH_ROOT):
    return _read_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One workload: its configuration, traffic, limits and metrics."""

    def __init__(self, workload, root=BENCH_ROOT):
        bench = benchmark(root)
        entries = {w["name"]: w for w in bench["workloads"]}
        if workload not in entries:
            raise KeyError("no workload {!r} in BENCHMARK.json (have {})"
                           .format(workload, sorted(entries)))
        self.root = root
        self.name = workload
        self.entry = entries[workload]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = _read_json(os.path.join(
            root, configs[self.entry["config"]]["file"]))
        self.traffic = _read_json(os.path.join(
            root, "chipbench", "traffic", self.entry["traffic"] + ".json"))
        self.limits = _read_json(os.path.join(
            root, "chipbench", "cells", workload + ".json"))["limits"]
        self.chips = int(self.entry["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]

    # -- the model ---------------------------------------------------------

    def bert_config(self):
        from lddl_tpu.models import BertConfig
        c = self.config
        return BertConfig(
            vocab_size=c["vocab_size"],
            hidden_size=c["hidden_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            intermediate_size=c["intermediate_size"],
            max_position_embeddings=c["max_position_embeddings"],
            type_vocab_size=c["type_vocab_size"],
            hidden_dropout=c["hidden_dropout_prob"],
            attention_dropout=c["attention_probs_dropout_prob"],
            layer_norm_eps=c["layer_norm_eps"],
            initializer_range=c["initializer_range"],
            dropout_rng_impl=c["dropout_rng_impl"],
            attention_impl=self.traffic["attention_impl"])

    def optimizer(self):
        from lddl_tpu.models.train import make_optimizer
        return make_optimizer(**self.traffic["optimizer"])

    def make_state(self, key):
        """The program's TrainState at step 0 with the benchmark's weights
        from ``key``. Traceable: the harness jits it into one call."""
        import jax.numpy as jnp
        from lddl_tpu.models.train import TrainState
        from .weights import make_ref_params, to_program_params
        tx = self.optimizer()
        params = to_program_params(self.config,
                                   make_ref_params(self.config, key))
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), tx=tx)

    def seq_lens(self):
        loader = self.traffic["loader"]
        lens = loader.get("fixed_seq_lengths")
        if lens is None:
            lens = [self.traffic["preprocess"]["target_seq_length"]]
        return list(lens)

    # -- per-layer metrics -------------------------------------------------

    def metric_readers(self):
        """[(BENCHMARK.json entry, reader module)] for this cell."""
        out = []
        for m in self.per_layer:
            path = os.path.join(self.root, "chipbench", "metrics",
                                m["name"] + ".py")
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + m["name"].replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            out.append((m, mod))
        return out


def load(workload, root=BENCH_ROOT):
    return Cell(workload, root)
