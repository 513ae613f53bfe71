"""Configurations, traffic mixes, cells and per-layer metrics are found
by name: adding one is adding files, and the harness needs no edit."""

import json
import os

from chipbench import cell as cells

EMPTY = {"seconds": 1, "chips": 1, "peak_flops": None,
         "window_real_tokens": 0, "window_padded_slots": 0,
         "window_useful_flops": 0, "preprocess_s": 0, "corpus_bytes": 0,
         "loader_stages": {}, "trace": None}


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def test_new_files_appear_with_no_edit(tiny_root):
    bench = cells.benchmark(tiny_root)
    bench["configs"].append(dict(bench["configs"][0], name="tiny2",
                                 file="chipbench/configs/tiny2.json"))
    bench["workloads"].append({"name": "tiny2.mix", "config": "tiny2",
                               "traffic": "mix", "chips": 1, "why": "t"})
    bench["per_layer"].append({
        "name": "dummy.count", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "loader",
        "moves": "tokens_per_s", "workloads": ["tiny2.mix"]})
    _write(tiny_root, "BENCHMARK.json", bench)
    config = cells.Cell("tiny.binned", tiny_root).config
    _write(tiny_root, "chipbench/configs/tiny2.json",
           dict(config, name="tiny2", hidden_size=32))
    _write(tiny_root, "chipbench/traffic/mix.json",
           dict(cells.Cell("tiny.binned", tiny_root).traffic, corpus_mb=1))
    _write(tiny_root, "chipbench/cells/tiny2.mix.json",
           {"limits": {"loss_rel_gap": 1}})
    _write(tiny_root, "chipbench/metrics/dummy.count.py",
           "UNIT = '1'\n\ndef read(record):\n    return 7.0\n")

    cell = cells.load("tiny2.mix", tiny_root)
    assert cell.config["hidden_size"] == 32
    assert cell.traffic["corpus_mb"] == 1
    assert cell.limits == {"loss_rel_gap": 1}
    readers = dict((m["name"], r) for m, r in cell.metric_readers())
    assert readers["dummy.count"].read(EMPTY) == 7.0
    # A metric that lists its cells stays out of the others.
    names = [m["name"] for m, _ in cells.load("tiny.binned",
                                              tiny_root).metric_readers()]
    assert "dummy.count" not in names


def test_readers_stay_silent_without_data():
    for entry, reader in cells.load("bert_large.p2_512").metric_readers():
        assert reader.read(EMPTY) is None, entry["name"]


def test_benchmark_names_every_reader():
    bench = cells.benchmark()
    here = os.path.join(cells.BENCH_ROOT, "chipbench", "metrics")
    files = {f[:-3] for f in os.listdir(here) if f.endswith(".py")}
    assert files == {m["name"] for m in bench["per_layer"]}
    for w in bench["workloads"]:
        cells.load(w["name"])
