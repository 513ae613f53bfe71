"""Model scopes and program spans of a traced run (``chipbench/scopes.py``):
the scope join on a CPU-compiled train step's HLO, the reduction on a
hand-made trace and on a cut of a chip trace, the window's step spans
from a span file, and the three readers on hand-made records."""

import collections
import glob
import gzip
import importlib.util
import json
import os
import re

import numpy as np
import pytest

from chipbench import scopes, xplane
from conftest import BENCH

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A profile of two steps of a tiny BERT train step on the CPU, with
    the program's spans armed, and the step's compiled text."""
    import jax
    from lddl_tpu import observability as obs
    from lddl_tpu.loader import to_device_batch
    from lddl_tpu.models import BertConfig, make_sharded_train_step
    from lddl_tpu.models.train import create_train_state
    from lddl_tpu.parallel import make_mesh

    cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=32, dropout_rng_impl="threefry")
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    ids = (np.arange(32, dtype=np.int32).reshape(2, 16) % 100) + 5
    labels = np.full((2, 16), -1, np.int32)
    labels[:, 1::5] = ids[:, 1::5]
    batch = to_device_batch({
        "input_ids": ids, "token_type_ids": np.zeros((2, 16), np.int32),
        "attention_mask": np.ones((2, 16), np.int32),
        "next_sentence_labels": np.zeros((2,), np.int32),
        "labels": labels}, mesh)
    state, _ = create_train_state(cfg, mesh, batch)
    step = make_sharded_train_step(mesh, cfg)
    text = step.lower(state, batch, 0).compile().as_text()
    root = tmp_path_factory.mktemp("cpu_profile")
    trace_dir = str(root / "trace")
    obs.configure(dir=str(root / "metrics"))
    try:
        state, m = step(state, batch, 0)
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, m = step(state, batch, 0)
                with jax.profiler.TraceAnnotation("bench.readback"):
                    float(m["loss"])
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.disable()
    return trace_dir, text


def _text_scopes(text):
    """{fusion instruction: scope} from the compiled text, counted
    independently of ``scopes.module_scopes``: the most common scope of
    the op names in the computation a fusion calls (one level deep)."""
    comps, current = collections.defaultdict(list), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            current = head.group(1)
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        if current and m and "/" in m.group(1):
            comps[current].append(m.group(1).rpartition("/")[0])
    out = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%(\S+) = .* fusion\(.*calls=%([^,\s]+)",
                     line)
        if m and comps.get(m.group(2)):
            ranked = collections.Counter(comps[m.group(2)]).most_common(2)
            if len(ranked) == 1 or ranked[0][1] > ranked[1][1]:
                out[m.group(1)] = ranked[0][0]
    return out


def test_scope_join_on_a_cpu_compiled_step(cpu_profile):
    trace_dir, text = cpu_profile
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    with open(path, "rb") as f:
        raw = f.read()
    modules = {}
    for name, plane in xplane.planes(raw):
        if name == scopes.METADATA_PLANE:
            for module, stat, value in xplane.metadata_stats(plane):
                if stat == scopes.HLO_STAT:
                    modules[module] = scopes.module_scopes(
                        xplane.hlo_instructions(value)[1])
    step = [m for name, m in modules.items()
            if name.startswith("jit_step_fn")]
    assert step, sorted(modules)
    joined = step[0]
    layers = collections.Counter(scopes.layer_of(s) for s in joined.values())
    assert layers["attention"] > 0 and layers["optimizer"] > 0
    assert any("/jvp(loss)" in s for s in joined.values())
    assert any("/layer_0/attention" in s for s in joined.values())
    # A fusion whose called computation has one clear majority scope in
    # the compiled text joins to that scope (nested calls may tip a few).
    pairs = [(joined[name], scope) for name, scope in _text_scopes(text)
             .items() if name in joined]
    assert len(pairs) > 20
    assert all(scopes.layer_of(a) == scopes.layer_of(b) for a, b in pairs)
    assert sum(a == b for a, b in pairs) >= 0.9 * len(pairs)


def test_program_spans_load_from_a_cpu_profile(cpu_profile):
    trace_dir, _ = cpu_profile
    ops, spans = scopes.load(trace_dir)
    assert ops == {}  # no TPU plane on the CPU
    names = collections.Counter(sp[2] for sp in spans)
    assert names["bench.dispatch"] == 2 and names["train_step"] == 2
    steps = sorted(int(sp[3]["step_num"]) for sp in spans
                   if sp[2] == "train_step")
    assert steps == [1, 2]
    for ds, de, name, _ in spans:
        if name == "bench.dispatch":
            inner = [sp for sp in spans if sp[2] == "train_step"
                     and ds <= sp[0] and sp[1] <= de]
            assert len(inner) == 1


def test_hand_made_trace():
    a = "jit(step_fn)/jvp(BertForPreTraining)/layer_0/attention"
    o = "jit(step_fn)/optimizer"
    ops = {"/device:TPU:0": [(10, 30, "fusion.1", a), (30, 40, "fusion.2", o),
                             (60, 70, "copy.3", None),
                             (70, 90, "fusion.4", "jit(step_fn)")]}
    spans = [(0, 50, "bench.dispatch", {}), (5, 45, "train_step",
                                          {"step_num": 4}),
             (50, 100, "bench.readback", {}),
             (52, 58, "loader.prefetch_wait", {"batch": 1}),
             (41, 59, "loader.batch_wait", {"batch": 2})]
    r = scopes.reduce(ops, spans)
    # Busy 10-40 and 60-90: 60 ns; attention 20, optimizer 10.
    assert r["layer_share"] == {"attention": 20 / 60, "optimizer": 10 / 60}
    assert r["resolved_share"] == 30 / 60
    assert r["top_ops"][0] == ["fusion.1", 20e-9, a]
    assert scopes.traced_steps(spans) == (4, 1, 1)
    # Gaps 0-10 (train_step covers half), 40-60 (batch_wait covers 18 of
    # 20 ns, prefetch_wait less than half) and 90-100 (no program span).
    assert {(round(t * 1e9), p, b) for t, p, b in r["idle_gaps"]} == {
        (10, "train_step", "bench.dispatch"),
        (20, "loader.batch_wait", "bench.dispatch"),
        (10, None, "bench.readback")}


def test_covering_prefers_the_deepest_span_over_half():
    spans = [(0, 100, "outer"), (10, 70, "inner"), (20, 30, "small")]
    assert scopes.covering(10, 70, spans) == ("inner", 1.0)
    assert scopes.covering(0, 100, spans) == ("inner", 0.6)
    # No span covers half: no name, and the largest share covered.
    assert scopes.covering(60, 200, spans) == (None, 40 / 140)
    assert scopes.covering(300, 400, spans) == (None, 0.0)


def _record(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


def test_window_steps_and_longest_intervals():
    # Times in microseconds, as the span file writes them.
    records = [_record("train_step", 0, 5e6, step=0),   # set-up, compiles
               _record("train_step", 6e6, 2e3, step=1)]
    records += [_record("train_step", 10e6 + i * 0.3e6, 1e3, step=2 + i)
                for i in range(10)]
    records[-1]["ts"] += 2.5e6           # one stalled interval
    records.append(_record("loader.prefetch_wait", 12.75e6, 2.6e6, batch=9))
    records.append(_record("loader.batch_wait", 12.8e6, 2.5e6, batch=9))
    records.append(_record("loader.epoch", 0, 40e6))
    records.append({"name": "jax.compile", "ph": "i", "ts": 13e6})
    records += [_record("train_step", 30e6 + i, 1e3, step=12 + i)
                for i in range(3)]                   # traced after
    steps = scopes.window_steps(records, first_traced=12, seconds=6)
    assert [r["args"]["step"] for r in steps] == list(range(2, 12))
    longest = scopes.longest_intervals(records, steps, n=2)
    assert longest[0][0] == pytest.approx(2.8e3)
    assert longest[0][1] == "loader.batch_wait"
    assert longest[0][3] == ["jax.compile"]
    assert longest[1][0] == pytest.approx(0.3e3)


@pytest.mark.parametrize("name,key,expected", [
    ("train_step.attention_share", "attention", 41.5),
    ("train_step.optimizer_share", "optimizer", 4.25),
    ("train_step.dispatch_ms", None, 3.5),
])
def test_readers_on_hand_made_records(name, key, expected):
    reader = _reader(name)
    record = {"program_trace": {
        "layer_share": {"attention": 0.415, "optimizer": 0.0425},
        "dispatch_ms": 3.5}}
    assert reader.read(record) == pytest.approx(expected)
    # A program without the scope or the span reads nothing.
    record = {"program_trace": {"layer_share": {}, "dispatch_ms": None}}
    assert reader.read(record) is None
    assert reader.read({"program_trace": None}) is None
    assert (reader.UNIT, reader.BETTER, reader.MOVES) == (
        "ms" if key is None else "%", "lower", "tokens_per_s")


def test_reading_without_a_traced_run_reads_nothing():
    assert scopes.reading({"seconds": 30, "chips": 1}) is None


def test_chip_trace_cut():
    """A cut of a ``--trace 1`` run of bert_large.p2_512 on a TPU v5 lite:
    each op with the scope the HLO join gave it, and every span."""
    with gzip.open(os.path.join(DATA, "trace_bert_large_scopes.json.gz"),
                   "rt") as f:
        fix = json.load(f)
    ops = {k: [tuple(x) for x in v] for k, v in fix["ops"].items()}
    spans = [tuple(x) for x in fix["spans"]]
    r = scopes.reduce(ops, spans)
    assert r["resolved_share"] >= 0.99
    assert 0.2 < r["layer_share"]["attention"] < 0.8
    assert 0.0 < r["layer_share"]["optimizer"] < 0.2
    # Each bench.dispatch holds the program's one train_step span.
    first, dispatches, with_one_step = scopes.traced_steps(spans)
    assert first is not None and dispatches >= 2
    assert with_one_step == dispatches
    names = {sp[2] for sp in spans}
    assert {"loader.prefetch_wait", "loader.h2d", "loader.batch_wait",
            "loader.ipc", "train_step"} <= names
    assert all(p is None or p.startswith(scopes.PROGRAM_PREFIXES)
               for _, p, _ in r["idle_gaps"])
