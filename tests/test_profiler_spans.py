"""Program spans on the profiler's timeline and model scopes in the
compiled train step: an armed span is a ``jax.profiler`` annotation as
well as a JSONL record, stage counters are fed from their spans'
durations, a disarmed span costs no annotation, the train step counts its
dispatches, and every part of the step's HLO carries a model scope."""

import glob
import importlib
import json
import os
import re

import numpy as np
import pytest

import jax

from lddl_tpu import observability as obs
from lddl_tpu.observability import attribution, exporters, tracing

reg_mod = importlib.import_module("lddl_tpu.observability.registry")


@pytest.fixture(autouse=True)
def _obs_isolation():
    prev = os.environ.get(reg_mod.ENV_DIR)
    obs.registry().reset()
    tracing._reset_for_tests()
    os.environ.pop(reg_mod.ENV_DIR, None)
    yield
    exporters.stop_periodic_export()
    if prev is None:
        os.environ.pop(reg_mod.ENV_DIR, None)
    else:
        os.environ[reg_mod.ENV_DIR] = prev
    obs.registry().reset()
    tracing._reset_for_tests()


def _records(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, dict(ev.stats), ev.duration_ns))
    return out


def test_armed_span_is_on_the_profile_and_in_the_jsonl(tmp_path):
    obs.configure(dir=str(tmp_path / "metrics"))
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        with obs.span("loader.h2d", batch=3) as span:
            jax.numpy.ones(8).block_until_ready()
        with obs.step_span("train_step", 7):
            pass
    finally:
        jax.profiler.stop_trace()
    events = _host_events(trace_dir)
    h2d = [(stats, dur) for name, stats, dur in events if name == "loader.h2d"]
    assert len(h2d) == 1
    assert str(h2d[0][0]["batch"]) == "3"
    # The profiler's span and the program's measurement agree.
    assert abs(h2d[0][1] / 1e9 - span.seconds) < 0.5 * span.seconds + 1e-3
    step = [stats for name, stats, _ in events if name == "train_step"]
    assert len(step) == 1 and str(step[0]["step_num"]) == "7"
    assert str(step[0]["_r"]) == "1"  # a StepTraceAnnotation
    recs = {r["name"]: r for r in _records(obs.flush())}
    assert recs["loader.h2d"]["args"] == {"batch": 3}
    assert recs["loader.h2d"]["dur"] == pytest.approx(span.seconds * 1e6)
    assert recs["train_step"]["args"] == {"step": 7}


def test_disarmed_span_is_null_and_enters_no_annotation(monkeypatch):
    made = []

    class Recording:
        def __init__(self, name, **kwargs):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Recording)
    assert obs.span("loader.h2d", batch=1) is tracing._NULL_SPAN
    assert obs.step_span("train_step", 1) is tracing._NULL_SPAN
    with obs.span("loader.h2d", batch=1):
        pass
    assert made == []
    os.environ[reg_mod.ENV_DIR] = "/nonexistent-unused"
    with obs.span("loader.h2d", batch=1):
        pass
    with obs.span("loader.epoch", annotate=False):
        pass
    assert made == ["loader.h2d"]
    tracing._reset_for_tests()


@pytest.mark.parametrize("source", ["dataloader", "list"])
def test_stage_counters_equal_their_spans(tmp_path, source):
    """Each stage counter at a span's site is the sum of its spans'
    durations: prefetch_wait and h2d around ``prefetch_to_device``, and
    batch_wait around a DataLoader."""
    from lddl_tpu.loader import prefetch_to_device
    from lddl_tpu.loader.dataloader import DataLoader

    class Stream:
        """A dataset of 10 integer samples in one stream."""
        def start_epoch(self):
            return [iter(range(10))]

    obs.configure(dir=str(tmp_path))
    loader = (DataLoader(Stream(), batch_size=3) if source == "dataloader"
              else [np.arange(3) + i for i in range(4)])
    out = [b for b in prefetch_to_device(loader, device_put=lambda b: b)]
    assert len(out) == 4
    stages = attribution.stage_seconds()
    by_name = {}
    for r in _records(obs.flush()):
        if r["ph"] == "X":
            by_name.setdefault(r["name"], []).append(r)
    checked = {"loader.prefetch_wait": "prefetch_wait", "loader.h2d": "h2d"}
    if source == "dataloader":
        checked["loader.batch_wait"] = "batch_wait"
    for span_name, stage in checked.items():
        assert stages[stage] == pytest.approx(
            sum(r["dur"] for r in by_name[span_name]) / 1e6, rel=1e-9)
    # One wait per batch, and the wait that met the epoch's end.
    assert [r["args"]["batch"] for r in by_name["loader.prefetch_wait"]] \
        == list(range(5))
    assert [r["args"]["batch"] for r in by_name["loader.h2d"]] \
        == list(range(4))


def _tiny_step():
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
    return make_sharded_train_step(mesh, cfg), state, batch


ARITHMETIC = {"add", "subtract", "multiply", "divide", "sqrt", "rsqrt",
              "power", "maximum", "minimum", "exponential", "log"}


def test_compiled_step_has_model_scopes():
    step, state, batch = _tiny_step()
    text = step.lower(state, batch, 0).compile().as_text()
    ops = re.findall(r'^\s*(?:ROOT )?%\S+ = \S+ ([a-z][a-z0-9_-]*)\(.*?'
                     r'op_name="([^"]*)"', text, re.M)
    names = [name for _, name in ops]
    assert len(names) > 1000
    assert any("/jvp(loss)/" in n for n in names)
    assert any("/transpose(jvp(loss))/" in n for n in names)
    assert any("/optimizer/" in n for n in names)
    assert any("/layer_0/attention/" in n for n in names)
    # Unscoped arithmetic: the step's own jit and then the primitive.
    unscoped = [opc for opc, n in ops
                if n.startswith("jit(") and n.count("/") == 1
                and opc in ARITHMETIC]
    assert len(unscoped) < 0.01 * len(names), unscoped[:20]


def test_train_step_counts_dispatches_when_armed(tmp_path):
    from lddl_tpu.models.train import DISPATCHES, DISPATCH_SECONDS
    step, state, batch = _tiny_step()
    state, _ = step(state, batch, 0)  # disarmed: no telemetry at all
    assert obs.registry().names() == []
    obs.configure(dir=str(tmp_path))
    for _ in range(2):
        state, m = step(state, batch, 0)
    float(m["loss"])
    reg = obs.registry()
    assert reg.counter(DISPATCHES).total() == 2
    spans = [r for r in _records(obs.flush()) if r["name"] == "train_step"]
    assert [r["args"]["step"] for r in spans] == [0, 1]
    assert reg.counter(DISPATCH_SECONDS).total() == pytest.approx(
        sum(r["dur"] for r in spans) / 1e6, rel=1e-9)


def test_configure_counts_compiles(tmp_path):
    obs.configure(dir=str(tmp_path))
    jax.jit(lambda x: x * 3 + 1)(np.arange(5.0)).block_until_ready()
    reg = obs.registry()
    assert reg.counter("jax_compiles_total").total() >= 1
    assert reg.counter("jax_compile_seconds_total").total() > 0
    names = [r["name"] for r in _records(obs.flush())]
    assert "jax.compile" in names
