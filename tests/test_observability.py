"""Observability: registry semantics, tracing, exporters, stage spans
(lint), trace_summary tool, and — the load-bearing part — telemetry
INERTNESS: byte-identical pipeline output with metrics on vs off, and a
near-zero disabled-mode cost guard."""

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from lddl_tpu import observability as obs
from lddl_tpu.observability import exporters, tracing

# The package exports a ``registry()`` accessor under the same name as the
# submodule, so fetch the MODULE explicitly.
reg_mod = importlib.import_module("lddl_tpu.observability.registry")


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Every test starts disabled with an empty registry and leaves no
    env/exporter-thread residue for the rest of the suite."""
    prev_dir = os.environ.get(reg_mod.ENV_DIR)
    prev_rank = os.environ.get(reg_mod.ENV_RANK)
    obs.registry().reset()
    tracing._reset_for_tests()
    os.environ.pop(reg_mod.ENV_DIR, None)
    yield
    exporters.stop_periodic_export()
    for key, prev in ((reg_mod.ENV_DIR, prev_dir),
                      (reg_mod.ENV_RANK, prev_rank)):
        if prev is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prev
    obs.registry().reset()
    tracing._reset_for_tests()


# ------------------------------------------------------------ registry


def test_disabled_helpers_record_nothing():
    assert not obs.enabled()
    obs.inc("x_total", 5)
    obs.set_gauge("g", 1.0)
    obs.observe("h", 2.0)
    assert obs.registry().names() == []


def test_disabled_span_is_shared_noop():
    s1 = obs.span("a")
    s2 = obs.span("b", k=1)
    assert s1 is s2  # shared singleton: no per-call allocation
    with s1:
        pass
    obs.event("e")
    assert tracing.pending_events() == 0


def test_counter_gauge_histogram_semantics(tmp_path):
    obs.configure(dir=str(tmp_path))
    reg = obs.registry()
    c = reg.counter("req_total")
    c.inc()
    c.inc(2, stage="a")
    c.inc(3, stage="a")
    assert c.value() == 1
    assert c.value(stage="a") == 5
    assert c.total() == 6
    c.inc(-7)  # counters are monotonic: negative deltas clamp to 0
    assert c.value() == 1

    g = reg.gauge("fill")
    g.set(0.5)
    g.set(0.25, worker=1)
    assert g.value() == 0.5
    assert g.value(worker=1) == 0.25

    h = reg.histogram("lat")
    for v in (0.001, 0.002, 0.004, 3.0, 0.0):
        h.observe(v)
    st = h.stats()
    assert st["count"] == 5
    assert st["min"] == 0.0 and st["max"] == 3.0
    assert abs(st["sum"] - 3.007) < 1e-9
    # log-bucketed: 0.001->2^-9, 0.002->2^-8, 0.004->2^-7, 3.0->2^2,
    # 0.0 -> the None underflow bucket
    assert sum(st["buckets"].values()) == 5
    assert st["buckets"][None] == 1

    # same name, different type: a genuine instrumentation bug, raises
    with pytest.raises(TypeError):
        reg.gauge("req_total")


def test_registry_thread_safety(tmp_path):
    obs.configure(dir=str(tmp_path))
    c = obs.registry().counter("n_total")

    def worker():
        for _ in range(10000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 80000


def test_enablement_is_env_inherited(tmp_path):
    # The env var is the source of truth, so spawned workers inherit it.
    assert not obs.enabled()
    os.environ[reg_mod.ENV_DIR] = str(tmp_path)
    assert obs.enabled()
    assert obs.metrics_dir() == str(tmp_path)
    del os.environ[reg_mod.ENV_DIR]
    assert not obs.enabled()


# ------------------------------------------------------------- tracing


def test_span_emits_chrome_trace_events(tmp_path):
    obs.configure(dir=str(tmp_path), rank=3)
    with obs.span("stage.outer", shard=7):
        with obs.span("stage.inner"):
            pass
    obs.event("stage.tick", n=1)
    path = obs.flush()
    assert os.path.basename(path) == "trace-rank3-pid{}.jsonl".format(
        os.getpid())
    events = [json.loads(l) for l in open(path)]
    by_name = {e["name"]: e for e in events}
    assert by_name["stage.outer"]["ph"] == "X"
    assert by_name["stage.outer"]["args"] == {"shard": 7}
    assert by_name["stage.inner"]["ph"] == "X"
    assert by_name["stage.tick"]["ph"] == "i"
    # the inner span nests inside the outer one on the same timeline
    outer, inner = by_name["stage.outer"], by_name["stage.inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["dur"] <= outer["dur"]
    assert by_name["process_name"]["ph"] == "M"  # Perfetto metadata


def test_span_records_error_but_propagates(tmp_path):
    obs.configure(dir=str(tmp_path))
    with pytest.raises(ValueError):
        with obs.span("stage.fails"):
            raise ValueError("boom")
    events = [json.loads(l) for l in open(obs.flush())]
    ev = [e for e in events if e["name"] == "stage.fails"][0]
    assert ev["args"]["error"] == "ValueError"


# ----------------------------------------------------------- exporters


def test_prom_and_jsonl_and_summary_exports(tmp_path):
    obs.configure(dir=str(tmp_path), rank=0)
    obs.inc("loader_real_tokens_total", 90)
    obs.inc("loader_padded_slots_total", 100)
    obs.inc("resilience_retry_attempts_total", 2, op="read")
    obs.observe("loader_batch_latency_seconds", 0.004)

    prom = open(obs.export_prom()).read()
    assert "# TYPE loader_real_tokens_total counter" in prom
    assert "loader_real_tokens_total 90" in prom
    assert 'resilience_retry_attempts_total{op="read"} 2' in prom
    assert 'loader_batch_latency_seconds_bucket{le="+Inf"} 1' in prom
    assert "loader_batch_latency_seconds_count 1" in prom

    line = json.loads(open(obs.export_jsonl()).read().splitlines()[-1])
    assert line["metrics"]["loader_real_tokens_total"]["values"][""] == 90

    s = obs.summary()
    assert s["padding_efficiency"] == pytest.approx(0.9)
    assert s["retries"] == 2
    summary_path = obs.write_summary()
    assert json.load(open(summary_path))["real_tokens"] == 90


def test_export_failure_is_inert(tmp_path):
    # An unwritable metrics dir must not raise into the pipeline.
    target = tmp_path / "file"
    target.write_text("not a dir")
    os.environ[reg_mod.ENV_DIR] = str(target / "sub")
    obs.inc("x_total")
    with obs.span("s"):
        pass
    assert obs.export_prom() is None
    assert obs.export_jsonl() is None
    assert obs.write_summary() is None


# ------------------------------------------------- lint: stage spans


def test_every_stage_entry_point_opens_a_top_level_span():
    """The public entry point of each pipeline stage must open its
    top-level span, so traces always carry the stage skeleton. The span
    names are stable API (README table). Migrated from a grep to the AST
    analyzer's stage-span rule (single source of truth — see
    tests/test_analysis.py)."""
    from lddl_tpu import analysis
    from lddl_tpu.analysis.rules import STAGE_SPANS
    assert set(STAGE_SPANS.items()) == {
        ("lddl_tpu/preprocess/runner.py", ("preprocess.run",)),
        ("lddl_tpu/preprocess/steal.py", ("preprocess.gather",
                                          "preprocess.finalize")),
        ("lddl_tpu/balance/balancer.py", ("balance.run",)),
        ("lddl_tpu/loader/dataloader.py", ("loader.epoch",)),
        ("lddl_tpu/ingest/incremental.py", ("ingest.run",)),
    }
    report = analysis.run_check(
        ["lddl_tpu"], rules=analysis.get_rules(["stage-span"]))
    assert report.errors == []
    assert report.new == [], (
        "stage entry points without a top-level span:\n{}".format(
            "\n".join(f.format() for f in report.new)))
    # The rule still fails a stage file that loses its span.
    findings, _ = analysis.analyze_source(
        "def balance_shards(a, b):\n    return None\n",
        "lddl_tpu/balance/balancer.py", analysis.get_rules(["stage-span"]))
    assert [f.rule for f in findings] == ["stage-span"]


# ------------------------------------------------------ trace_summary


def _load_trace_summary():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_summary.py")
    spec = importlib.util.spec_from_file_location("trace_summary", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_summary_tool(tmp_path, capsys):
    obs.configure(dir=str(tmp_path))
    with obs.span("preprocess.run"):
        with obs.span("preprocess.scatter"):
            pass
    with obs.span("loader.epoch"):
        pass
    obs.event("resilience.retry", op="read")
    obs.flush()

    ts = _load_trace_summary()
    spans, instants = ts.collect(ts.resolve_paths([str(tmp_path)]))
    assert spans["preprocess.run"]["count"] == 1
    assert spans["preprocess.scatter"]["total_us"] <= \
        spans["preprocess.run"]["total_us"]
    assert instants["resilience.retry"] == 1
    stages = ts.rollup_stages(spans)
    assert set(stages) == {"preprocess", "loader"}
    assert stages["preprocess"]["count"] == 2

    assert ts.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "per-stage wall time:" in out
    assert "preprocess" in out and "loader" in out
    assert "resilience.retry" in out


# ------------------------------------------ inertness: the real proof


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Demo corpus + wordpiece vocab shared by the inertness tests
    (same recipe as tests/test_loader.py, smaller)."""
    root = tmp_path_factory.mktemp("obs_corpus")
    source = root / "corpus" / "source"
    source.mkdir(parents=True)
    words = ("alpha beta gamma delta epsilon zeta eta theta iota kappa "
             "lambda mu nu xi omicron pi rho sigma tau upsilon").split()
    g = np.random.Generator(np.random.Philox(key=[0, 23]))
    docs = []
    for d in range(48):
        sents = []
        for _ in range(int(g.integers(2, 8))):
            n = int(g.integers(4, 12))
            sents.append(" ".join(
                words[int(g.integers(0, len(words)))] for _ in range(n)
            ).capitalize() + ".")
        docs.append("doc-{} {}".format(d, " ".join(sents)))
    for shard in range(3):
        with open(source / "{}.txt".format(shard), "w") as f:
            for line in docs[shard::3]:
                f.write(line + "\n")
    from lddl_tpu.preprocess import build_wordpiece_vocab, get_tokenizer
    vocab = build_wordpiece_vocab([" ".join(words)] * 3,
                                  str(root / "vocab.txt"), vocab_size=300)
    return {"root": root, "corpus": str(root / "corpus"),
            "vocab": vocab, "tokenizer": get_tokenizer(vocab_file=vocab)}


def _run_pipeline(corpus, out_root, bin_size=None):
    from lddl_tpu.balance import balance_shards
    from lddl_tpu.preprocess import BertPretrainConfig, run_bert_preprocess
    pre = os.path.join(str(out_root), "pre")
    bal = os.path.join(str(out_root), "bal")
    run_bert_preprocess(
        {"wiki": corpus["corpus"]}, pre, corpus["tokenizer"],
        config=BertPretrainConfig(max_seq_length=64, duplicate_factor=2,
                                  masking=True),
        num_blocks=4, sample_ratio=1.0, seed=0, bin_size=bin_size)
    balance_shards(pre, bal, 4)
    return pre, bal


@pytest.fixture(scope="module")
def binned_off(corpus, tmp_path_factory):
    """Telemetry-OFF binned pipeline run (module-shared reference)."""
    assert reg_mod.metrics_dir() is None
    return _run_pipeline(corpus, tmp_path_factory.mktemp("binned_off"),
                         bin_size=16)


@pytest.fixture(scope="module")
def unbinned_off(corpus, tmp_path_factory):
    """Telemetry-OFF unbinned pipeline run (module-shared reference)."""
    assert reg_mod.metrics_dir() is None
    return _run_pipeline(corpus, tmp_path_factory.mktemp("unbinned_off"),
                         bin_size=None)


def _parquet_bytes(d):
    return {
        name: open(os.path.join(d, name), "rb").read()
        for name in sorted(os.listdir(d)) if ".parquet" in name
    }


def _first_batches(path, vocab, n=6, base_seed=11):
    """First ``n`` batches of one epoch. The epoch is DRAINED fully —
    abandoning it mid-stream would leave the worker thread reading shards
    while the caller moves on (e.g. into faults.disarm()/summary()),
    which is exactly the nondeterminism these tests must not have."""
    from lddl_tpu.loader import get_bert_pretrain_data_loader
    loader = get_bert_pretrain_data_loader(
        path, vocab_file=vocab, batch_size=16, num_workers=1,
        shuffle_buffer_size=64, shuffle_buffer_warmup_factor=4,
        base_seed=base_seed)
    out = []
    for i, batch in enumerate(loader):
        if i < n:
            out.append({k: np.asarray(v).copy() for k, v in batch.items()})
    return out


def _assert_batches_equal(a, b):
    assert len(a) == len(b)
    for ba, bb in zip(a, b):
        assert sorted(ba) == sorted(bb)
        for k in ba:
            np.testing.assert_array_equal(ba[k], bb[k])


@pytest.mark.fault
def test_pipeline_bytes_identical_with_observability_on(corpus, binned_off,
                                                        tmp_path):
    """The inertness contract, end to end: preprocess -> balance -> load
    twice in the same environment, telemetry off vs on; shard files and
    the first N batches must be byte-identical (fresh same-env runs, not
    the pinned goldens), and the instrumented run must actually have
    recorded stage telemetry."""
    assert not obs.enabled()
    pre_off, bal_off = binned_off
    batches_off = _first_batches(bal_off, corpus["vocab"])

    obs.configure(dir=str(tmp_path / "metrics"))
    pre_on, bal_on = _run_pipeline(corpus, tmp_path / "on", bin_size=16)
    batches_on = _first_batches(bal_on, corpus["vocab"])
    snap = obs.registry().snapshot()
    trace = obs.flush()
    obs.disable()

    for d_off, d_on in ((pre_off, pre_on), (bal_off, bal_on)):
        off_bytes, on_bytes = _parquet_bytes(d_off), _parquet_bytes(d_on)
        assert sorted(off_bytes) == sorted(on_bytes)
        for name in off_bytes:
            assert off_bytes[name] == on_bytes[name], (
                "shard {} bytes differ with observability enabled".format(
                    name))
    _assert_batches_equal(batches_off, batches_on)

    # ...and the instrumented run was not silently dark:
    assert sum(snap["preprocess_samples_total"]["values"].values()) > 0
    assert sum(snap["loader_batches_total"]["values"].values()) > 0
    assert snap["loader_padding_efficiency"]["values"][""] > 0
    names = [json.loads(l)["name"] for l in open(trace)]
    for required in ("preprocess.run", "preprocess.scatter",
                     "preprocess.gather", "balance.run", "loader.epoch"):
        assert required in names, "missing span {}".format(required)


@pytest.mark.fault
def test_faulted_stream_identical_and_retries_counted(corpus, unbinned_off,
                                                      tmp_path, monkeypatch):
    """Acceptance: with LDDL_TPU_FAULTS armed at p=0.2 EIO the batch
    stream is byte-identical to an uninjected same-env run, and the
    end-of-run summary reports nonzero retry counters."""
    from lddl_tpu.resilience import faults
    _, bal = unbinned_off
    clean = _first_batches(bal, corpus["vocab"], n=8)

    # More attempts + tiny backoff: with p=0.2 per guarded op the chance
    # of exhausting 8 attempts on one op is 0.2^8 ~ 3e-6 (keeps the test
    # deterministic-in-practice without weakening the injected rate).
    monkeypatch.setenv("LDDL_TPU_RETRY_ATTEMPTS", "8")
    monkeypatch.setenv("LDDL_TPU_RETRY_BASE_DELAY_S", "0.001")
    monkeypatch.setenv("LDDL_TPU_RETRY_MAX_DELAY_S", "0.01")
    obs.configure(dir=str(tmp_path / "metrics"))
    # The injector's per-clause RNG mixes in the PID (so spawned workers
    # draw independent streams) — with only a handful of guarded ops in
    # this short load, one unlucky pid can draw zero injections (~0.8^k).
    # Identity must hold on EVERY attempt; for the counter assertions,
    # re-arm with fresh seeds until at least one fault actually fired.
    summary = None
    try:
        for seed in (7, 11, 23, 41, 59):
            faults.arm("*:eio:p=0.2:seed={}".format(seed))
            faulted = _first_batches(bal, corpus["vocab"], n=8)
            _assert_batches_equal(clean, faulted)
            summary = obs.summary()
            if summary["faults_injected"] > 0:
                break
    finally:
        faults.disarm()
    obs.disable()

    assert summary["faults_injected"] > 0
    assert summary["retries"] > 0
    assert summary["retries"] >= summary["faults_injected"]


def test_padding_efficiency_reproduces_bin_gap(corpus, binned_off,
                                               unbinned_off, tmp_path):
    """The paper's headline: binned loading wastes fewer padded slots.
    Measure both layouts with the new gauge on the demo corpus — the
    binned run must come out strictly more token-efficient."""

    def efficiency(bal, fixed):
        obs.registry().reset()
        obs.configure(dir=str(tmp_path / "metrics"))
        from lddl_tpu.loader import get_bert_pretrain_data_loader
        loader = get_bert_pretrain_data_loader(
            bal, vocab_file=corpus["vocab"], batch_size=16, num_workers=1,
            shuffle_buffer_size=64, shuffle_buffer_warmup_factor=4,
            base_seed=11, fixed_seq_lengths=fixed)
        for _ in loader:
            pass
        eff = obs.registry().gauge("loader_padding_efficiency").value()
        obs.disable()
        return eff

    eff_unbinned = efficiency(unbinned_off[1], [64])
    eff_binned = efficiency(binned_off[1], [16, 32, 48, 64])
    assert eff_binned > eff_unbinned, (
        "binned padding efficiency {} not better than unbinned {}".format(
            eff_binned, eff_unbinned))


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "off"])
def test_dropout_mask_tiles_counted_once_per_batch(tmp_path, armed):
    """``attention_dropout_mask_tiles_total`` counts each batch's hashed and
    skipped tiles of the flash forward's dropout-mask hash once, from the
    batch's attention mask, and only while telemetry is armed."""
    from lddl_tpu.loader.dataloader import DataLoader
    from lddl_tpu.ops.flash_attention import dropout_mask_tiles

    lengths = [512, 100, 300, 0, 129, 384, 17]
    masks = [(np.arange(512) < n).astype(np.int32) for n in lengths]

    class Stream:
        """One stream of [512] attention masks."""
        def start_epoch(self):
            return [iter(masks)]

    if armed:
        obs.configure(dir=str(tmp_path))
    loader = DataLoader(Stream(), batch_size=3, collate_fn=lambda rows: {
        "attention_mask": np.stack(rows)})
    batches = [b for b in loader]
    assert len(batches) == 3
    values = obs.registry().counter(
        "attention_dropout_mask_tiles_total").snapshot()["values"]
    if not armed:
        assert values == {}
        return
    want = [sum(x) for x in zip(*(dropout_mask_tiles(b["attention_mask"])
                                  for b in batches))]
    assert values == {"tile=hashed": want[0], "tile=skipped": want[1]}
    # S = 16 query rows by 128 keys: 32 x 4 tiles a row.
    assert sum(want) == len(lengths) * 32 * 4
    assert want[0] == sum(-(-n // 16) * -(-n // 128) for n in lengths)


# ------------------------------------------- disabled-mode cost guard


@pytest.mark.slow
def test_disabled_mode_overhead_near_zero():
    """No-op-mode micro-benchmark guard: a disabled instrumentation call
    must stay within a few dict-lookups of free, so the loader hot path
    can afford it unconditionally (acceptance: < 2% loader throughput
    regression with telemetry off)."""
    import timeit
    assert not obs.enabled()
    n = 200000
    t_inc = timeit.timeit(lambda: obs.inc("x_total"), number=n) / n
    t_span = timeit.timeit(lambda: obs.span("s"), number=n) / n
    t_enabled = timeit.timeit(obs.enabled, number=n) / n
    # Generous CI bound: each disabled call is one env lookup (~0.2us
    # measured); 5us catches an accidental O(real work) regression
    # without flaking on slow shared runners.
    assert t_inc < 5e-6, "disabled inc() costs {:.2e}s/call".format(t_inc)
    assert t_span < 5e-6, "disabled span() costs {:.2e}s/call".format(t_span)
    assert t_enabled < 5e-6
