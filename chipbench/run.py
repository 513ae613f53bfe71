"""Chip benchmark of loader-fed BERT pretraining.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process drives the program's own entry points in the order a
training job calls them, and holds the chip:

1. a corpus generated from the traffic's fixed corpus seed
   (``chipbench/corpus.py``), the same for every run of a cell;
2. ``preprocess_bert_pretrain.main`` and ``balance_shards.main``;
3. ``get_bert_pretrain_data_loader`` (process workers);
4. ``prefetch_to_device`` / ``to_device_batch``;
5. ``make_sharded_train_step`` on a state whose weights the benchmark
   drew from the seed in one jitted call.

``--seed`` draws the loader's order, the weights and the dropout masks;
the corpus and its preprocessing are the cell's own, so every seed trains
on the same samples in another order.

Set-up takes loader batches until every bin's stream has delivered one,
steps on the first batch of each width (and more, up to three steps) and
reads what the comparison needs from those steps (see ``check.py``), so
every shape has run, and nothing compiles, before the window. The window
is a trainer's loop with one step in flight: take the
next device batch, dispatch step i, read back step i-1's loss. A step
completes when its loss is read back; the window counts the steps that
complete inside it. With ``--trace 1`` the loader's counters are armed
and a few more steps after the window are traced with the profiler.

Then the program's state is freed and the plain float32 reference
follows the set-up's steps. The last stdout line is the result as one
JSON object; the numbers compared, each with its limit, are the last
lines on stderr and the last key of that object. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import cell as cells  # noqa: E402
from chipbench.weights import seed_key  # noqa: E402

CACHE_DIR = os.path.join(HERE, ".jax_cache")
# Set-up takes loader batches until every bin width has come; a width
# that has not come in this many is missing from the traffic.
MAX_SETUP_BATCHES = 2000
VOCAB = os.path.join(HERE, "data", "vocab.txt")


class NoChip(RuntimeError):
    pass


def log(name, **fields):
    print("{}: {}".format(name, json.dumps(fields, sort_keys=True)),
          flush=True)


def seed31(seed):
    """The seed as the program's int32 arguments take it."""
    return int(seed) % (2 ** 31 - 1)


# -- set-up -----------------------------------------------------------------

def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (it overrides a directory the environment names), and counters of its
    lookups and hits and of the compiles this process makes."""
    import jax
    import jax.monitoring
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counts = {"lookups": 0, "hits": 0, "compiles": 0}
    names = {"/jax/compilation_cache/compile_requests_use_cache": "lookups",
             "/jax/compilation_cache/cache_hits": "hits"}

    def on_event(event, **_):
        if event in names:
            counts[names[event]] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def prepare_data(cell, work):
    """Corpus, preprocess and balance through the CLIs' ``main``, from
    the traffic's fixed corpus seed. Returns (balanced shard dir,
    {component: seconds}, raw corpus bytes)."""
    from chipbench.corpus import make_corpus
    from lddl_tpu.cli import balance_shards, preprocess_bert_pretrain

    pre = cell.traffic["preprocess"]
    seed = cell.traffic["corpus_seed"]
    times = {}
    t = time.perf_counter()
    corpus = os.path.join(work, "corpus")
    nbytes = make_corpus(corpus, cell.traffic["corpus_mb"], seed)
    times["corpus_s"] = time.perf_counter() - t
    argv = ["--wikipedia", corpus, "--sink", os.path.join(work, "pre"),
            "--vocab-file", VOCAB,
            "--target-seq-length", str(pre["target_seq_length"]),
            "--short-seq-prob", str(pre["short_seq_prob"]),
            "--masked-lm-ratio", str(pre["masked_lm_ratio"]),
            "--duplicate-factor", str(pre["duplicate_factor"]),
            "--sample-ratio", "1.0", "--seed", str(seed31(seed)),
            "--local-workers", "1"]
    if pre["masking"]:
        argv.append("--masking")
    if pre.get("bin_size"):
        argv += ["--bin-size", str(pre["bin_size"])]
    t = time.perf_counter()
    preprocess_bert_pretrain.main(
        preprocess_bert_pretrain.attach_args().parse_args(argv))
    times["preprocess_s"] = time.perf_counter() - t
    bal = os.path.join(work, "bal")
    t = time.perf_counter()
    balance_shards.main(balance_shards.attach_args().parse_args(
        ["--indir", os.path.join(work, "pre"), "--outdir", bal,
         "--num-shards", str(pre["num_shards"])]))
    times["balance_s"] = time.perf_counter() - t
    return bal, times, nbytes


class Feed:
    """The loader through ``prefetch_to_device``, across epochs. Yields
    (host batch, device batch) of the cell's shape only: a worker's last
    partial batch of an epoch is dropped (counted), and a batch whose
    length is outside the bins is counted as failed."""

    def __init__(self, cell, bal, mesh, seed, collate_hook=None):
        from lddl_tpu.loader import (get_bert_pretrain_data_loader,
                                     prefetch_to_device, to_device_batch)
        ld = cell.traffic["loader"]
        lens = cell.seq_lens()
        self.batch_size = ld["batch_size"]
        self.lens = set(lens)
        self.loader = get_bert_pretrain_data_loader(
            bal, vocab_file=VOCAB, dp_rank=0, num_dp_groups=1,
            batch_size=ld["batch_size"], num_workers=ld["num_workers"],
            worker_mode=ld["worker_mode"],
            fixed_seq_lengths=lens if len(lens) > 1 else lens[0],
            base_seed=seed31(seed))
        # The program spawns a bin's process workers at that bin's first
        # batch, and a spawned worker spends tens of seconds importing: a
        # rare bin's first batch would stall the window. Start every
        # bin's pool now, one thread each, while the weights are made;
        # the first ``take`` waits for them.
        self._spawners = [
            threading.Thread(target=dl._ensure_worker_pool, daemon=True)
            for dl in getattr(self.loader, "_dataloaders", None)
            or [self.loader] if getattr(dl, "_worker_mode", "") == "process"]
        for t in self._spawners:
            t.start()
        hook = collate_hook or (lambda b: b)

        def put(batch):
            batch = hook(batch)
            return batch, to_device_batch(batch, mesh)
        self._feed = prefetch_to_device(self.loader, device_put=put,
                                        depth=ld["prefetch_depth"])
        self.epoch = 0
        self.dropped = 0
        self.bad_shape = 0
        self._it = self._batches()

    def _batches(self):
        while True:
            for item in self._feed:
                yield item
            self.epoch += 1

    def _spawned(self):
        for t in self._spawners:
            t.join()
        self._spawners = []

    def take(self):
        self._spawned()
        while True:
            host, dev = next(self._it)
            n, width = host["input_ids"].shape
            if n != self.batch_size:
                self.dropped += 1
            elif width not in self.lens:
                self.bad_shape += 1
            else:
                return host, dev

    def close(self):
        self._spawned()
        self._it.close()
        self.loader.shutdown_workers()


def synthetic_batch(batch_size, width, vocab_size):
    """A batch of the loader's layout and dtypes, to compile a step
    program before the first loader batch of its width comes."""
    import numpy as np
    g = np.random.default_rng(0)
    ids = g.integers(1000, vocab_size, (batch_size, width), dtype=np.int32)
    ids[:, 0], ids[:, -1] = 2, 3
    labels = np.full((batch_size, width), -1, np.int32)
    labels[:, 1::7] = ids[:, 1::7]
    return {"input_ids": ids,
            "token_type_ids": np.zeros((batch_size, width), np.int32),
            "attention_mask": np.ones((batch_size, width), np.int32),
            "next_sentence_labels": np.zeros((batch_size,), np.int32),
            "labels": labels}


def adam_first_moment(opt_state):
    """Adam's first moment in the program's optimizer state."""
    import optax
    found = [s.mu for s in __import__("jax").tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state, found {}".format(
            len(found)))
    return found[0]


class Program:
    """The system under test on one chip: state, step and feed."""

    def __init__(self, cell, seed, bal, devices, step_hook=None,
                 collate_hook=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from lddl_tpu.models import make_sharded_train_step
        from lddl_tpu.parallel import make_mesh
        from chipbench.weights import leaf_norms

        self.cell = cell
        self.seed = seed
        self.mesh = make_mesh({"dp": cell.traffic["dp"]},
                              devices=devices[:cell.chips])
        self.feed = Feed(cell, bal, self.mesh, seed, collate_hook)
        self.key = seed_key(seed)
        rep = NamedSharding(self.mesh, P())
        t = time.perf_counter()
        self.state = jax.jit(cell.make_state, out_shardings=rep)(self.key)
        jax.block_until_ready(self.state)
        self.init_s = time.perf_counter() - t
        step = make_sharded_train_step(self.mesh, cell.bert_config())
        self._program_step = step
        self.step = (step_hook(step, self.mesh, cell.bert_config())
                     if step_hook else step)
        self.step_seed = seed31(seed)
        b1 = cell.traffic["optimizer"]["b1"]
        self._grad1 = jax.jit(lambda s: leaf_norms(jax.tree.map(
            lambda m: m / (1 - b1), adam_first_moment(s.opt_state))))

        def change(params, key):
            from chipbench.weights import make_ref_params, to_program_params
            p0 = to_program_params(cell.config,
                                   make_ref_params(cell.config, key))
            return leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
        self._change = jax.jit(change)
        # Compile (or load from the persistent cache) every program the
        # run will call, on a thread, while the loader's workers start.
        self._preload = threading.Thread(target=self._compile_all,
                                         daemon=True)
        self._preload.start()

    def _compile_all(self):
        from lddl_tpu.loader import to_device_batch
        for width in sorted(self.feed.lens):
            batch = to_device_batch(synthetic_batch(
                self.feed.batch_size, width, self.cell.config["vocab_size"]),
                self.mesh)
            self._program_step.lower(self.state, batch,
                                     self.step_seed).compile()
        self._grad1.lower(self.state).compile()
        self._change.lower(self.state.params, self.key).compile()

    def first_steps(self):
        """Set-up's steps. Loader batches are taken until every width of
        the cell's bins has come, so that every bin's stream has started;
        the first batch of each width is stepped on in the order they
        came, and the next batches too while fewer than three steps would
        be left; the rest are dropped. Then one more loader step is put in
        flight for the window to open on. Returns (readings of the stepped
        batches, their host batches, every host batch taken, the metrics
        and host batch of the step in flight)."""
        import jax
        hosts, losses, gnorms, taken = [], [], [], []
        grad1 = None
        times = self.timings = {}
        todo = set(self.feed.lens)
        t_start = time.perf_counter()
        while todo or len(hosts) < 3:
            host, dev = self.feed.take()
            taken.append(host)
            if len(taken) > MAX_SETUP_BATCHES:
                raise RuntimeError("no batch of width {} in {} batches"
                                   .format(sorted(todo), len(taken) - 1))
            width = host["input_ids"].shape[1]
            if width not in todo and len(hosts) + len(todo) >= 3:
                continue
            todo.discard(width)
            t = time.perf_counter()
            if not hosts:
                times["first_batch_s"] = t - t_start
                self._preload.join()
                times["compiled_s"] = time.perf_counter() - t
            self.state, m = self.step(self.state, dev, self.step_seed)
            hosts.append(host)
            losses.append(float(m["loss"]))
            gnorms.append(float(m["grad_norm"]))
            times["step{}_L{}_s".format(len(hosts), width)] = \
                time.perf_counter() - t
            if len(hosts) == 1:
                grad1 = jax.device_get(self._grad1(self.state))
        change = jax.device_get(self._change(self.state.params, self.key))
        times["batches_taken"] = len(taken)
        times["steps_s"] = time.perf_counter() - t_start
        readings = {"losses": losses, "grad_norms": gnorms,
                    "grad1": grad1, "change": change,
                    "widths": [h["input_ids"].shape[1] for h in hosts]}
        last_host, dev = self.feed.take()
        taken.append(last_host)
        self.state, last = self.step(self.state, dev, self.step_seed)
        return readings, hosts, taken, last, last_host

    def close(self):
        self.feed.close()
        self.state = None


def reference_readings(cell, seed, hosts, precision="f32", half=False):
    """The reference's readings over set-up's host batches (each at its
    own width), from the weights and dropout keys of ``seed``, mapped onto
    the program's leaf names. ``half`` feeds it the first half of each
    batch's rows (the half-batch fault)."""
    import jax
    import numpy as np
    from chipbench.reference.bert import reference_steps
    from chipbench.weights import leaf_norms, to_program_params

    batches = []
    for h in hosts:
        rows = h["input_ids"].shape[0] // (2 if half else 1)
        batches.append({k: np.asarray(v)[:rows] for k, v in h.items()})
    block = cell.traffic["reference_block_rows"]
    losses, gnorms, raw1, g1, change = reference_steps(
        cell.config, cell.traffic["optimizer"], seed_key(seed), batches,
        seed31(seed), precision,
        block_rows=min(block, batches[0]["input_ids"].shape[0]))
    norms = jax.jit(lambda t: leaf_norms(to_program_params(cell.config, t)))
    return {"losses": [float(x) for x in np.asarray(losses)],
            "grad_norms": [float(x) for x in np.asarray(gnorms)],
            "raw_grad1": jax.device_get(norms(raw1)),
            "grad1": jax.device_get(norms(g1)),
            "change": jax.device_get(norms(change))}


# -- the window ---------------------------------------------------------------

def annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def window(program, seconds, last, last_host, on_step=None):
    """The timed loop. Returns the steps that completed in the window as
    (completion time, host batch or None, loss), the window's start, and
    the metrics of the step still in flight when it closed."""
    feed, step, seed = program.feed, program.step, program.step_seed
    done = []
    t0 = time.perf_counter()
    end = t0 + seconds
    prev, prev_host = last, last_host
    while True:
        with annotate("bench.batch_wait"):
            host, dev = feed.take()
        with annotate("bench.dispatch"):
            program.state, m = step(program.state, dev, seed)
        with annotate("bench.readback"):
            loss = float(prev["loss"])
        t = time.perf_counter()
        if t > end:
            break
        done.append((t, prev_host, loss))
        if on_step is not None:
            on_step(len(done))
        prev, prev_host = m, host
    return done, t0, m, host


def traced_steps(program, n_steps, last, trace_dir):
    """``n_steps`` more steps of the same loop under the profiler."""
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        prev = last
        for _ in range(n_steps):
            with annotate("bench.batch_wait"):
                _, dev = program.feed.take()
            with annotate("bench.dispatch"):
                program.state, m = program.step(program.state, dev,
                                                 program.step_seed)
            with annotate("bench.readback"):
                float(prev["loss"])
            prev = m
        with annotate("bench.readback"):
            float(prev["loss"])
    finally:
        jax.profiler.stop_trace()


# -- one run ------------------------------------------------------------------

def percentile(values, q):
    """The q-th percentile (0-100), linear between order statistics."""
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


def judge(cell, numbers):
    """(correct, {name: (value, limit)}): each number the cell's limits
    file names against its limit, ``failed_steps`` against 0."""
    limits = dict(cell.limits, failed_steps=0)
    compared = {k: (numbers[k], limits[k]) for k in sorted(numbers)
                if k in limits}
    return all(v <= lim for v, lim in compared.values()), compared


def run(workload, seed, seconds, trace, root=cells.BENCH_ROOT,
        require_tpu=True, step_hook=None, collate_hook=None, control=None):
    """One run. Returns (result dict, compared {name: (value, limit)}).
    ``control="fp8"`` puts the fp8 reference's readings in the place of
    the program's in the comparison (the control, which must read not
    correct); the hooks plant faults under the timed path."""
    import jax
    devices = jax.devices()
    cell = cells.load(workload, root)
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip("cell {} needs {} TPU chip(s); JAX found {} {}".format(
            workload, cell.chips, len(devices), dev.platform))
    counts = enable_cache()
    from lddl_tpu import observability as obs
    from lddl_tpu.observability import attribution
    from lddl_tpu.preprocess import get_tokenizer
    from chipbench import check, flops, trace as trace_mod

    work = tempfile.mkdtemp(prefix="chipbench_")
    program = None
    try:
        if trace:
            obs.configure(dir=os.path.join(work, "metrics"))
        t = time.perf_counter()
        import_s = t - T_START
        bal, prep_times, corpus_bytes = prepare_data(cell, work)
        t = time.perf_counter()
        program = Program(cell, seed, bal, devices, step_hook, collate_hook)
        init_s = program.init_s
        loader_s = time.perf_counter() - t - init_s
        t = time.perf_counter()
        first, hosts, taken, last, last_host = program.first_steps()
        warm_s = time.perf_counter() - t
        timings = program.timings
        compiles_setup = counts["compiles"]
        stages0 = attribution.stage_seconds() if trace else {}
        done, t0, in_flight, in_flight_host = window(program, seconds, last,
                                                     last_host)
        setup_s = t0 - T_START
        stages1 = attribution.stage_seconds() if trace else {}
        compiles_window = counts["compiles"] - compiles_setup
        reduced = None
        if trace:
            trace_dir = os.path.join(work, "trace")
            traced_steps(program, cell.traffic["trace_steps"], in_flight,
                         trace_dir)
            reduced = trace_mod.reduce(*trace_mod.load(trace_dir),
                                       n_chips=cell.chips)
        else:
            float(in_flight["loss"])
        peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                   for d in devices[:cell.chips]) \
            if dev.platform == "tpu" else 0
        feed_stats = {"epochs_crossed": program.feed.epoch,
                      "partial_batches_dropped": program.feed.dropped,
                      "bad_shape_batches": program.feed.bad_shape}
        program.close()
        program = None
        log("setup", import_s=import_s, init_s=init_s, loader_s=loader_s,
            warm_s=warm_s, total_s=setup_s, **prep_times)
        log("setup_steps", **timings)
        log("compile_cache", dir=CACHE_DIR, compiles_in_setup=compiles_setup,
            compiles_in_window=compiles_window,
            lookups=counts["lookups"], hits=counts["hits"])
        log("feed", **feed_stats)

        # The window's numbers.
        losses = [loss for _, _, loss in done]
        attempted = len(done)
        failed = (sum(1 for x in losses if not math.isfinite(x))
                  + feed_stats["bad_shape_batches"])
        win_hosts = [h for _, h, _ in done if h is not None]
        real = sum(int(h["attention_mask"].sum()) for h in win_hosts)
        slots = sum(int(h["attention_mask"].size) for h in win_hosts)
        useful = sum(flops.batch_train_flops(cell.config, h)
                     for h in win_hosts)
        times = [t0] + [t for t, _, _ in done]
        intervals = [b - a for a, b in zip(times[1:], times[2:])]
        metrics = {}
        if not trace:
            metrics["tokens_per_s"] = {"value": real / seconds,
                                       "unit": "tokens/s"}
            if len(intervals) >= 2:
                metrics["step_ms_p95"] = {
                    "value": 1e3 * percentile(intervals, 95), "unit": "ms"}
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        widths = [h["input_ids"].shape[1] for h in win_hosts]
        log("window", steps=attempted, real_tokens=real, padded_slots=slots,
            seconds=seconds, median_step_ms=1e3 * statistics.median(
                intervals) if intervals else None,
            longest_ms=[1e3 * x for x in sorted(intervals)[-5:]],
            batches_per_length={w: widths.count(w) for w in sorted(
                set(widths))},
            in_flight_loss=float(in_flight["loss"]))

        record = {
            "seconds": seconds,
            "chips": cell.chips,
            "peak_flops": flops.peak_flops(dev.device_kind)
            if dev.platform == "tpu" else None,
            "window_real_tokens": real,
            "window_padded_slots": slots,
            "window_useful_flops": useful,
            "preprocess_s": prep_times["preprocess_s"],
            "corpus_bytes": corpus_bytes,
            "loader_stages": {k: stages1.get(k, 0.0) - stages0.get(k, 0.0)
                              for k in stages1},
            "trace": reduced,
        }
        if trace:
            for entry, reader in cell.metric_readers():
                value = reader.read(record)
                if value is not None:
                    metrics[entry["name"]] = {"value": value,
                                              "unit": entry["unit"]}

        # Correctness, with the program's state freed.
        tok = get_tokenizer(vocab_file=VOCAB)
        cls_id = tok.convert_tokens_to_ids("[CLS]")
        sep_id = tok.convert_tokens_to_ids("[SEP]")
        t = time.perf_counter()
        bad_rows, missing, sampled = check.loader_check(
            taken + win_hosts, bal, cls_id, sep_id, seed31(seed))
        ref = reference_readings(cell, seed, hosts)
        if control == "fp8":
            first = dict(reference_readings(cell, seed, hosts, "fp8"),
                         widths=first["widths"])
        numbers, where = check.train_numbers(first, ref)
        log("reference", seconds=time.perf_counter() - t, control=control,
            widths=first["widths"],
            program_losses=first["losses"], reference_losses=ref["losses"],
            program_grad_norms=first["grad_norms"],
            reference_grad_norms=ref["grad_norms"], rows_sampled=sampled,
            **where)
        numbers["loader_bad_rows"] = bad_rows
        numbers["loader_rows_not_in_shards"] = missing
        numbers["failed_steps"] = failed
        correct, compared = judge(cell, numbers)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if trace and reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        result["compared"] = {k: {"value": v, "limit": lim}
                              for k, (v, lim) in compared.items()}
        return result, compared
    finally:
        if program is not None:
            program.close()
        if trace:
            obs.disable()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result, compared = run(args.workload, args.seed, args.seconds,
                               args.trace)
    except NoChip as e:
        print("chipbench: {}".format(e), file=sys.stderr)
        return 2
    for name, (value, limit) in compared.items():
        print("compared {} {!r} limit {!r}".format(name, value, limit),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
