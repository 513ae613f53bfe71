"""Batch iteration: worker-threaded or worker-process DataLoader +
synchronized Binned wrapper.

Reference parity: lddl/torch/dataloader.py. The reference rides
torch.utils.data.DataLoader worker *processes*
(lddl/torch/bert.py:386, persistent_workers=True); we offer both:

- ``worker_mode="thread"`` (default): the hot per-sample work (pyarrow
  decode, numpy collate) releases the GIL, threads share the batch memory
  with the consumer (no pickle copy), and determinism is trivial.
- ``worker_mode="process"``: PERSISTENT spawned workers (the reference's
  persistent_workers=True) — spawned once, each epoch is a command; the
  worker rebuilds its stream from the dataset's pure (seed, epoch, dp,
  worker) definition, no state handoff. Batches cross the process
  boundary pickled, so this wins only when collate cost dominates the
  copy (GIL-bound tokenize-heavy transforms on many-core hosts). Both
  modes produce identical batches in identical order (tested).
"""

import itertools
import queue
import threading

from .. import observability as obs
from ..resilience import faults
from ..utils import rng as lrng
from ..utils.logging import DatasetLogger


class _EpochObserver:
    """Per-batch telemetry with the registry handles resolved ONCE per
    epoch (each ``obs.inc(name)`` is a registry dict lookup under a lock;
    at tens of thousands of batches/s that was the telemetry hot-path
    cost): the per-batch work is cached-handle increments only, and the
    padding-efficiency gauge (real tokens / padded slots, the paper's
    headline quantity) is folded into the end-of-epoch summary instead of
    being recomputed per batch. Read-only on the batch; constructed only
    when telemetry is enabled."""

    __slots__ = ("_latency", "_batches", "_samples", "_real", "_padded",
                 "_gauge", "_mask_tiles")

    def __init__(self):
        reg = obs.registry()
        self._latency = reg.histogram("loader_batch_latency_seconds")
        self._batches = reg.counter("loader_batches_total")
        self._samples = reg.counter("loader_samples_total")
        self._real = reg.counter("loader_real_tokens_total")
        self._padded = reg.counter("loader_padded_slots_total")
        self._gauge = reg.gauge("loader_padding_efficiency")
        self._mask_tiles = reg.counter("attention_dropout_mask_tiles_total")

    def batch(self, batch, dt_s):
        self._latency.observe(dt_s)
        self._batches.inc()
        if isinstance(batch, dict) and "attention_mask" in batch:
            mask = batch["attention_mask"]
            self._samples.inc(len(mask))
            self._real.inc(int(mask.sum()))
            self._padded.inc(int(mask.size))
            count_dropout_mask_tiles(self._mask_tiles, mask)
        elif isinstance(batch, (list, tuple)):
            self._samples.inc(len(batch))

    def finish(self):
        """End-of-epoch gauge update from the process-cumulative totals —
        the same value the per-batch recomputation converged to."""
        padded = self._padded.total()
        if padded:
            self._gauge.set(self._real.total() / padded)


def count_dropout_mask_tiles(counter, attention_mask):
    """Count the attention-dropout mask tiles the single-block flash
    forward hashes and skips for one batch's [B, L] ``attention_mask``
    (``tile="hashed"|"skipped"``)."""
    from ..ops.flash_attention import dropout_mask_tiles
    hashed, skipped = dropout_mask_tiles(attention_mask)
    counter.inc(hashed, tile="hashed")
    counter.inc(skipped, tile="skipped")


def _timed_collate(collate):
    """Wrap a collate once per epoch with the ``collate`` stage
    accumulator (attribution). One wrap, zero per-batch branches; the
    wrapped function is semantically identical, so batch bytes are
    untouched. Only called when telemetry is enabled."""
    import time
    from ..observability import attribution
    stage = attribution.stage_counter()
    pc = time.perf_counter

    def timed(batch, _c=collate, _s=stage, _pc=pc):
        t0 = _pc()
        out = _c(batch)
        _s.inc(_pc() - t0, stage="collate")
        return out

    return timed


def _stream_one_epoch(dataset, worker_idx, epoch, batch_size, collate_fn,
                      rng_spec, out_q):
    """Stream one epoch's collated batches into the queue.

    Batches are serialized HERE (one framed bytes payload per batch via
    qserde: pickle protocol 5 with out-of-band numpy buffers), not by
    mp.Queue's feeder thread — a feeder-thread pickling error would
    silently drop the batch and still deliver a clean 'end'; serializing
    in this try block turns it into a forwarded error instead."""
    from . import qserde

    try:
        if rng_spec is not None:
            g = lrng.sample_rng(*rng_spec)
            collate = lambda b: collate_fn(b, g=g)  # noqa: E731
        else:
            collate = collate_fn or (lambda b: b)
        if obs.enabled():
            # Spawned workers inherit LDDL_TPU_METRICS_DIR: their collate
            # stage seconds land in the child registry and reach the
            # fleet rollup via the colocated per-pid exports.
            collate = _timed_collate(collate)

        def put_batch(b):
            # Chaos-harness site: a "worker:kill" fault SIGKILLs this
            # worker here, before the batch is enqueued (supervision in
            # DataLoader._iter_process restarts + replays it).
            faults.fault_point("worker", "w{}".format(worker_idx))
            out_q.put(("batch", qserde.encode(collate(b))))

        batch = []
        for sample in dataset.worker_stream(epoch, worker_idx):
            batch.append(sample)
            if len(batch) == batch_size:
                put_batch(batch)
                batch = []
        if batch:
            put_batch(batch)
        out_q.put(("end", None))
    except BaseException:  # noqa: BLE001 - forwarded to consumer
        import traceback
        out_q.put(("error", traceback.format_exc()))


def _persistent_worker_main(dataset, worker_idx, batch_size, collate_fn,
                            cmd_q, out_q):
    """Persistent process-worker loop (the reference's
    persistent_workers=True, lddl/torch/bert.py:386): spawn once, then
    serve ("epoch", n, rng_spec) commands until ("stop",). The worker's
    pickled dataset copy never advances its epoch counter — every stream
    is the pure function dataset.worker_stream(epoch, w)."""
    while True:
        cmd = cmd_q.get()
        if cmd[0] == "stop":
            return
        _, epoch, rng_spec = cmd
        _stream_one_epoch(dataset, worker_idx, epoch, batch_size,
                          collate_fn, rng_spec, out_q)


class DataLoader:
    """Iterates a ParquetDataset in batches.

    Epoch advance happens on ``__iter__`` (via dataset.start_epoch), like
    the reference's IterableDataset. Worker w collates its own stream into
    batches; the loader serves worker batches round-robin, so batch order
    is a pure function of (base_seed, epoch).
    """

    def __init__(self, dataset, batch_size, collate_fn=None, prefetch=2,
                 worker_mode="thread"):
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if worker_mode not in ("thread", "process"):
            raise ValueError("worker_mode must be thread|process")
        # A loader process armed ONLY through the env (LDDL_TPU_FLEET_DIR,
        # the documented equivalent of --fleet-telemetry) never calls
        # configure() or record(), so nothing would start the heartbeat or
        # point the metrics spool — and every obs.enabled() gate below
        # would read False. Kick the fleet once here, before any gate.
        try:
            from ..observability import fleet
            fleet.ensure_started()
        except Exception:  # noqa: BLE001 - telemetry must stay inert
            pass
        if worker_mode == "process":
            worker_mode = self._check_process_mode(dataset)
        self.dataset = dataset
        self.batch_size = batch_size
        self._user_collate = collate_fn  # None = raw samples (picklable)
        self._collate_fn = collate_fn or (lambda samples: samples)
        self._prefetch = max(1, prefetch)
        self._worker_mode = worker_mode
        self._procs = self._cmd_qs = self._out_qs = None
        self._local_qs = self._pump_stops = None
        self._finalizer = None
        self._pool_gen = 0
        self._epoch_active = False
        # Generation-aware datasets bump files_version when an epoch
        # boundary picks up newly published shards; persistent process
        # workers hold a PICKLED dataset copy, so a version change forces
        # a pool respawn (workers re-pickle the refreshed dataset).
        self._seen_files_version = getattr(dataset, "files_version", 0)
        # Cumulative process-mode IPC cost: framed qserde bytes and
        # batches received over this loader's lifetime (benchmarks read
        # these to report pickle-bytes/batch; always 0 in thread mode).
        self.queue_bytes = 0
        self.queue_batches = 0

    @staticmethod
    def _check_process_mode(dataset):
        """Process workers only pay off when spare cores exist: on a
        single-core host every recorded measurement shows them losing
        badly to thread mode (LOADER_BENCH.json w4proc rows: 66-415
        samples/s vs ~16k — spawn, pickle and queue costs with zero
        parallel upside), so fall back to threads with a warning instead
        of silently running a known-pathological configuration."""
        import os
        if os.environ.get("LDDL_TPU_FORCE_PROCESS_WORKERS"):
            return "process"  # tests / benchmarks of the mode itself
        from ..utils.cpus import loader_io_threads, pool_cpu_budget
        # Each worker process runs its own shard fetch/decode-ahead
        # threads (loader/shardcache.py); budget for them so "spare
        # cores" means cores actually left over, not the raw count.
        io_threads = loader_io_threads()
        budget = pool_cpu_budget(reserve=io_threads)
        if budget < 2:
            logger = getattr(dataset, "logger", None)
            msg = ("worker_mode='process' with a {}-CPU budget (usable "
                   "cores minus {} shard-I/O thread(s) per stream): "
                   "falling back to thread mode (process workers "
                   "measured 40-240x slower without spare cores — "
                   "LOADER_BENCH.json)".format(budget, io_threads))
            if logger is not None:
                try:
                    logger.to("rank").warning(msg)
                except Exception:
                    pass
            import warnings
            warnings.warn(msg, stacklevel=4)
            return "thread"
        return "process"

    @property
    def num_batches_per_worker(self):
        num_files_per_worker = (self.dataset.num_files_per_group //
                                self.dataset.num_workers)
        samples_per_worker = (self.dataset.num_samples_per_file *
                              num_files_per_worker)
        return (samples_per_worker - 1) // self.batch_size + 1

    def __len__(self):
        """Batches per epoch, accounting for each worker's final partial
        batch. (ref: lddl/torch/dataloader.py:96-105)"""
        return self.num_batches_per_worker * self.dataset.num_workers

    # Domain tag for per-worker collation RNG streams (dynamic masking) —
    # distinct from the shuffle-buffer streams so the two never correlate.
    _COLLATE_RNG_TAG = 0xC011

    def _worker_loop(self, stream, out_q, stop, collate):
        def put(item):
            # Bounded-queue put that gives up if the consumer abandoned the
            # epoch (e.g. partial iteration) so threads never leak blocked.
            while not stop.is_set():
                try:
                    out_q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        if obs.enabled():
            collate = _timed_collate(collate)
        try:
            batch = []
            for sample in stream:
                batch.append(sample)
                if len(batch) == self.batch_size:
                    if not put(("batch", collate(batch))):
                        return
                    batch = []
            if batch:
                if not put(("batch", collate(batch))):
                    return
            put(("end", None))
        except BaseException as e:  # noqa: BLE001 - forwarded to consumer
            put(("error", e))

    def _bind_collate(self, worker_idx):
        """Bind a per-(epoch, dp group, worker) RNG stream into the collate
        when it asks for one (dynamic masking)."""
        if not getattr(self._collate_fn, "needs_rng", False):
            return self._collate_fn
        ds = self.dataset
        g = lrng.sample_rng(ds.base_seed, self._COLLATE_RNG_TAG, ds.epoch,
                            ds.dp_rank, worker_idx)
        return lambda batch: self._collate_fn(batch, g=g)

    def _ensure_worker_pool(self):
        """Spawn the persistent worker pool once (reference:
        persistent_workers=True); respawned automatically after a failed
        or abandoned epoch tears it down, or when a worker died while
        idle between epochs (OOM killer etc.)."""
        if self._procs is not None:
            if all(p.is_alive() for p in self._procs):
                return
            self.shutdown_workers()
        import multiprocessing
        import weakref
        ctx = multiprocessing.get_context("spawn")
        ds = self.dataset
        n = ds.num_workers
        self._cmd_qs = [ctx.Queue() for _ in range(n)]
        self._out_qs = [ctx.Queue(maxsize=self._prefetch) for _ in range(n)]
        procs = [
            ctx.Process(
                target=_persistent_worker_main,
                args=(ds, w, self.batch_size, self._user_collate,
                      self._cmd_qs[w], self._out_qs[w]),
                daemon=True)
            for w in range(n)
        ]
        try:
            for p in procs:
                p.start()
        except BaseException:
            for p in procs:
                if p.is_alive():
                    p.terminate()
            raise
        self._procs = procs
        self._local_qs = [None] * n
        self._pump_stops = [None] * n
        for w in range(n):
            self._start_pump(w)
        self._pool_gen += 1
        # GC safety net: daemon workers die with the interpreter anyway,
        # but a finalizer releases them as soon as the loader is dropped.
        self._finalizer = weakref.finalize(
            self, DataLoader._shutdown_procs, procs)

    @staticmethod
    def _shutdown_procs(procs, grace_s=0):
        if grace_s:
            for p in procs:
                if p.pid is not None:
                    p.join(timeout=grace_s)
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            if p.pid is not None:
                p.join(timeout=5)

    def shutdown_workers(self):
        """Stop persistent process workers (no-op in thread mode):
        graceful ("stop",) command with a short grace period, then
        terminate stragglers."""
        if self._procs is None:
            return
        for q in self._cmd_qs:
            try:
                q.put(("stop",))
            except Exception:  # noqa: BLE001 - queue may be broken
                pass
        self._shutdown_procs(self._procs, grace_s=2)
        if self._pump_stops is not None:
            for stop in self._pump_stops:
                if stop is not None:
                    stop.set()
        if self._finalizer is not None:
            self._finalizer.detach()
        self._procs = self._cmd_qs = self._out_qs = None
        self._local_qs = self._pump_stops = None
        self._finalizer = None

    # A dead process worker (OOM killer, preemption, segfault in native
    # code) is restarted at most this many times per worker per epoch;
    # the second death of the same worker fails fast with a named error.
    _MAX_WORKER_RESTARTS = 1
    # How long a queue get waits before re-checking worker liveness.
    _POLL_TIMEOUT_S = 5.0

    @staticmethod
    def _pump_worker_queue(mp_q, local_q, stop):
        """Forward worker output from the mp queue onto an in-process
        queue from a SACRIFICIAL daemon thread. mp.Queue.get's timeout
        covers only the initial poll — once a frame header arrives, the
        payload read blocks until complete, and a frame torn by a SIGKILL
        mid-put never completes (the parent holds a write end, so no EOF
        either). The training loop therefore must never read the pipe
        directly: if this thread wedges on a torn frame, the supervisor
        simply abandons it with the dead worker's queues. The local queue
        is size-1 so the mp queue's prefetch bound still backpressures
        the worker."""
        while not stop.is_set():
            try:
                item = mp_q.get(timeout=0.5)
            except queue.Empty:
                continue
            except Exception:  # noqa: BLE001 - torn pipe / unpickling
                item = ("pump_torn", None)
            while not stop.is_set():
                try:
                    local_q.put(item, timeout=0.5)
                    break
                except queue.Full:
                    continue
            if item[0] == "pump_torn":
                return

    def _start_pump(self, w):
        stop = threading.Event()
        local_q = queue.Queue(maxsize=1)
        t = threading.Thread(target=self._pump_worker_queue,
                             args=(self._out_qs[w], local_q, stop),
                             daemon=True)
        t.start()
        self._local_qs[w] = local_q
        self._pump_stops[w] = stop

    def _restart_worker(self, w):
        """Replace dead worker ``w`` with a fresh spawn on FRESH queues
        and a fresh pump thread (a SIGKILL mid-``put`` can leave a torn
        frame in the old queue — possibly with the old pump wedged on it —
        so both are abandoned wholesale). The pool lists mutate in place
        so the GC finalizer and any local aliases track the replacement."""
        import multiprocessing
        ctx = multiprocessing.get_context("spawn")
        self._pump_stops[w].set()
        for q in (self._cmd_qs[w], self._out_qs[w]):
            try:
                q.close()
                q.cancel_join_thread()
            except Exception:  # noqa: BLE001 - queue may already be broken
                pass
        self._cmd_qs[w] = ctx.Queue()
        self._out_qs[w] = ctx.Queue(maxsize=self._prefetch)
        p = ctx.Process(
            target=_persistent_worker_main,
            args=(self.dataset, w, self.batch_size, self._user_collate,
                  self._cmd_qs[w], self._out_qs[w]),
            daemon=True)
        p.start()
        old = self._procs[w]
        self._procs[w] = p
        self._start_pump(w)
        try:
            old.join(timeout=1)
        except Exception:  # noqa: BLE001
            pass

    def _handle_worker_death(self, w, epoch, rng_spec, restarts, served,
                             skip):
        """Supervision policy: restart a dead worker once and replay its
        pure (seed, epoch, dp, worker) stream deterministically — the
        first ``served[w]`` batches are discarded unopened, so the
        consumer-visible batch sequence is unchanged. A second death of
        the same worker raises a named error instead of looping."""
        import warnings
        code = self._procs[w].exitcode
        restarts[w] += 1
        obs.inc("loader_worker_deaths_total", worker=w)
        if restarts[w] > self._MAX_WORKER_RESTARTS:
            obs.event("loader.worker_failed", worker=w, exit_code=code)
            raise RuntimeError(
                "loader worker {} died again after a restart (last exit "
                "code {}); failing fast — a worker that keeps dying needs "
                "a human, not another retry".format(w, code))
        obs.inc("loader_worker_restarts_total", worker=w)
        obs.event("loader.worker_restart", worker=w, exit_code=code,
                  replayed_batches=served[w])
        warnings.warn(
            "loader worker {} died (exit code {}); restarting it once and "
            "replaying its deterministic stream (discarding {} already-"
            "served batch(es))".format(w, code, served[w]), stacklevel=3)
        self._restart_worker(w)
        self._cmd_qs[w].put(("epoch", epoch, rng_spec))
        skip[w] = served[w]

    def _iter_process(self):
        from . import qserde
        ds = self.dataset
        epoch = ds.advance_epoch()
        version = getattr(ds, "files_version", 0)
        if version != self._seen_files_version:
            # The dataset picked up a new generation at this boundary:
            # the workers' pickled copies are stale — respawn the pool so
            # every worker re-pickles the refreshed file list.
            self._seen_files_version = version
            self.shutdown_workers()
        rng = getattr(self._collate_fn, "needs_rng", False)
        if self._epoch_active:
            # A previous epoch's iterator is still mid-stream on the
            # shared queues (partially consumed and kept alive): its
            # leftovers would masquerade as this epoch's data. Tear down
            # and respawn for a clean slate.
            self.shutdown_workers()
            self._epoch_active = False
        self._ensure_worker_pool()
        gen = self._pool_gen
        self._epoch_active = True
        procs, local_qs = self._procs, self._local_qs
        n = len(procs)

        def rng_spec(w):
            return ((ds.base_seed, self._COLLATE_RNG_TAG, epoch, ds.dp_rank,
                     w) if rng else None)

        for w in range(n):
            self._cmd_qs[w].put(("epoch", epoch, rng_spec(w)))
        live = list(range(n))
        served = [0] * n    # batches yielded to the consumer, per worker
        restarts = [0] * n  # deaths survived this epoch, per worker
        skip = [0] * n      # replayed batches to discard after a restart
        obs_on = obs.enabled()
        if obs_on:
            from ..observability import attribution
            stage = attribution.stage_counter()
            n_out = 0  # batches yielded this epoch: the spans' batch id
        try:
            while live:
                for w in list(live):
                    payload = None
                    while True:
                        # Timed get + liveness check against the PUMPED
                        # in-process queue (never the mp pipe itself — see
                        # _pump_worker_queue): a worker killed without
                        # enqueueing (OOM killer, segfault in native code)
                        # must be detected here, not hang the training
                        # loop forever. Batches already pumped when the
                        # worker died are a valid stream prefix and are
                        # consumed normally first.
                        try:
                            kind, payload = local_qs[w].get(
                                timeout=self._POLL_TIMEOUT_S)
                        except queue.Empty:
                            if procs[w].is_alive():
                                continue
                            self._handle_worker_death(
                                w, epoch, rng_spec(w), restarts, served,
                                skip)
                            continue
                        if kind == "pump_torn":
                            # A SIGKILL mid-put tore the queue pipe; only
                            # a dead worker excuses that.
                            if procs[w].is_alive():
                                raise RuntimeError(
                                    "loader worker {} output queue broke "
                                    "while the worker is alive".format(w))
                            self._handle_worker_death(
                                w, epoch, rng_spec(w), restarts, served,
                                skip)
                            continue
                        if kind == "batch" and skip[w] > 0:
                            skip[w] -= 1  # replayed duplicate: drop unopened
                            continue
                        break
                    if kind == "error":
                        raise RuntimeError(
                            "loader worker {} failed:\n{}".format(w, payload))
                    if kind == "end":
                        if skip[w] > 0:
                            raise RuntimeError(
                                "loader worker {} replay ended {} batch(es) "
                                "early; its stream is not reproducing "
                                "deterministically".format(w, skip[w]))
                        live.remove(w)
                        continue
                    served[w] += 1
                    self.queue_bytes += len(payload)
                    self.queue_batches += 1
                    if obs_on:
                        # IPC stage: the cross-process payload decode the
                        # thread mode never pays (queue WAIT is already
                        # covered by the batch_wait boundary upstream).
                        with obs.span("loader.ipc", batch=n_out) as span:
                            decoded = qserde.decode(payload)
                        stage.inc(span.seconds, stage="ipc")
                        n_out += 1
                        yield decoded
                    else:
                        yield qserde.decode(payload)
        finally:
            if live:
                # Failed or abandoned mid-epoch: workers are mid-stream
                # with no way to fast-forward — tear the pool down (next
                # epoch respawns it), UNLESS a newer epoch already
                # replaced this pool (a stale abandoned iterator being
                # GC'd must not kill the successor's workers).
                if self._pool_gen == gen and self._procs is not None:
                    self.shutdown_workers()
            if self._pool_gen == gen:
                self._epoch_active = False

    def __iter__(self):
        inner = (self._iter_process() if self._worker_mode == "process"
                 else self._iter_thread())
        if not obs.enabled():
            # Telemetry off: the raw iterator, zero per-batch overhead
            # (the no-op-mode guard in tests/test_observability.py holds
            # the whole loader hot path to this).
            yield from inner
            return
        yield from self._iter_instrumented(inner)

    def _iter_instrumented(self, inner):
        """Top-level loader span + per-batch latency/padding accounting.
        Each wait in the inner ``next()`` is a ``loader.batch_wait`` span;
        its duration is the batch latency the training loop actually
        experiences (prefetch included). The same span boundaries also
        feed the attribution boundary pair: ``batch_wait`` (consumer
        blocked in next()) and ``step_gap`` (consumer away between
        batches) partition the epoch wall exactly — the input-share those
        yield is what the bound verdict reads."""
        from ..observability import attribution
        watcher = _EpochObserver()
        stage = attribution.stage_counter()
        end = object()
        try:
            # Open across the yields below, so off the profiler timeline.
            with obs.span("loader.epoch", annotate=False,
                          mode=self._worker_mode,
                          batch_size=self.batch_size):
                n, back = 0, None
                while True:
                    with obs.span("loader.batch_wait", batch=n) as wait:
                        batch = next(inner, end)
                    stage.inc(wait.seconds, stage="batch_wait")
                    if back is not None:
                        stage.inc(wait.start - back, stage="step_gap")
                    if batch is end:
                        return
                    watcher.batch(batch, wait.seconds)
                    n += 1
                    yield batch
                    back = wait.start + wait.seconds
        finally:
            # Abandoned epochs still summarize what they served.
            watcher.finish()

    def attribution_snapshot(self):
        """Critical-path attribution accumulated so far in this process:
        per-stage self-time seconds, per-stage shares of the observed
        wall, and the bound verdict (input-bound / compute-bound /
        balanced). None when telemetry is off or nothing has iterated.
        Registry-wide by design — stages recorded by worker threads and
        the device prefetcher all land in the same accumulator."""
        from ..observability import attribution
        return attribution.snapshot()

    def _iter_thread(self):
        streams = self.dataset.start_epoch()
        stop = threading.Event()
        queues = [queue.Queue(maxsize=self._prefetch) for _ in streams]
        threads = [
            threading.Thread(target=self._worker_loop,
                             args=(s, q, stop, self._bind_collate(w)),
                             daemon=True)
            for w, (s, q) in enumerate(zip(streams, queues))
        ]
        for t in threads:
            t.start()
        live = list(range(len(queues)))
        try:
            while live:
                for w in list(live):
                    kind, payload = queues[w].get()
                    if kind == "error":
                        raise payload
                    if kind == "end":
                        live.remove(w)
                        continue
                    yield payload
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)


class _DevicePrefetcher:
    """Iterable produced by :func:`prefetch_to_device` (re-iterable: each
    ``iter()`` runs one epoch of the wrapped loader, like DataLoader)."""

    def __init__(self, loader, device_put, depth):
        self._loader = loader
        self._device_put = device_put
        self._depth = depth

    def __len__(self):
        return len(self._loader)

    def __iter__(self):
        stop = threading.Event()
        q = queue.Queue(maxsize=self._depth)

        def put(item):
            # Stop-aware bounded put (terminal markers included): an
            # abandoned consumer must never leave this thread blocked on
            # a full queue forever.
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        obs_on = obs.enabled()
        device_put = self._device_put
        if obs_on:
            from ..observability import attribution
            stage = attribution.stage_counter()
            batch_ids = itertools.count()

            def device_put(b, _d=self._device_put):
                # h2d stage: the dispatch cost of the transfer (the
                # transfer itself is asynchronous — overlap is the point).
                with obs.span("loader.h2d", batch=next(batch_ids)) as span:
                    out = _d(b)
                stage.inc(span.seconds, stage="h2d")
                return out

        def produce():
            try:
                for batch in self._loader:
                    # device_put dispatches the H2D transfer
                    # asynchronously; the consumer's current step overlaps
                    # with the NEXT batch's host collate + transfer.
                    if not put(("batch", device_put(batch))):
                        return
                put(("end", None))
            except BaseException as e:  # noqa: BLE001 - forwarded
                put(("error", e))

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            n, back = 0, None
            while True:
                if not obs_on:
                    kind, payload = q.get()
                else:
                    with obs.span("loader.prefetch_wait", batch=n) as wait:
                        kind, payload = q.get()
                    stage.inc(wait.seconds, stage="prefetch_wait")
                    if back is not None:
                        # The consumer was away running its step: that gap
                        # is the compute side of the outermost boundary.
                        stage.inc(wait.start - back, stage="prefetch_gap")
                    back = wait.start + wait.seconds
                    n += 1
                if kind == "error":
                    raise payload
                if kind == "end":
                    return
                yield payload
        finally:
            stop.set()
            t.join(timeout=5)


def prefetch_to_device(loader, device_put=None, depth=2):
    """Double-buffered host->device pipeline: a background thread drains
    ``loader`` and runs ``device_put`` (default ``jax.device_put``, which
    dispatches transfers asynchronously) up to ``depth`` batches ahead of
    the consumer, so host collate + H2D transfer overlap with the running
    train step instead of serializing with it.

    Pass ``device_put=lambda b: to_device_batch(b, mesh)`` to land
    globally-sharded batches on a device mesh (benchmarks/mock_train.py
    --with-model does). The wrapper is re-iterable — each ``iter()``
    advances the wrapped loader one epoch — and order-preserving, so the
    determinism contract is untouched. Telemetry (when armed): the
    ``loader.prefetch_wait`` span around the consumer's wait on the queue
    (near zero when the pipeline keeps up) and the ``loader.h2d`` span
    around each ``device_put``, each with its stage counter."""
    if device_put is None:
        import jax
        device_put = jax.device_put
    return _DevicePrefetcher(loader, device_put, max(1, depth))


class Binned:
    """One DataLoader per sequence-length bin; every iteration all ranks
    draw the same bin from the world RNG stream, weighted by remaining
    samples — identical choice with zero communication.
    (ref: lddl/torch/dataloader.py:32-91)
    """

    def __init__(self, dataloaders, base_seed=12345, start_epoch=0,
                 logger=None):
        self._dataloaders = dataloaders
        self._base_seed = base_seed
        self._epoch = start_epoch - 1
        self._logger = logger or DatasetLogger()

    def __len__(self):
        return sum(len(dl) for dl in self._dataloaders)

    @property
    def epoch(self):
        return self._epoch

    def _get_batch_size(self, batch):
        raise NotImplementedError("Binned is abstract: use a subclass that "
                                  "knows the batch structure")

    def shutdown_workers(self):
        """Stop every bin loader's persistent process workers (no-op in
        thread mode)."""
        for dl in self._dataloaders:
            dl.shutdown_workers()

    def attribution_snapshot(self):
        """Critical-path attribution + bound verdict across every bin
        (the stage accumulator is registry-wide; see
        DataLoader.attribution_snapshot)."""
        from ..observability import attribution
        return attribution.snapshot()

    def __iter__(self):
        self._epoch += 1
        # Refresh every bin dataset BEFORE sizing the epoch: the
        # remaining-sample bookkeeping below and each bin's own epoch
        # advance must agree on one file set (maybe_refresh is once per
        # epoch, so the advance inside iter(dl) will not refresh again).
        for dl in self._dataloaders:
            refresh = getattr(dl.dataset, "maybe_refresh", None)
            if refresh is not None:
                refresh()
        world_g = lrng.world_rng(self._base_seed, self._epoch)
        remaining = [len(dl.dataset) for dl in self._dataloaders]
        iters = [iter(dl) for dl in self._dataloaders]
        bin_ids = list(range(len(iters)))  # allocation-free hot loop
        obs_on = obs.enabled()
        for i in range(len(self)):
            bin_id = lrng.choices(world_g, bin_ids, weights=remaining)[0]
            self._logger.to("rank").info(
                "iteration {} selects bin {}".format(i, bin_id))
            if obs_on:
                obs.inc("loader_bin_choice_total", bin=bin_id)
            assert remaining[bin_id] > 0
            batch = next(iters[bin_id])
            remaining[bin_id] -= self._get_batch_size(batch)
            yield batch
        assert sum(remaining) == 0, (
            "bin bookkeeping out of sync: {} samples unaccounted".format(
                sum(remaining)))
        # Let each bin iterator finish NATURALLY (consume its end-of-epoch
        # marker): count-based iteration leaves generators suspended on
        # their last yield, and closing a suspended process-mode iterator
        # looks like mid-epoch abandonment — tearing down the persistent
        # worker pools every epoch.
        for it in iters:
            leftover = next(it, None)
            assert leftover is None, "bin served a batch past its count"
