"""Span tracing: Chrome-trace-format JSONL per process, Perfetto-openable.

``span("preprocess.scatter", shard=3)`` is a context manager that records
one complete ("ph": "X") Trace Event with microsecond start/duration;
nested spans on the same thread render as a span tree in Perfetto
(https://ui.perfetto.dev — open the ``trace-*.jsonl`` file directly; the
JSON trace importer accepts newline-delimited events without the
enclosing ``[]``). ``event(...)`` records an instant ("ph": "i") event
for point occurrences (a retry, a fault injection, a worker restart).

In a process that has imported jax, an armed span is also a
``jax.profiler.TraceAnnotation`` for its duration: a profiler session
running at the time records it in its host plane, on the device trace's
clock, so program spans and device ops share one timeline. jax is never
imported here (preprocess-only processes stay free of it), and without a
session the annotation is one cheap check. A TraceMe must end on the
thread it began on, so an annotated span is never held open across a
``yield``; a span that is (a whole epoch) passes ``annotate=False`` and
goes to the JSONL only. After exit a span exposes ``start`` (its
``perf_counter`` at entry) and ``seconds`` (its duration): a stage
counter at the same site is incremented from them, so the site reads the
clock once and the counter and the span cannot disagree.

Inertness contract (same as registry.py): disabled spans are a shared
reusable null context manager (no allocation, one env lookup), enabled
spans never raise into the caller and never touch any RNG. Events buffer
in memory and append to ``<metrics_dir>/trace-rank<r>-pid<p>.jsonl`` on
``flush()`` — called by the exporter thread, at interpreter exit, and by
``mock_train``'s end-of-run report. Worker *processes* inherit the env
var and write their own per-pid file, which is what makes the
scatter/gather span tree span process boundaries.
"""

import json
import os
import sys
import threading
import time

from .registry import metrics_dir, rank

# RLock, not Lock: the SIGTERM flush handler (exporters.install_signal_
# flush) runs on the main thread between bytecodes and may interrupt a
# frame that already holds this lock mid-_push; a non-reentrant lock
# would deadlock the dying process instead of flushing it. Re-entry is
# safe: flush() only swaps the buffer out, and the interrupted append
# lands in the fresh buffer.
_lock = threading.RLock()
_buffer = []          # pending trace event dicts
_emitted_meta = set()  # pids that already wrote their process_name event
_MAX_BUFFER = 50000    # hard cap: a runaway loop must not eat the heap
_atexit_registered = []


def _now_us():
    # Wall clock so events from different PROCESSES (pool workers, loader
    # workers) land on one comparable timeline in Perfetto; durations use
    # the monotonic perf counter so a clock step cannot produce negative
    # or inflated span widths.
    return time.time() * 1e6


class Span:
    """One timed section. Use via ``span(...)``; re-entrant use of a
    single instance is not supported (make a new span instead)."""

    __slots__ = ("name", "args", "start", "seconds", "_t0", "_note")

    def __init__(self, name, args, note=None):
        self.name = name
        self.args = args
        self.start = 0.0
        self.seconds = 0.0
        self._t0 = 0.0
        self._note = note

    def __enter__(self):
        if self._note is not None:
            self._note.__enter__()
        self._t0 = _now_us()
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self.start
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        record = {
            "name": self.name,
            "ph": "X",
            "ts": self._t0,
            "dur": self.seconds * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0x7FFFFFFF,
        }
        if self.args:
            record["args"] = self.args
        if exc_type is not None:
            record.setdefault("args", {})["error"] = exc_type.__name__
        _push(record)
        return False  # never swallow pipeline exceptions


class _NullSpan:
    """Shared disabled-mode span: zero state, reusable, nestable."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


def _annotation(name, args, step=None):
    """The profiler annotation of an armed span, or None where jax is not
    imported. ``step`` makes it a ``StepTraceAnnotation``."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        if step is not None:
            return jax.profiler.StepTraceAnnotation(name, step_num=step)
        return jax.profiler.TraceAnnotation(name, **args)
    except Exception:  # noqa: BLE001 - telemetry must stay inert
        return None


def span(name, *, annotate=True, **args):
    """Context manager timing one section; a shared no-op when disabled.
    ``annotate=False`` keeps it off the profiler's timeline, for a span
    that stays open across a ``yield``."""
    if metrics_dir() is None:
        return _NULL_SPAN
    return Span(name, args, _annotation(name, args) if annotate else None)


def step_span(name, step):
    """``span(name, step=step)`` that also marks a training step for the
    profiler (``StepTraceAnnotation(name, step_num=step)``)."""
    if metrics_dir() is None:
        return _NULL_SPAN
    return Span(name, {"step": step}, _annotation(name, None, step))


def event(name, **args):
    """Record an instant event (a point in time, not a duration)."""
    if metrics_dir() is None:
        return
    record = {
        "name": name,
        "ph": "i",
        "ts": _now_us(),
        "pid": os.getpid(),
        "tid": threading.get_ident() & 0x7FFFFFFF,
        "s": "t",
    }
    if args:
        record["args"] = args
    _push(record)


def _push(record):
    try:
        with _lock:
            if len(_buffer) >= _MAX_BUFFER:
                return
            pid = record["pid"]
            if pid not in _emitted_meta:
                _emitted_meta.add(pid)
                _buffer.append({
                    "name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": "rank{} pid{}".format(rank(), pid)},
                })
            _buffer.append(record)
            if not _atexit_registered:
                _atexit_registered.append(True)
                import atexit
                atexit.register(flush)
    except Exception:  # noqa: BLE001 - telemetry must stay inert
        pass


def trace_path():
    """This process's trace file path, or None when disabled."""
    d = metrics_dir()
    if d is None:
        return None
    return os.path.join(
        d, "trace-rank{}-pid{}.jsonl".format(rank(), os.getpid()))


def flush():
    """Append buffered events to the per-process trace file. Safe to call
    any time from any thread; failures (unwritable dir, disk full) drop
    the batch rather than disturb the pipeline."""
    path = trace_path()
    with _lock:
        if not _buffer:
            return path
        batch, _buffer[:] = list(_buffer), []
    if path is None:
        return None
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "a", encoding="utf-8") as f:
            for record in batch:
                f.write(json.dumps(record) + "\n")
    except Exception:  # noqa: BLE001 - telemetry must stay inert
        pass
    return path


def pending_events():
    """Number of buffered (unflushed) events — tests and debugging."""
    with _lock:
        return len(_buffer)


def _reset_for_tests():
    with _lock:
        _buffer[:] = []
        _emitted_meta.clear()
