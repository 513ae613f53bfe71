"""Host time to dispatch one train step, in the traced run's window: the
mean duration of the program's ``train_step`` spans of the window's steps
(read from its span file; the same durations feed its
``train_step_dispatch_seconds_total`` and ``train_step_dispatches_total``
counters). See ``chipbench/scopes.py``."""

UNIT = "ms"
BETTER = "lower"
LAYER = "train step"
MOVES = "tokens_per_s"


def read(record):
    from chipbench import scopes
    r = scopes.reading(record)
    if r is None:
        return None
    return r["dispatch_ms"]
