"""Share of the window the trainer spent blocked on the loader: the
program's ``loader_stage_seconds_total`` at the device-prefetch boundary,
prefetch_wait / (prefetch_wait + prefetch_gap), over the window only.
Armed in the traced run alone."""

UNIT = "%"
BETTER = "lower"
LAYER = "loader"
MOVES = "tokens_per_s"


def read(record):
    stages = record.get("loader_stages") or {}
    wait = stages.get("prefetch_wait", 0.0)
    gap = stages.get("prefetch_gap", 0.0)
    if wait + gap <= 0:
        return None
    return 100.0 * wait / (wait + gap)
