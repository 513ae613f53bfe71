"""One minus the union of the device's op intervals over the traced
sub-window (averaged over the chips used)."""

UNIT = "%"
BETTER = "lower"
LAYER = "device"
MOVES = "tokens_per_s"


def read(record):
    tr = record.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
