"""Share of the device's busy time spent in ops of the model's attention
scope (forward and backward: every op whose scope has an element
``attention``), over the traced sub-window, mean over the chips used.
Scopes come from the profile (``chipbench/scopes.py``)."""

UNIT = "%"
BETTER = "lower"
LAYER = "train step"
MOVES = "tokens_per_s"


def read(record):
    from chipbench import scopes
    r = scopes.reading(record)
    if r is None or "attention" not in r["layer_share"]:
        return None
    return 100.0 * r["layer_share"]["attention"]
