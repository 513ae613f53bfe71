"""Share of the device's busy time spent in ops of the program's
``optimizer`` scope (gradient norm, clipping, AdamW and the parameter
update), over the traced sub-window, mean over the chips used. Scopes
come from the profile (``chipbench/scopes.py``)."""

UNIT = "%"
BETTER = "lower"
LAYER = "train step"
MOVES = "tokens_per_s"


def read(record):
    from chipbench import scopes
    r = scopes.reading(record)
    if r is None or "optimizer" not in r["layer_share"]:
        return None
    return 100.0 * r["layer_share"]["optimizer"]
