"""Model FLOP utilisation of the whole window: useful training FLOPs of
the steps completed in it (real lengths and masked counts only; see
``chipbench/flops.py``) over the window's seconds times the chips' bf16
peak."""

UNIT = "%"
BETTER = "higher"
LAYER = "train step"
MOVES = "tokens_per_s"


def read(record):
    if not record.get("peak_flops") or not record.get("window_useful_flops"):
        return None
    return 100.0 * record["window_useful_flops"] / (
        record["seconds"] * record["chips"] * record["peak_flops"])
